"""Batch-stage spans on the profiler's clock (obs/recorder.py StageClock):
each stage of schedule_batch is a TraceMe span `sched.<stage>` inside one
`sched.batch`, the solve stage splits into `solve.upload` / `solve.kernel` /
`solve.readback` / `solve.host` parts that sum to it, and each flight
record carries the XLA compiles and garbage-collection pauses of its batch
(obs/gcpause.py)."""

import gc
import glob
import os

import jax
import jax.numpy as jnp
import pytest

from kubernetes_tpu.obs import gcpause
from kubernetes_tpu.obs.recorder import StageClock
from kubernetes_tpu.scheduler import Framework
from kubernetes_tpu.scheduler import batch as batch_mod
from kubernetes_tpu.scheduler.batch import BatchScheduler
from kubernetes_tpu.scheduler.plugins import default_plugins
from kubernetes_tpu.store import APIStore
from kubernetes_tpu.testing import MakeNode, MakePod

SOLVE_PARTS = ("solve.upload", "solve.kernel", "solve.readback", "solve.host")


def _cluster(n_nodes=4, cpu="8"):
    store = APIStore()
    for i in range(n_nodes):
        store.create("nodes", MakeNode(f"node-{i}").capacity(
            {"cpu": cpu, "memory": "32Gi", "pods": "110"}).obj())
    sched = BatchScheduler(store, Framework(default_plugins()),
                           batch_size=1024, solver="fast",
                           pipeline_binds=False)
    sched.sync()
    return store, sched


def _create(store, n, cpu="100m", prefix="p"):
    store.create_many("pods", [MakePod(f"{prefix}-{i}").req(
        {"cpu": cpu}).obj() for i in range(n)], consume=True)


def _during_upload(sched, fn):
    """Run fn() inside the solve stage's upload part of the next batches."""
    tc = sched._tensor_cache
    orig = tc.device_views

    def views(cluster):
        fn()
        return orig(cluster)

    tc.device_views = views


def _trace_events(logdir):
    """{(plane, line index): [(name, start_ns, end_ns)]} of every host
    line."""
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host"):
            continue
        for i, line in enumerate(plane.lines):
            out[(plane.name, i)] = [
                (e.name, e.start_ns, e.start_ns + e.duration_ns)
                for e in line.events]
    return out


def _traced(tmp_path, fn):
    jax.profiler.start_trace(str(tmp_path))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    return _trace_events(str(tmp_path))


def _sched_line(lines):
    found = [evs for evs in lines.values()
             if any(n == "sched.batch" for n, _s, _e in evs)]
    assert len(found) == 1, "sched.batch on one host line"
    return found[0]


def _within(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def test_stage_and_part_spans_nest_on_the_scheduling_threads_line(tmp_path):
    store, sched = _cluster()
    _create(store, 24)
    sched.pump_events()
    lines = _traced(tmp_path, lambda: sched.schedule_batch(timeout=0.0))
    evs = _sched_line(lines)
    batches = [e for e in evs if e[0] == "sched.batch"]
    assert len(batches) == 1
    names = {n for n, _s, _e in evs}
    for stage in ("ingest", "pop", "tensorize", "build_pod_batch", "solve",
                  "assume", "dispatch"):
        assert "sched." + stage in names, stage
    for p in SOLVE_PARTS:
        assert p in names, p
    for e in evs:
        if e[0].startswith("sched.") and e[0] != "sched.bind":
            assert _within(e, batches[0]), e
    solve = next(e for e in evs if e[0] == "sched.solve")
    for e in evs:
        if e[0].startswith("solve."):
            assert _within(e, solve), e


def test_parts_sum_to_solve():
    store, sched = _cluster()
    _create(store, 40)
    sched.run_until_idle()
    recs = [r for r in sched.flightrec.records() if "solve" in r["stages"]]
    assert recs
    for r in recs:
        assert set(r["parts_ms"]) <= set(SOLVE_PARTS)
        assert {"solve.upload", "solve.kernel"} <= set(r["parts_ms"])
        assert sum(r["parts_ms"].values()) == pytest.approx(
            r["stages"]["solve"], abs=0.01 * len(r["parts_ms"]))


def test_compile_in_upload_lands_in_that_part():
    store, sched = _cluster()
    # a new function object: its first call must compile
    _during_upload(sched, lambda: jax.jit(lambda x: x * 3 + 1)(
        jnp.ones((7, 3))).block_until_ready())
    _create(store, 8)
    sched.run_until_idle()
    rec = next(r for r in sched.flightrec.records()
               if "solve" in r["stages"])
    assert rec["compiles"] >= 1
    assert rec["compile_ms"]["solve.upload"] > 0
    # a stage's entry holds its parts' compile time
    assert rec["compile_ms"]["solve"] >= rec["compile_ms"]["solve.upload"]
    assert rec["compile_ms"]["solve"] <= rec["stages"]["solve"] + 0.01


def test_full_collection_in_batch_counts_and_spans(tmp_path):
    store, sched = _cluster()
    _during_upload(sched, gc.collect)
    _create(store, 8)
    sched.pump_events()
    lines = _traced(tmp_path, lambda: sched.schedule_batch(timeout=0.0))
    rec = sched.flightrec.last()
    assert rec["gc_ms"] > 0
    assert rec["gc_collections"][2] >= 1
    evs = _sched_line(lines)
    full = [e for e in evs if e[0] == gcpause.FULL_GC_SPAN]
    assert full
    upload = next(e for e in evs if e[0] == "solve.upload")
    assert any(_within(e, upload) for e in full)


class _KeptClocks(StageClock):
    made: list = []

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        _KeptClocks.made.append(self)


@pytest.mark.parametrize("case,expect", [
    # placed pods and one that fits nowhere
    ("mixed", ["ingest", "pop", "tensorize", "build_pod_batch", "solve",
               "assume", "dispatch", "reject"]),
    # nothing placed: the stretch after solve is the rejects' stage
    ("none_placed", ["ingest", "pop", "tensorize", "build_pod_batch",
                     "solve", "reject"]),
    ("all_placed", ["ingest", "pop", "tensorize", "build_pod_batch", "solve",
                    "assume", "dispatch"]),
])
def test_stage_table_matches_the_marks(monkeypatch, case, expect):
    """Each stage still runs from the previous boundary to its own, as the
    old end-of-stage marks attributed it: the stages come in pipeline order,
    abut, and each one's time is its boundaries' width (less the sub-stages
    another bucket claims: queue_add in ingest, synchronous binds in
    dispatch)."""
    monkeypatch.setattr(batch_mod, "StageClock", _KeptClocks)
    _KeptClocks.made = []
    store, sched = _cluster(n_nodes=2, cpu="1")
    if case in ("mixed", "all_placed"):
        _create(store, 4, cpu="100m")
    if case in ("mixed", "none_placed"):
        _create(store, 1, cpu="64", prefix="huge")
    sched.pump_events()
    assert sched.schedule_batch(timeout=0.0) > 0
    clock = _KeptClocks.made[-1]
    names = [b[0] for b in clock.bounds]
    assert names == expect
    assert list(sched.flightrec.last()["stages"]) == expect
    for (_n, _b0, b1), (_m, c0, _c1) in zip(clock.bounds, clock.bounds[1:]):
        assert c0 == b1
    for name, b0, b1 in clock.bounds:
        if name in ("ingest", "dispatch"):
            assert clock.stages[name] <= b1 - b0
        else:
            assert clock.stages[name] == b1 - b0
