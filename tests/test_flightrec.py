"""Pipeline flight recorder (ISSUE 3): ring-buffer bounds, record schema
stability, Prometheus label escaping, the outcome-labeled batch histogram,
gang observability counters, the /debug/schedstats surface, and the
disabled-recorder parity invariant (identical placements with the recorder
on and off — instrumentation must never steer scheduling)."""

import json
import urllib.request

from kubernetes_tpu.scheduler import Framework
from kubernetes_tpu.scheduler.batch import BatchScheduler
from kubernetes_tpu.scheduler.flightrec import (
    BATCH_STAGES,
    FlightRecorder,
    StageClock,
    schedstats_snapshot,
)
from kubernetes_tpu.scheduler.plugins import default_plugins
from kubernetes_tpu.server import metrics as m
from kubernetes_tpu.store import APIStore
from kubernetes_tpu.testing import MakeNode, MakePod, make_pod_group
from kubernetes_tpu.utils import FakeClock


def _nodes(n, cpu="8", mem="32Gi"):
    return [MakeNode(f"node-{i}").capacity(
        {"cpu": cpu, "memory": mem, "pods": "110"}).obj() for i in range(n)]


def _sched(store, solver="fast", **kw):
    sched = BatchScheduler(store, Framework(default_plugins()),
                           batch_size=1024, solver=solver,
                           pipeline_binds=False, **kw)
    sched.sync()
    return sched


def _placements(store):
    return {p.metadata.name: p.spec.node_name
            for p in store.list("pods")[0] if p.spec.node_name}


# -- FlightRecorder unit surface -----------------------------------------------


def _mk_record(fr, seq_pods=1):
    return fr.record(pods=seq_pods, nodes=2, outcome="scheduled",
                     solver="fast", stages={"solve": 0.01}, total_s=0.02)


class TestRingBuffer:
    def test_capacity_bound_evicts_oldest(self):
        fr = FlightRecorder(capacity=4)
        for i in range(10):
            _mk_record(fr, seq_pods=i)
        assert len(fr) == 4
        recs = fr.records()
        assert [r["seq"] for r in recs] == [7, 8, 9, 10]
        assert fr.last()["seq"] == 10

    def test_aggregates_survive_eviction(self):
        fr = FlightRecorder(capacity=2)
        for _ in range(5):
            _mk_record(fr)
        # stage table covers ALL 5 batches, not just the 2 still in the ring
        table = fr.stage_table()
        assert table["solve"]["batches"] == 5
        assert abs(table["solve"]["total_ms"] - 50.0) < 1e-6

    def test_disabled_recorder_records_nothing(self):
        fr = FlightRecorder(enabled=False)
        assert _mk_record(fr) is None
        fr.add_outside("bind", 1.0)
        assert len(fr) == 0
        assert fr.stage_table() == {}

    def test_outside_buckets_and_overlap_flag(self):
        fr = FlightRecorder()
        _mk_record(fr)
        fr.add_outside("bind", 0.5)
        fr.add_outside("bind_wait", 0.25)
        table = fr.stage_table()
        assert table["bind"]["overlapped"] is True
        assert table["bind_wait"]["overlapped"] is False
        assert abs(table["bind"]["total_ms"] - 500.0) < 1e-6
        assert fr.outside_seconds("bind", "bind_wait") == 0.75

    def test_clear_resets_everything(self):
        fr = FlightRecorder()
        _mk_record(fr)
        fr.add_outside("bind", 0.5)
        fr.note_self_time(0.1)
        fr.clear()
        assert len(fr) == 0 and fr.stage_table() == {}
        assert fr.self_seconds == 0.0


class TestStageClock:
    def test_marks_are_disjoint_and_sum_to_total(self):
        clock = StageClock("a")
        clock.enter("b")
        clock.enter("x")
        clock.drop("c")  # x is left out of the table
        clock.enter(None)
        total = clock.total()
        clock.finish()
        assert set(clock.stages) == {"a", "b", "c"}
        assert sum(clock.stages.values()) <= total
        # each closed stage keeps its boundaries, in order and disjoint
        assert [b[0] for b in clock.bounds] == ["a", "b", "c"]
        for (_n, t0, t1), (_m, u0, _u1) in zip(clock.bounds,
                                                clock.bounds[1:]):
            assert t0 <= t1 <= u0

    def test_sub_floors_at_zero(self):
        clock = StageClock("a")
        clock.enter(None)
        clock.sub("a", 10.0)
        clock.finish()
        assert clock.stages["a"] == 0.0


# -- record schema (the contract bench.py and ktl render from) ------------------

RECORD_KEYS = {"seq", "ts", "pods", "nodes", "outcome", "solver", "total_ms",
               "stages", "scheduled", "unschedulable", "fallback",
               "preempted", "reasons", "gang", "repair", "solver_iterations",
               "breaker", "error", "bind_failures", "parts_ms", "compile_ms",
               "compiles", "gc_ms", "gc_collections", "upload"}


class TestRecordSchema:
    def test_live_batch_record_schema(self):
        store = APIStore()
        for n in _nodes(4):
            store.create("nodes", n)
        sched = _sched(store)
        store.create_many("pods", [MakePod(f"p-{i}").req(
            {"cpu": "100m"}).obj() for i in range(6)], consume=True)
        sched.run_until_idle()
        rec = sched.flightrec.last()
        assert set(rec) == RECORD_KEYS
        assert rec["outcome"] == "scheduled"
        assert rec["pods"] == 6 and rec["nodes"] == 4
        assert rec["scheduled"] == 6 and rec["unschedulable"] == 0
        assert rec["stages"] and all(
            isinstance(v, float) and v >= 0 for v in rec["stages"].values())
        assert set(rec["stages"]) <= set(BATCH_STAGES)
        # the big serial stages are all present for a real solved batch
        for stage in ("ingest", "pop", "tensorize", "build_pod_batch",
                      "solve", "assume", "dispatch"):
            assert stage in rec["stages"], stage

    def test_unschedulable_batch_attributes_reasons(self):
        store = APIStore()
        store.create("nodes", MakeNode("n0").capacity(
            {"cpu": "1", "memory": "1Gi", "pods": "10"}).obj())
        sched = _sched(store)
        store.create("pods", MakePod("huge").req({"cpu": "64"}).obj())
        sched.schedule_batch(timeout=0.0)
        rec = sched.flightrec.last()
        assert rec["outcome"] == "unschedulable"
        assert rec["unschedulable"] == 1
        assert sum(rec["reasons"].values()) == 1
        assert "NodeResourcesFit" in rec["reasons"]

    def test_no_nodes_batch_records_unschedulable(self):
        store = APIStore()
        sched = _sched(store)
        store.create("pods", MakePod("p").req({"cpu": "1"}).obj())
        before = m.batch_solve_duration.child("unschedulable").snapshot()[1]
        sched.schedule_batch(timeout=0.0)
        rec = sched.flightrec.last()
        assert rec is not None and rec["outcome"] == "unschedulable"
        assert rec["nodes"] == 0
        # the satellite fix: the early-return path now observes the
        # outcome-labeled batch_solve_duration histogram
        after = m.batch_solve_duration.child("unschedulable").snapshot()[1]
        assert after == before + 1

    def test_empty_pop_records_no_batch(self):
        store = APIStore()
        for n in _nodes(2):
            store.create("nodes", n)
        sched = _sched(store)
        sched.schedule_batch(timeout=0.0)
        assert sched.flightrec.last() is None


# -- Prometheus text exposition escaping ----------------------------------------


class TestLabelEscaping:
    def test_counter_escapes_quotes_backslashes_newlines(self):
        c = m.Counter("test_escape_total", "h")
        c.inc(pod='we"ird\\name\nx')
        line = [ln for ln in c.render() if not ln.startswith("#")][0]
        assert line == 'test_escape_total{pod="we\\"ird\\\\name\\nx"} 1.0'

    def test_labeled_histogram_escapes_label(self):
        h = m.LabeledHistogram("test_hist_seconds", "h", label="stage",
                               buckets=(1,))
        h.observe(0.5, 'a"b\\c')
        lines = h.render()
        assert any('stage="a\\"b\\\\c"' in ln for ln in lines)
        # exposition shape: HELP/TYPE once, then buckets/sum/count per child
        assert lines[0].startswith("# HELP") and lines[1].startswith("# TYPE")
        assert any("test_hist_seconds_count" in ln for ln in lines)

    def test_registry_render_roundtrips(self):
        reg = m.Registry()
        c = reg.counter("a_total")
        c.inc(x="1")
        g = reg.gauge("b")
        g.set(2.0)
        h = reg.labeled_histogram("c_seconds", label="stage", buckets=(1,))
        h.observe(0.1, "s")
        text = reg.render()
        assert 'a_total{x="1"} 1.0' in text
        assert "b 2.0" in text
        assert 'c_seconds_bucket{stage="s",le="1"} 1' in text


# -- gang observability ---------------------------------------------------------


class TestGangCounters:
    def test_orphan_release_increments_counter(self):
        clock = FakeClock()
        store = APIStore()
        for n in _nodes(4):
            store.create("nodes", n)
        sched = BatchScheduler(store, Framework(default_plugins()),
                               batch_size=1024, solver="fast",
                               pipeline_binds=False, clock=clock)
        sched.sync()
        store.create("podgroups", make_pod_group("doomed", 3))
        store.create("podgroups", make_pod_group("other", 2))
        store.create_many("pods", [
            MakePod(f"g-{i}").gang("doomed").req({"cpu": "100m"}).obj()
            for i in range(2)])
        sched.pump_events()
        assert sched.queue.gang_staged_count() == 2
        store.delete("podgroups", "default/doomed")
        sched.pump_events()
        before = m.gang_orphan_released_total.value()
        clock.step(31.0)
        sched.queue.flush_unschedulable_left_over()
        assert m.gang_orphan_released_total.value() == before + 2

    def test_gang_veto_counter_and_record(self):
        store = APIStore()
        # 2 nodes x 1 cpu: a 3-member gang needing 1cpu each can never place
        for n in _nodes(2, cpu="1"):
            store.create("nodes", n)
        sched = _sched(store)
        store.create("podgroups", make_pod_group("big", 3))
        store.create_many("pods", [
            MakePod(f"g-{i}").gang("big").req({"cpu": "800m"}).obj()
            for i in range(3)])
        before = m.gang_vetoed_total.value(reason="solver")
        sched.schedule_batch(timeout=0.0)
        assert m.gang_vetoed_total.value(reason="solver") == before + 1
        rec = sched.flightrec.last()
        assert rec["gang"] is not None and rec["gang"]["vetoed"] == 1
        assert rec["reasons"].get("GangScheduling") == 3

    def test_quorum_expired_assumes_measurable(self):
        from kubernetes_tpu.scheduler.gang import GangDirectory

        gd = GangDirectory()
        gd.observe_podgroup("ADDED", make_pod_group("t", 2))
        p = MakePod("r0").gang("t").obj()
        gd.note_assumed(p)
        # cache no longer knows the pod (assume expired): the leak is counted
        assert gd.quorum_expired_count(lambda key: False) == 1
        assert gd.quorum_expired_count(lambda key: True) == 0


# -- parity: the recorder must never steer placement ----------------------------


class TestRecorderParity:
    def test_disabled_recorder_identical_placements(self):
        def run(flight_recorder):
            store = APIStore()
            for n in _nodes(6):
                store.create("nodes", n)
            sched = _sched(store, flight_recorder=flight_recorder)
            store.create_many("pods", [
                MakePod(f"p-{i}").req(
                    {"cpu": "500m", "memory": "1Gi"}).obj()
                for i in range(40)], consume=True)
            sched.run_until_idle()
            return _placements(store), sched

        on_placed, on_sched = run(True)
        off_placed, off_sched = run(False)
        assert len(on_placed) == 40
        assert on_placed == off_placed
        assert len(on_sched.flightrec) > 0
        assert len(off_sched.flightrec) == 0
        assert off_sched.sched_stats()["recorder"]["enabled"] is False


# -- the HTTP + registry surface ------------------------------------------------


class TestSchedStatsSurface:
    def test_registry_snapshot_and_http_endpoint(self):
        from kubernetes_tpu.server import APIServer

        store = APIStore()
        srv = APIServer(store).start()
        try:
            for n in _nodes(3):
                store.create("nodes", n)
            sched = _sched(store)
            store.create_many("pods", [MakePod(f"p-{i}").req(
                {"cpu": "100m"}).obj() for i in range(5)], consume=True)
            sched.run_until_idle()
            name = sched._bind_origin
            snap = schedstats_snapshot()
            assert name in snap
            assert snap[name]["scheduled"] == 5
            assert "solve" in snap[name]["stages"]
            with urllib.request.urlopen(
                    f"{srv.url}/debug/schedstats") as resp:
                payload = json.loads(resp.read())
            assert name in payload
            assert payload[name]["batches_solved"] >= 1
            assert payload[name]["last_batch"]["outcome"] == "scheduled"
        finally:
            srv.stop()

    def test_ktl_sched_stats_renders_table(self):
        import io
        from contextlib import redirect_stdout

        from kubernetes_tpu.cli.ktl import main as ktl_main
        from kubernetes_tpu.server import APIServer

        store = APIStore()
        srv = APIServer(store).start()
        try:
            for n in _nodes(3):
                store.create("nodes", n)
            sched = _sched(store)
            store.create_many("pods", [MakePod(f"p-{i}").req(
                {"cpu": "100m"}).obj() for i in range(5)], consume=True)
            sched.run_until_idle()
            buf = io.StringIO()
            with redirect_stdout(buf):
                assert ktl_main(["--server", srv.url, "sched", "stats"]) == 0
            out = buf.getvalue()
            assert "STAGE" in out and "solve" in out
            assert sched._bind_origin in out
            buf = io.StringIO()
            with redirect_stdout(buf):
                assert ktl_main(["--server", srv.url, "sched", "stats",
                                 "-o", "json"]) == 0
            doc = json.loads(buf.getvalue())
            assert sched._bind_origin in doc
        finally:
            srv.stop()
