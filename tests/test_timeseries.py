"""Steady-state telemetry (ISSUE 13): windowed time-series math (quantiles,
rotation under 3x-capacity churn, probes), trend/slope/drift gates on known
series, the resource/GIL sampler (per-thread CPU attribution, honesty
flags, gc pauses), sampler on/off placement parity (both watch_coalesce
modes, mutation detector forced), the leak-detector proof (the PR-11
parked-bind-worker heap pin caught by the trend gate, passing once
released), ring=true subscription pins for observability consumers, and
the /debug/timeseries + `ktl sched top` surfaces."""

import gc
import io
import json
import threading
import time
import urllib.request
from contextlib import redirect_stdout
from types import SimpleNamespace

import pytest

from kubernetes_tpu.obs.resource import (ResourceSampler, probe_thread_clock,
                                         read_thread_cpu_s)
from kubernetes_tpu.obs.timeseries import (TimeSeriesRecorder, drift_ratio,
                                           fit_slope)
from kubernetes_tpu.scheduler import Framework
from kubernetes_tpu.scheduler.batch import BatchScheduler
from kubernetes_tpu.scheduler.flightrec import timeseries_snapshot
from kubernetes_tpu.scheduler.plugins import default_plugins
from kubernetes_tpu.scheduler.slo import (SOAK_SLO, TREND_MIN_WINDOWS,
                                          evaluate_slo)
from kubernetes_tpu.store import APIStore
from kubernetes_tpu.testing import MakeNode, MakePod, mutation_detector_guard


@pytest.fixture(autouse=True)
def _force_mutation_detector(monkeypatch):
    """The PR 4 CI pattern: every store this module builds runs with the
    mutation detector FORCE-ENABLED and checked at teardown — the sampler
    and window probes read live scheduler/store state and must never
    mutate it."""
    yield from mutation_detector_guard(monkeypatch)


def _nodes(n, cpu="8", mem="32Gi"):
    return [MakeNode(f"node-{i}").capacity(
        {"cpu": cpu, "memory": mem, "pods": "110"}).obj() for i in range(n)]


def _pods(n, prefix="p", cpu="100m", mem="128Mi"):
    return [MakePod(f"{prefix}-{i}").req({"cpu": cpu, "memory": mem}).obj()
            for i in range(n)]


def _sched(store, **kw):
    kw.setdefault("batch_size", 1024)
    kw.setdefault("solver", "exact")
    kw.setdefault("pipeline_binds", False)
    sched = BatchScheduler(store, Framework(default_plugins()), **kw)
    sched.sync()
    return sched


# -- windowed time-series core ---------------------------------------------------


class TestTimeSeriesRecorder:
    def test_windows_settle_nearest_rank_quantiles(self):
        # 100 batches in ONE window with solve = 1..100 ms: nearest-rank
        # p50/p99 over the window's per-batch samples are EXACT
        ts = TimeSeriesRecorder(window_s=10.0)
        for i in range(1, 101):
            ts.note_batch({"solve": i / 1000.0}, pods=1, scheduled=1,
                          now=100.0 + i * 0.01)
        ts.note_batch({}, now=200.0)  # next window: closes the first
        w = ts.windows()[0]
        assert w["batches"] == 100
        row = w["stages"]["solve"]
        assert row["p50_ms"] == 50.0
        assert row["p99_ms"] == 99.0
        assert row["total_ms"] == pytest.approx(5050.0, abs=0.5)
        assert row["batches"] == 100

    def test_pods_per_sec_and_counts(self):
        ts = TimeSeriesRecorder(window_s=2.0)
        ts.note_batch({"solve": 0.001}, pods=100, scheduled=90, failed=10,
                      now=50.0)
        ts.note_batch({"solve": 0.001}, pods=50, scheduled=50, now=51.0)
        ts.note_batch({}, now=53.0)
        w = ts.windows()[0]
        assert (w["pods"], w["scheduled"], w["failed"]) == (150, 140, 10)
        assert w["pods_per_sec"] == pytest.approx(140 / 2.0, rel=0.01)

    def test_ring_bounded_under_3x_capacity_churn(self):
        # 3x capacity worth of windows: the ring keeps the newest CAPACITY,
        # seq stays monotonic, nothing leaks
        # the read closes the last window on the recorder's clock, which the
        # stamps below live on (not the host's perf_counter)
        now = [2000.0]
        ts = TimeSeriesRecorder(window_s=1.0, capacity=8,
                                clock=lambda: now[0])
        for i in range(24):
            ts.note_batch({"solve": 0.001}, pods=1, now=1000.0 + i)
        ts.note_batch({}, now=2000.0)
        now[0] = 2001.0
        ws = ts.windows()
        assert len(ws) == 8
        assert ts.windows_closed == 25  # 24 churn + the 2000.0 stale close
        seqs = [w["seq"] for w in ws]
        assert seqs == sorted(seqs) and seqs[-1] >= 24

    def test_idle_gap_emits_no_fabricated_windows(self):
        now = [500.0]
        ts = TimeSeriesRecorder(window_s=1.0, clock=lambda: now[0])
        ts.note_batch({"solve": 0.001}, now=10.0)
        ts.note_batch({"solve": 0.001}, now=500.0)  # long idle gap
        now[0] = 501.5
        ws = ts.windows()  # the read closes the open window (its clock)
        assert len(ws) == 2  # one per ACTIVE period, no empty filler
        assert ws[1]["start_ts"] == 500.0  # fresh epoch AT the batch
        assert all(w["batches"] == 1 for w in ws)

    def test_note_stage_outside_bucket_joins_window(self):
        ts = TimeSeriesRecorder(window_s=5.0)
        ts.note_batch({"solve": 0.002}, pods=1, now=10.0)
        ts.note_stage("bind", 0.004, now=11.0)
        ts.note_stage("bind", 0.008, now=12.0)
        ts.note_batch({}, now=20.0)
        w = ts.windows()[0]
        assert w["stages"]["bind"]["batches"] == 2
        assert w["stages"]["bind"]["total_ms"] == pytest.approx(12.0, abs=0.5)
        assert w["batches"] == 1  # outside taps don't count as batches

    def test_probes_fire_once_per_close_and_failures_skip(self):
        ts = TimeSeriesRecorder(window_s=1.0)
        calls = []

        def probe():
            calls.append(1)
            return {"depth": len(calls)}

        def bad_probe():
            raise RuntimeError("wedged")

        ts.add_probe("queue", probe)
        ts.add_probe("broken", bad_probe)
        for i in range(3):
            ts.note_batch({"solve": 0.001}, now=100.0 + i)
        ts.note_batch({}, now=200.0)
        ws = ts.windows()
        assert len(calls) == len(ws)
        assert ws[0]["queue"] == {"depth": 1}
        assert all("broken" not in w for w in ws)

    def test_series_path_extraction_skips_missing(self):
        ts = TimeSeriesRecorder(window_s=1.0)
        probe_val = {"rss_mb": None}
        ts.add_probe("resource",
                     lambda: ({"rss_mb": probe_val["rss_mb"]}
                              if probe_val["rss_mb"] is not None else None))
        ts.note_batch({"solve": 0.001}, now=10.0)
        probe_val["rss_mb"] = 100.0
        ts.note_batch({"solve": 0.001}, now=11.0)
        probe_val["rss_mb"] = None  # this window contributes NO resource
        ts.note_batch({"solve": 0.001}, now=12.0)
        ts.note_batch({}, now=100.0)
        pts = ts.series("resource", "rss_mb")
        assert len(pts) == 1 and pts[0][1] == 100.0
        assert len(ts.series("stages", "solve", "p99_ms")) == 3

    def test_clear_resets_everything(self):
        ts = TimeSeriesRecorder(window_s=1.0)
        ts.note_batch({"solve": 0.001}, now=10.0)
        ts.note_batch({}, now=20.0)
        assert ts.windows()
        ts.clear()
        assert ts.windows_closed == 0
        assert ts.self_seconds == 0.0
        assert ts.windows() == []

    def test_windows_close_stale_open_window_on_read(self):
        ts = TimeSeriesRecorder(window_s=0.01)
        ts.note_batch({"solve": 0.001}, pods=3)
        time.sleep(0.03)
        ws = ts.windows()  # read-side settle: no second batch needed
        assert len(ws) == 1 and ws[0]["pods"] == 3

    def test_disabled_recorder_is_inert(self):
        ts = TimeSeriesRecorder(window_s=0.01, enabled=False)
        ts.note_batch({"solve": 0.001}, now=10.0)
        ts.note_stage("bind", 0.001, now=11.0)
        assert ts.windows() == []
        assert ts.self_seconds == 0.0

    def test_self_time_accrues_and_bills_sink(self):
        sink_total = []

        class Sink:
            def note_self_time(self, s):
                sink_total.append(s)

        ts = TimeSeriesRecorder(window_s=1.0, stat_sink=Sink())
        for i in range(50):
            ts.note_batch({"solve": 0.001}, now=10.0 + i * 0.01)
        assert ts.self_seconds > 0
        assert sum(sink_total) == pytest.approx(ts.self_seconds, rel=0.01)


# -- trend math on known series --------------------------------------------------


class TestTrendMath:
    def test_fit_slope_exact_line(self):
        assert fit_slope([(i, 3.0 * i + 7) for i in range(10)]) == \
            pytest.approx(3.0)

    def test_fit_slope_flat_and_degenerate(self):
        assert fit_slope([(i, 42.0) for i in range(5)]) == pytest.approx(0.0)
        assert fit_slope([(0.0, 1.0)]) is None
        assert fit_slope([]) is None
        assert fit_slope([(5.0, 1.0), (5.0, 9.0)]) is None  # one timestamp

    def test_fit_slope_noisy_line(self):
        pts = [(i, 2.0 * i + (1 if i % 2 else -1)) for i in range(50)]
        assert fit_slope(pts) == pytest.approx(2.0, abs=0.05)

    def test_drift_ratio_flat_grow_short(self):
        assert drift_ratio([5.0] * 9) == pytest.approx(1.0)
        assert drift_ratio([float(i) for i in range(1, 10)]) == \
            pytest.approx(8.0 / 2.0)
        assert drift_ratio([1.0, 2.0]) is None
        assert drift_ratio([0.0, 0.0, 0.0]) is None  # zero first third

    def test_drift_ratio_median_absorbs_one_spike(self):
        # one co-scheduling stall in the tail third must not fake a drift
        flat = [10.0] * 12
        flat[-1] = 500.0
        assert drift_ratio(flat) == pytest.approx(1.0)


# -- the windowed SLO gates ------------------------------------------------------


def _mk_windows(n, rss=None, alloc=None, p99=None, t0=1000.0, dt=5.0):
    out = []
    for i in range(n):
        w = {"end_ts": t0 + i * dt, "stages": {}, "resource": {}}
        if p99 is not None:
            w["stages"]["solve"] = {"p99_ms": p99[i]}
        if rss is not None:
            w["resource"]["rss_mb"] = rss[i]
        if alloc is not None:
            w["resource"]["alloc_blocks"] = alloc[i]
        out.append(w)
    return out


class TestTrendGates:
    def test_per_window_ceiling_fails_on_worst_window(self):
        # whole-run aggregate would absorb one stalled window; the windowed
        # key must not
        wins = _mk_windows(10, p99=[100.0] * 9 + [9000.0])
        res = evaluate_slo({"windows": wins},
                           {"stage_p99_ms_per_window": {"solve": 5000.0}})
        assert res["failed"] == ["stage_p99_ms_per_window:solve"]
        checks = {c["name"]: c for c in res["checks"]}
        assert checks["stage_p99_ms_per_window:solve"]["actual"] == 9000.0

    def test_rss_slope_gate_pass_flat_fail_growing(self):
        flat = _mk_windows(12, rss=[500.0 + (i % 2) * 0.5 for i in range(12)])
        grow = _mk_windows(12, rss=[500.0 + 10.0 * i for i in range(12)])
        spec = {"rss_slope_mb_per_min": 30.0}
        assert evaluate_slo({"windows": flat}, spec)["pass"] is True
        res = evaluate_slo({"windows": grow}, spec)
        # 10 MB per 5s window = 120 MB/min
        assert res["failed"] == ["rss_slope_mb_per_min"]
        actual = res["checks"][0]["actual"]
        assert actual == pytest.approx(120.0, rel=0.05)

    def test_alloc_block_slope_gate(self):
        grow = _mk_windows(
            12, alloc=[10**6 + 200_000 * i for i in range(12)])
        res = evaluate_slo({"windows": grow},
                           {"alloc_block_slope_per_s": 10_000.0})
        assert res["failed"] == ["alloc_block_slope_per_s"]
        assert res["checks"][0]["actual"] == pytest.approx(40_000.0,
                                                           rel=0.05)

    def test_trend_checks_skip_under_min_windows(self):
        wins = _mk_windows(TREND_MIN_WINDOWS - 1,
                           rss=[500.0] * (TREND_MIN_WINDOWS - 1),
                           alloc=[1] * (TREND_MIN_WINDOWS - 1),
                           p99=[1e9] * (TREND_MIN_WINDOWS - 1))
        res = evaluate_slo({"windows": wins}, {
            "rss_slope_mb_per_min": 30.0,
            "alloc_block_slope_per_s": 1.0,
            "p99_drift_ratio": 2.0})
        # unavailable trend = reported SKIP, never a silent pass — but the
        # per-window ceiling still sees the windows it has
        assert set(res["skipped"]) == {"rss_slope_mb_per_min",
                                       "alloc_block_slope_per_s",
                                       "p99_drift_ratio"}
        assert res["pass"] is True

    def test_drift_gate_fails_on_creep_ignores_submillisecond(self):
        creep = _mk_windows(12, p99=[10.0 * (1.3 ** i) for i in range(12)])
        res = evaluate_slo({"windows": creep}, {"p99_drift_ratio": 3.0})
        assert res["failed"] == ["p99_drift_ratio"]
        # the same creep entirely below 1ms is noise, not regression: the
        # check reports SKIP (no qualifying stage), never a false FAIL
        tiny = _mk_windows(12, p99=[0.01 * (1.3 ** i) for i in range(12)])
        res2 = evaluate_slo({"windows": tiny}, {"p99_drift_ratio": 3.0})
        assert res2["skipped"] == ["p99_drift_ratio"]

    def test_soak_spec_keys_are_known(self):
        # a typo in SOAK_SLO itself would FAIL loudly via unknown_spec_key
        res = evaluate_slo({"windows": _mk_windows(
            12, rss=[1.0] * 12, alloc=[1] * 12, p99=[1.0] * 12)}, SOAK_SLO)
        assert not any(c["name"].startswith("unknown_spec_key")
                       for c in res["checks"])

    def test_no_windows_section_skips_all_trends(self):
        res = evaluate_slo({}, {"rss_slope_mb_per_min": 30.0,
                                "p99_drift_ratio": 2.0,
                                "stage_p99_ms_per_window": {"solve": 1.0}})
        assert res["pass"] is True
        assert len(res["skipped"]) == 3


# -- the resource / GIL sampler --------------------------------------------------


class TestResourceSampler:
    def test_sample_once_fields(self):
        s = ResourceSampler(interval_s=0.1)
        rec = s.sample_once()
        assert rec["rss_mb"] > 0
        assert rec["alloc_blocks"] > 0
        assert len(rec["gc"]["gen_counts"]) == 3
        assert rec["process_cpu_s"] > 0
        assert s.samples_taken == 1
        assert s.self_seconds > 0

    def test_honesty_flags_published(self):
        s = ResourceSampler(interval_s=0.1)
        summ = s.summary()
        assert summ["clock_source"] in ("clockid", "schedstat",
                                        "unavailable")
        if summ["clock_source"] != "unavailable":
            # the resolution is MEASURED (clock_getres lies on some
            # containers), and published right next to the cpu columns
            assert summ["clock_resolution_s"] is None or \
                summ["clock_resolution_s"] > 0
        assert "overhead_frac" in summ

    def test_thread_cpu_attribution(self):
        probe = probe_thread_clock()
        if probe["source"] == "unavailable":
            pytest.skip("no per-thread CPU clock on this platform")
        s = ResourceSampler(interval_s=0.05)
        stop = threading.Event()

        def spin():
            while not stop.is_set():
                sum(range(1000))

        t = threading.Thread(target=spin, daemon=True)
        t.start()
        s.register_thread("spin", t)
        s.register_thread("idle")  # this thread: sleeps through the window
        s.sample_once()
        deadline = time.perf_counter() + 2.0
        spin_cpu = 0.0
        while time.perf_counter() < deadline:
            time.sleep(0.05)
            rec = s.sample_once()
            spin_cpu = rec["threads"].get("spin", {}).get("cpu_s", 0.0)
            if spin_cpu > 0.02:
                break
        stop.set()
        t.join()
        assert spin_cpu > 0.02, "spinning thread accrued no CPU"
        summ = s.summary()
        assert summ["thread_cpu_s"]["spin"] >= spin_cpu * 0.5
        assert summ["thread_cpu_s"]["idle"] < summ["thread_cpu_s"]["spin"]

    def test_reregistration_keeps_column_monotonic(self):
        probe = probe_thread_clock()
        if probe["source"] == "unavailable":
            pytest.skip("no per-thread CPU clock on this platform")
        s = ResourceSampler(interval_s=0.05)

        def burn():
            t0 = time.perf_counter()
            while time.perf_counter() - t0 < 0.1:
                sum(range(1000))

        for _ in range(2):
            t = threading.Thread(target=burn)
            t.start()
            s.register_thread("worker", t)
            while t.is_alive():
                s.sample_once()
                time.sleep(0.01)
            t.join()
        total = s.summary()["thread_cpu_s"]["worker"]
        # both generations' CPU lands in ONE monotonic column
        assert total > 0.05, total

    def test_gc_pause_accounting(self):
        s = ResourceSampler(interval_s=0.1)
        junk = [[i] for i in range(1000)]
        del junk
        gc.collect()
        rec = s.sample_once()
        assert rec["gc"]["collections"] >= 1
        assert rec["gc"]["pause_s"] > 0
        assert rec["gc"]["pause_max_s"] <= rec["gc"]["pause_s"]

    def test_ring_bounded_and_reset(self):
        s = ResourceSampler(interval_s=0.1, capacity=4)
        for _ in range(10):
            s.sample_once()
        assert len(s.samples()) == 4
        s.reset()
        assert s.samples() == []
        assert s.samples_taken == 0
        assert s.latest() is None

    def test_sampler_thread_start_stop(self):
        s = ResourceSampler(interval_s=0.01)
        s.start()
        deadline = time.perf_counter() + 2.0
        while s.samples_taken < 3 and time.perf_counter() < deadline:
            time.sleep(0.01)
        s.stop()
        assert s.samples_taken >= 3
        taken = s.samples_taken
        time.sleep(0.05)
        assert s.samples_taken == taken  # really stopped

    def test_dead_thread_column_goes_quiet_not_fatal(self):
        probe = probe_thread_clock()
        if probe["source"] == "unavailable":
            pytest.skip("no per-thread CPU clock on this platform")
        s = ResourceSampler(interval_s=0.05)
        t = threading.Thread(target=lambda: None)
        t.start()
        t.join()
        s.register_thread("gone", t)
        rec = s.sample_once()  # dead tid: the column is absent, no raise
        assert "gone" not in rec["threads"] or \
            rec["threads"]["gone"]["cpu_s"] >= 0

    def test_read_thread_cpu_bad_source(self):
        assert read_thread_cpu_s(1, "nonsense") is None


# -- scheduler integration -------------------------------------------------------


class TestSchedulerIntegration:
    def _run(self, columnar, sampler=None, **kw):
        store = APIStore()
        for n in _nodes(6):
            store.create("nodes", n)
        sched = _sched(store, columnar=columnar, ts_window_s=0.02, **kw)
        if sampler is not None:
            sched.attach_resource_sampler(sampler)
            sampler.sample_once()
        store.create_many("pods", _pods(40, prefix="ti"), consume=True)
        sched.run_until_idle()
        time.sleep(0.03)  # let the open window expire
        return store, sched

    def _placements(self, store):
        return {p.metadata.name: p.spec.node_name
                for p in store.list("pods")[0] if p.spec.node_name}

    @pytest.mark.parametrize("columnar", [True, False])
    def test_sampler_onoff_placements_byte_identical(self, columnar):
        s_on, sched_on = self._run(columnar,
                                   sampler=ResourceSampler(interval_s=0.05))
        s_off, sched_off = self._run(columnar, sampler=None)
        on = self._placements(s_on)
        off = self._placements(s_off)
        assert len(on) == 40
        assert json.dumps(sorted(on.items())) == \
            json.dumps(sorted(off.items()))
        # and the sampled run's windows carry the resource columns
        ws = sched_on.timeseries.windows()
        assert ws and any("resource" in w for w in ws)
        assert sched_off.sched_stats()["resource"] is None

    def test_windows_in_sched_stats_with_probe_columns(self):
        _store, sched = self._run(True)
        st = sched.sched_stats()
        assert st["timeseries"]["enabled"] is True
        assert st["timeseries"]["windows_closed"] >= 1
        ws = st["windows"]
        assert ws, "no closed windows in sched_stats"
        # the solve batch lands in SOME window (outside buckets like
        # queue_add may open their own earlier/later windows)
        assert any((w["stages"].get("solve") or {}).get("p99_ms") is not None
                   for w in ws), ws
        w = ws[0]
        assert "active" in w["queue"]
        assert w["breaker"]["state"] == "closed"
        assert w["watch"]["subscribers"] >= 1
        assert "self_s" in w  # per-window instrumentation self-time
        assert "partition" not in w  # standalone: the probe contributes none

    def test_outside_stages_window_via_flightrec_forwarding(self):
        store = APIStore()
        for n in _nodes(6):
            store.create("nodes", n)
        sched = _sched(store, columnar=True, pipeline_binds=True,
                       ts_window_s=0.02)
        store.create_many("pods", _pods(40, prefix="ob"), consume=True)
        sched.run_until_idle()
        sched.flush_binds()
        time.sleep(0.03)
        stages = {name for w in sched.timeseries.windows()
                  for name in w["stages"]}
        assert "bind" in stages  # the worker's outside bucket windowed
        assert "bind_wait" in stages

    def test_recorder_off_disables_timeseries(self):
        store = APIStore()
        for n in _nodes(3):
            store.create("nodes", n)
        sched = _sched(store, flight_recorder=False)
        store.create_many("pods", _pods(10, prefix="off"), consume=True)
        sched.run_until_idle()
        time.sleep(0.02)
        assert sched.timeseries.enabled is False
        assert sched.timeseries.windows() == []

    def test_partition_probe_columns(self):
        from kubernetes_tpu.scheduler.partition import PartitionedScheduler

        store = APIStore()
        for n in _nodes(8):
            store.create("nodes", n)
        coord = PartitionedScheduler(
            store, lambda: Framework(default_plugins()), partitions=2,
            batch_size=256, solver="exact")
        for p in coord.pipelines:
            p.timeseries.window_s = 0.02
        sampler = ResourceSampler(interval_s=0.05)
        coord.attach_resource_sampler(sampler)
        sampler.sample_once()
        coord.sync()
        store.create_many("pods", _pods(40, prefix="pp"), consume=True)
        coord.run_until_idle()
        coord.flush_binds()
        time.sleep(0.03)
        idx_seen = set()
        for p in coord.pipelines:
            for w in p.timeseries.windows():
                part = w.get("partition")
                if part:
                    idx_seen.add(part["index"])
                    assert "conflicts" in part and "reroutes" in part
        assert idx_seen, "no partition columns in any window"
        coord.stop()


# -- the leak-detector proof -----------------------------------------------------


class TestLeakGateProof:
    """Re-introduce the PR-11 parked-bind-worker heap pin: a discarded
    BatchScheduler whose bind worker still parks in q.get() pins the whole
    scheduler object graph. The RSS/live-object trend gate must CATCH the
    pin, and pass once stop() releases the worker (the PR-11 fix)."""

    # per-5s-window ceilings: the pinned graph leaks ~60k blocks + a few
    # MB per window, an order of magnitude past both
    LEAK_SPEC = {"rss_slope_mb_per_min": 20.0,
                 "alloc_block_slope_per_s": 2_000.0}

    def _leak_iteration(self, release: bool):
        store = APIStore()
        for n in _nodes(4):
            store.create("nodes", n)
        sched = _sched(store, pipeline_binds=True)
        store.create_many("pods", _pods(30, prefix="lk"), consume=True)
        sched.run_until_idle()
        sched.flush_binds()
        assert sched._bind_worker is not None and \
            sched._bind_worker.is_alive()
        # the heap the parked worker pins: reachable from the scheduler
        sched._leak_ballast = list(range(60_000))
        if release:
            worker = sched._bind_worker
            sched.stop()  # the PR-11 fix: sentinel the worker out
            if worker is not None:
                worker.join(timeout=5)  # deterministic: the frame is gone
        # discard every reference; without stop() the parked worker's
        # frame keeps the graph alive
        del sched, store

    def _windows_under(self, release: bool):
        sampler = ResourceSampler(interval_s=1.0)
        wins = []
        # one unsampled warmup iteration: lazy imports / first-call caches
        # must not masquerade as growth in either leg
        self._leak_iteration(release)
        gc.collect()
        for i in range(6):
            self._leak_iteration(release)
            gc.collect()
            rec = sampler.sample_once()
            # the fixture simulates a soak cadence: one iteration per 5s
            # window (the real rung's axis) — the leak-per-window is what
            # the gate fits, not how fast this test loops
            wins.append({"end_ts": i * 5.0,
                         "resource": {"rss_mb": rec["rss_mb"],
                                      "alloc_blocks": rec["alloc_blocks"]}})
        return wins

    def test_parked_worker_pin_caught_then_released_passes(self):
        leaky = self._windows_under(release=False)
        res = evaluate_slo({"windows": leaky}, self.LEAK_SPEC)
        assert res["pass"] is False, res["checks"]
        # the live-object signal is the deterministic one (RSS may or may
        # not also trip depending on allocator arena reuse)
        assert "alloc_block_slope_per_s" in res["failed"], res["checks"]

        clean = self._windows_under(release=True)
        res2 = evaluate_slo({"windows": clean}, self.LEAK_SPEC)
        assert res2["pass"] is True, res2["checks"]


# -- ring-mode subscription pins (ISSUE 13 satellite) ----------------------------


class TestRingSubscriptionPins:
    def test_client_watch_ring_param_builds_ring_url(self, monkeypatch):
        from kubernetes_tpu.server.client import RESTClient

        seen = {}

        class _Resp:
            def __iter__(self):
                return iter([])

        def fake_urlopen(req, timeout=None):
            seen["url"] = req.full_url
            return _Resp()

        monkeypatch.setattr(urllib.request, "urlopen", fake_urlopen)
        c = RESTClient("http://127.0.0.1:1")
        list(c.watch("pods", ring=True))
        assert "ring=true" in seen["url"]
        list(c.watch("pods"))  # the cache-building default: NO ring
        assert "ring=true" not in seen["url"]

    def test_ktl_get_watch_subscribes_ring_true(self):
        # the `-w` dashboard is an observability consumer: its subscription
        # must be lossy (ring=True), never able to trigger the
        # terminate->relist storm PR 11 fixed
        from kubernetes_tpu.cli.ktl import cmd_get

        seen = {}

        class _StubClient:
            def list(self, resource, ns, label_selector=""):
                return [], 7

            def watch(self, resource, **kw):
                seen.update(kw)
                return iter([])

        args = SimpleNamespace(resource="pods", name=None, namespace=None,
                               output="wide", watch=True, selector="",
                               all_namespaces=False)
        buf = io.StringIO()
        with redirect_stdout(buf):
            cmd_get(_StubClient(), args)
        assert seen.get("ring") is True

    def test_informer_keeps_eviction_contract(self):
        # Informer builds a cache: it NEEDS terminate-on-overflow to know
        # it missed events (410 -> relist). Its watch must stay ring-less.
        import inspect

        from kubernetes_tpu.server.client import Informer, RESTClient

        src = inspect.getsource(Informer)
        assert "ring=True" not in src
        # and the client default itself is ring-less
        sig = inspect.signature(RESTClient.watch)
        assert sig.parameters["ring"].default is False

    def test_server_ring_watch_via_http(self):
        # end to end: a ?ring=true subscription lands a ring-mode Watch on
        # the server store (the PR-11 plumbing), pinned from the client API
        from kubernetes_tpu.server import APIServer

        store = APIStore()
        srv = APIServer(store).start()
        try:
            req = urllib.request.Request(
                f"{srv.url}/api/v1/pods?watch=true&resourceVersion=-1"
                "&ring=true")
            resp = urllib.request.urlopen(req, timeout=5)
            deadline = time.perf_counter() + 2.0
            while time.perf_counter() < deadline:
                with store._lock:
                    watchers = list(store._watchers)
                if watchers:
                    break
                time.sleep(0.01)
            assert watchers and watchers[-1].ring is True
            resp.close()
        finally:
            srv.stop()


# -- the /debug/timeseries + ktl sched top surfaces ------------------------------


class TestTimeseriesSurfaces:
    def _server_with_traffic(self):
        from kubernetes_tpu.server import APIServer

        store = APIStore()
        srv = APIServer(store).start()
        for n in _nodes(3):
            store.create("nodes", n)
        sched = _sched(store, ts_window_s=0.02)
        sched.attach_resource_sampler(ResourceSampler(interval_s=0.05))
        sched.resource_sampler.sample_once()
        store.create_many("pods", _pods(20, prefix="sv"), consume=True)
        sched.run_until_idle()
        time.sleep(0.03)
        return store, srv, sched

    def test_debug_timeseries_endpoint(self):
        store, srv, sched = self._server_with_traffic()
        try:
            name = sched._bind_origin
            snap = timeseries_snapshot()
            assert name in snap and snap[name]["windows"]
            with urllib.request.urlopen(
                    f"{srv.url}/debug/timeseries") as resp:
                payload = json.loads(resp.read())
            assert name in payload
            doc = payload[name]
            assert doc["windows"]
            assert doc["resource"]["rss_mb"] > 0
            assert doc["resource"]["clock_source"]
        finally:
            srv.stop()

    def test_ktl_sched_top_renders(self):
        from kubernetes_tpu.cli.ktl import main as ktl_main

        store, srv, sched = self._server_with_traffic()
        try:
            buf = io.StringIO()
            with redirect_stdout(buf):
                assert ktl_main(["--server", srv.url, "sched", "top"]) == 0
            out = buf.getvalue()
            assert "WIN" in out and "PODS/S" in out and "BREAKER" in out
            assert "ALLOCS" in out  # live zero-alloc gauge column (ISSUE 16)
            assert "resource:" in out and "clock=" in out
            buf = io.StringIO()
            with redirect_stdout(buf):
                assert ktl_main(["--server", srv.url, "sched", "top",
                                 "-o", "json"]) == 0
            doc = json.loads(buf.getvalue())
            assert sched._bind_origin in doc
        finally:
            srv.stop()

    def test_sched_top_empty_registry_message(self):
        from kubernetes_tpu.cli.ktl import _render_sched_top

        assert "no batch scheduler" in _render_sched_top({})
