"""Benchmark ladder: the reference's scheduler_perf workloads on the TPU path.

Measures the batch device path end-to-end per workload (tensorize + device
upload + solve + host readback on fresh state — what a long-running scheduler
executes per batch) against the reference's enforced CI thresholds
(BASELINE.md; sources in test/integration/scheduler_perf/*/performance-config
.yaml). The churn row runs the full BatchScheduler against the API store with
binds enabled and background churn — the honest end-to-end number.

Prints ONE JSON line: the headline metric is SchedulingBasic throughput; the
`workloads` map carries every rung (pods/s + vs_baseline), `min_vs_baseline`
the weakest rung.

Device: the ladder runs on a TPU. A run that finds none exits non-zero and
names the platform it found (kubernetes_tpu/device.py); `JAX_PLATFORMS=cpu`
names a CPU rehearsal, whose JSON says "platform": "cpu".

Robustness (the round-2 rc=124 failure mode):
  - fails FAST with an error when the device backend is down or hangs,
  - checkpoints partial results to BENCH_partial.json after every rung,
  - skips remaining rungs once the global wall-clock budget is spent, so a
    slow chip degrades coverage instead of producing nothing.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

PARTIAL_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "BENCH_partial.json")
# BENCH_SMOKE=1 shrinks every rung ~64x for a fast CPU harness check —
# validates the ladder end to end without TPU hardware (numbers meaningless)
SMOKE = os.environ.get("BENCH_SMOKE", "") not in ("", "0")


def sz(n: int, floor: int = 8) -> int:
    return max(floor, n // 64) if SMOKE else n
GLOBAL_BUDGET_S = float(os.environ.get("BENCH_BUDGET_S", "1500"))
# a rung is skipped when less than this much budget remains (quick mode
# shrinks it along with the budget)
MIN_RUNG_BUDGET_S = 60.0
_START = time.monotonic()


def budget_left() -> float:
    return GLOBAL_BUDGET_S - (time.monotonic() - _START)


def checkpoint(results) -> None:
    """Persist partial results after every rung — a later crash/timeout still
    leaves an inspectable record."""
    try:
        with open(PARTIAL_PATH, "w") as f:
            json.dump(results, f)
    except OSError:
        pass


ZONE = "topology.kubernetes.io/zone"
HOST = "kubernetes.io/hostname"

# reference thresholds (pods/s) — BASELINE.md
BASE_BASIC = 270.0          # misc/performance-config.yaml:63
BASE_PTS = 85.0             # misc/performance-config.yaml:186  TopologySpreading
BASE_ANTI = 60.0            # affinity/performance-config.yaml:68  PodAntiAffinity
BASE_AFF = 35.0             # affinity/performance-config.yaml:135 PodAffinity
BASE_NSANTI = 24.0          # affinity/performance-config.yaml:480 RequiredPodAntiAffinityWithNSSelector
BASE_CHURN = 265.0          # misc/performance-config.yaml:586 SchedulingWithMixedChurn
BASE_PREEMPT = 18.0         # misc/performance-config.yaml:363 PreemptionBasic (500 nodes)
NORTH_STAR = 100_000.0      # BASELINE.json: 100k pods / 10k nodes / <1s


def _nodes(n, cpu="8", mem="32Gi", zones=0):
    from kubernetes_tpu.testing import MakeNode

    out = []
    for i in range(n):
        labels = {HOST: f"node-{i}"}
        if zones:
            labels[ZONE] = f"zone-{i % zones}"
        out.append(MakeNode(f"node-{i}").labels(labels)
                   .capacity({"cpu": cpu, "memory": mem, "pods": "110"}).obj())
    return out


def make_snapshot(nodes, bound_pods=()):
    from kubernetes_tpu.scheduler import Cache
    from kubernetes_tpu.utils import FakeClock

    cache = Cache(clock=FakeClock())
    for n in nodes:
        cache.add_node(n)
    for p in bound_pods:
        cache.add_pod(p)
    return cache.update_snapshot()


def device_solve(snap, pods, solver, ns_labels=None):
    """One full device pass: tensorize + upload + solve + readback. Returns
    (assignment ndarray, seconds, info dict — repair-stage columns when the
    propose-and-repair solver ran, else empty)."""
    import numpy as np

    from kubernetes_tpu.models.repair import repair_solve
    from kubernetes_tpu.models.waterfill import make_groups, waterfill_solve
    from kubernetes_tpu.ops.solver import greedy_scan_solve, make_inputs
    from kubernetes_tpu.snapshot.tensorizer import build_cluster_tensors, build_pod_batch

    info = {}
    t0 = time.perf_counter()
    cluster = build_cluster_tensors(snap)
    batch = build_pod_batch(pods, snap, cluster, ns_labels=ns_labels)
    inputs, d_max = make_inputs(cluster, batch)
    if solver == "waterfill":
        a = np.asarray(waterfill_solve(inputs, make_groups(batch)))
    elif solver == "repair":
        solved = repair_solve(inputs, batch, d_max)
        assert solved is not None, "repair solver declined the problem shape"
        a, stats = solved
        a = np.asarray(a)
        s = stats.as_dict()
        info["repair"] = {k: s[k] for k in
                          ("rounds", "residual", "full_scan", "propose_calls")}
    else:
        assignment, _, _ = greedy_scan_solve(
            inputs, d_max, has_ipa=bool(batch.ipa.has_any),
            has_ct=bool(batch.ct_class.size), has_st=bool(batch.st_class.size))
        a = np.asarray(assignment)
    return a, time.perf_counter() - t0, info


def run_rung(name, snap, pods, solver, baseline, min_placed=None,
             results=None, ns_labels=None):
    """Warm-up (compile) + timed pass; records pods/s and vs_baseline. Every
    constraint rung publishes the SAME columns (solver / vs_baseline /
    repair-stage info) through this one path."""
    try:
        device_solve(snap, pods, solver, ns_labels=ns_labels)
        a, dt, info = device_solve(snap, pods, solver, ns_labels=ns_labels)
        placed = int((a >= 0).sum())
        want = len(pods) if min_placed is None else min_placed
        assert placed >= want, f"{name}: only {placed}/{want} placed"
        pods_per_sec = len(pods) / dt
        results[name] = {
            "pods_per_sec": round(pods_per_sec, 1),
            "vs_baseline": round(pods_per_sec / baseline, 2),
            "placed": placed,
            "pods": len(pods),
            "solver": solver,
            **info,
        }
        print(f"{name:>28}: {pods_per_sec:>9.0f} pods/s  "
              f"({placed}/{len(pods)} placed, {results[name]['vs_baseline']}x baseline "
              f"{baseline:.0f}, {solver})", file=sys.stderr)
    except Exception as e:  # a failed rung must not kill the whole bench
        results[name] = {"error": str(e)[:200]}
        print(f"{name:>28}: ERROR {e}", file=sys.stderr)


def rung_basic(results):
    from kubernetes_tpu.testing import MakePod

    snap = make_snapshot(_nodes(sz(5000)))
    pods = [MakePod(f"pod-{i}").req({"cpu": "500m", "memory": "1Gi"}).obj()
            for i in range(sz(10000))]
    run_rung("SchedulingBasic", snap, pods, "waterfill", BASE_BASIC, results=results)
    run_rung("SchedulingBasic_scan", snap, pods, "scan", BASE_BASIC, results=results)


def rung_topology_spread(results):
    # TopologySpreading: every pod spreads over zones with DoNotSchedule
    # (misc/performance-config.yaml:145-186 shape)
    from kubernetes_tpu.testing import MakePod

    snap = make_snapshot(_nodes(sz(5000), zones=10))
    pods = [MakePod(f"sp-{i}").labels({"app": "spread"})
            .req({"cpu": "200m", "memory": "256Mi"})
            .topology_spread(1, ZONE, "DoNotSchedule", {"app": "spread"})
            .obj() for i in range(sz(5000))]
    run_rung("TopologySpreading", snap, pods, "repair", BASE_PTS, results=results)


def rung_pod_anti_affinity(results):
    # PodAntiAffinity: 50 groups x 40 pods, each group hostname-anti-affine
    # (affinity/performance-config.yaml:23-68 shape: anti-affine batches)
    from kubernetes_tpu.testing import MakePod

    snap = make_snapshot(_nodes(sz(5000)))
    pods = []
    for g in range(sz(50)):
        for i in range(sz(40)):
            pods.append(MakePod(f"anti-{g}-{i}").labels({"grp": f"g{g}"})
                        .pod_anti_affinity(HOST, {"grp": f"g{g}"})
                        .req({"cpu": "200m"}).obj())
    run_rung("PodAntiAffinity", snap, pods, "repair", BASE_ANTI, results=results)


def rung_pod_affinity(results):
    # PodAffinity: seed pods labeled per zone; incoming pods require
    # colocation with their seed (affinity/performance-config.yaml:85-135)
    from kubernetes_tpu.testing import MakePod

    nodes = _nodes(sz(5000), zones=sz(50))
    seeds = [MakePod(f"seed-{z}").labels({"svc": f"s{z}"})
             .node(f"node-{z}").req({"cpu": "100m"}).obj() for z in range(sz(50))]
    snap = make_snapshot(nodes, bound_pods=seeds)
    pods = [MakePod(f"aff-{i}").labels({"peer": "1"})
            .pod_affinity(ZONE, {"svc": f"s{i % sz(50)}"})
            .req({"cpu": "200m"}).obj() for i in range(sz(5000))]
    run_rung("PodAffinity", snap, pods, "repair", BASE_AFF, results=results)


def rung_anti_affinity_ns_selector(results):
    # RequiredPodAntiAffinityWithNSSelector: pods across namespaces,
    # anti-affinity scoped by namespaceSelector
    # (affinity/performance-config.yaml:480 — the reference's worst case, 24).
    # Folded into run_rung (ISSUE 8): ns_labels flow through build_pod_batch
    # via device_solve, so this rung publishes the SAME columns as every
    # other constraint rung instead of a hand-rolled result dict.
    from kubernetes_tpu.api.types import Affinity, PodAffinityTerm
    from kubernetes_tpu.api.labels import Selector
    from kubernetes_tpu.testing import MakePod

    snap = make_snapshot(_nodes(sz(5000)))
    ns_labels = {f"team-{t}": {"team": "x"} for t in range(10)}
    pods = []
    for g in range(sz(50)):
        term = PodAffinityTerm(
            topology_key=HOST,
            selector=Selector.from_match_labels({"grp": f"g{g}"}),
            namespace_selector=Selector.from_match_labels({"team": "x"}),
        )
        for i in range(sz(40)):
            p = MakePod(f"nsa-{g}-{i}", namespace=f"team-{(g + i) % 10}").labels(
                {"grp": f"g{g}"}).req({"cpu": "200m"}).obj()
            p.spec.affinity = Affinity(pod_anti_affinity_required=[term])
            pods.append(p)
    run_rung("AntiAffinityNSSelector", snap, pods, "repair", BASE_NSANTI,
             results=results, ns_labels=ns_labels)


def rung_mixed_churn(results):
    """End-to-end: BatchScheduler against the API store, binds enabled,
    background churn between batches (SchedulingWithMixedChurn shape —
    misc/performance-config.yaml:527-586). Wall clock covers watch ingestion,
    cache updates, tensorize, solve, and pipelined store binds."""
    from kubernetes_tpu.scheduler import Framework
    from kubernetes_tpu.scheduler.batch import BatchScheduler
    from kubernetes_tpu.scheduler.plugins import default_plugins
    from kubernetes_tpu.store import APIStore
    from kubernetes_tpu.testing import MakeNode, MakePod

    try:
        n_nodes, n_pods = sz(5000), sz(10000)
        # warm-up on a throwaway cluster at the REAL batch shapes (the round-3
        # run compiled mid-measurement because the warm batch had 1 pod)
        warm_store = APIStore()
        for n in _nodes(n_nodes):
            warm_store.create("nodes", n)
        warm = BatchScheduler(warm_store, Framework(default_plugins()),
                              batch_size=sz(2500), solver="auto")
        warm.sync()
        warm_store.create_many(
            "pods", (MakePod(f"w-{i}").req(
                {"cpu": "500m", "memory": "1Gi"}).obj()
                for i in range(sz(2500))), consume=True)
        warm.run_until_idle()

        store = APIStore()
        for n in _nodes(n_nodes):
            store.create("nodes", n)
        sched = BatchScheduler(store, Framework(default_plugins()),
                               batch_size=sz(2500), solver="auto")
        sched.sync()
        store.create("pods", MakePod("warm").req({"cpu": "100m"}).obj())
        sched.run_until_idle()

        store.create_many(
            "pods", (MakePod(f"ch-{i}").req(
                {"cpu": "500m", "memory": "1Gi"}).obj()
                for i in range(n_pods)), consume=True)
        t0 = time.perf_counter()
        done = 0
        churn_i = 0
        while done < n_pods:
            handled = sched.schedule_batch(timeout=0.0)
            if handled == 0:
                sched.flush_binds()
                sched.pump_events()
                if sched.schedule_batch(timeout=0.0) == 0:
                    break
            done = sched.scheduled_count + sched.failed_count - 1  # minus warm pod
            # mixed churn: node updates + unrelated pod create/delete
            for _ in range(10):
                nm = f"node-{churn_i % n_nodes}"
                node = store.get("nodes", nm)
                node.metadata.labels["churn"] = str(churn_i)
                store.update("nodes", node, check_rv=False)
                churn_i += 1
        sched.flush_binds()
        dt = time.perf_counter() - t0
        bound = sum(1 for p in store.list("pods")[0] if p.spec.node_name)
        pps = (bound - 1) / dt
        results["MixedChurn_endtoend"] = {
            "pods_per_sec": round(pps, 1), "vs_baseline": round(pps / BASE_CHURN, 2),
            "placed": bound - 1, "pods": n_pods, "solver": "auto+store-binds"}
        print(f"{'MixedChurn_endtoend':>28}: {pps:>9.0f} pods/s  "
              f"({bound - 1}/{n_pods} bound through store, "
              f"{pps / BASE_CHURN:.1f}x baseline 265)", file=sys.stderr)
    except Exception as e:
        results["MixedChurn_endtoend"] = {"error": str(e)[:200]}
        print(f"MixedChurn_endtoend: ERROR {e}", file=sys.stderr)


def rung_preemption(results):
    """PreemptionBasic (misc/performance-config.yaml:363 shape, baseline 18):
    500 full nodes, 500 higher-priority preemptors, SERIAL victim preparation
    (the reference's non-async mode); PreemptionAsync covers the async mode."""
    _preemption_run(results, "PreemptionBasic", BASE_PREEMPT,
                    async_preparation=False)


def rung_north_star(results):
    # 100k pods / 10k nodes (BASELINE.json ladder top; constraint-free shape):
    # solver-only (tensorize + upload + solve + readback, target <1s)
    from kubernetes_tpu.testing import MakePod

    snap = make_snapshot(_nodes(sz(10000), cpu="16", mem="64Gi"))
    pods = [MakePod(f"ns-{i}").req({"cpu": "500m", "memory": "1Gi"}).obj()
            for i in range(sz(100_000))]
    try:
        device_solve(snap, pods, "waterfill")
        a, dt, _ = device_solve(snap, pods, "waterfill")
        placed = int((a >= 0).sum())
        pps = len(pods) / dt
        results["NorthStar_100k_10k"] = {
            "pods_per_sec": round(pps, 1), "wall_s": round(dt, 3),
            "vs_target": round(pps / NORTH_STAR, 2),
            "placed": placed, "pods": len(pods), "solver": "waterfill"}
        print(f"{'NorthStar_100k_10k':>28}: {pps:>9.0f} pods/s  "
              f"({placed}/{len(pods)} placed in {dt:.3f}s; target <1s)", file=sys.stderr)
    except Exception as e:
        results["NorthStar_100k_10k"] = {"error": str(e)[:200]}
        print(f"NorthStar_100k_10k: ERROR {e}", file=sys.stderr)


def rung_north_star_warm(results):
    """Steady-state variant: re-solve the SAME 100k backlog after churn on a
    few hundred nodes, through the TensorCache — tensorize work scales with
    the diff (generation-diff rows, pod-axis reuse, HBM scatter updates)
    instead of the cluster. The number the long-running scheduler sees per
    re-solve under churn."""
    import numpy as np

    from kubernetes_tpu.models.waterfill import make_groups, waterfill_solve
    from kubernetes_tpu.ops.solver import make_inputs
    from kubernetes_tpu.scheduler import Cache
    from kubernetes_tpu.snapshot.tensorizer import TensorCache, build_pod_batch
    from kubernetes_tpu.testing import MakeNode, MakePod
    from kubernetes_tpu.utils import FakeClock

    try:
        cache = Cache(clock=FakeClock())
        for n in _nodes(sz(10000), cpu="16", mem="64Gi"):
            cache.add_node(n)
        pods = [MakePod(f"nw-{i}").req({"cpu": "500m", "memory": "1Gi"}).obj()
                for i in range(sz(100_000))]
        tc = TensorCache()

        def solve_pass():
            t0 = time.perf_counter()
            snap = cache.update_snapshot()
            cluster, changed = tc.cluster_tensors(snap)
            batch = build_pod_batch(pods, snap, cluster, reuse=tc,
                                    changed_nodes=changed)
            inputs, _ = make_inputs(cluster, batch,
                                    device=tc.device_views(cluster))
            a = np.asarray(waterfill_solve(inputs, make_groups(batch)))
            return a, time.perf_counter() - t0

        solve_pass()  # cold: full tensorize + compile
        # warm-up the INCREMENTAL path too, at the SAME scatter width as the
        # measured pass (the .at[rows].set update compiles per row count)
        for i in range(sz(300)):
            p = MakePod(f"wchurn0-{i}").req({"cpu": "1"}).obj()
            p.spec.node_name = f"node-{i}"
            cache.add_pod(p)
        solve_pass()
        # churn: bind pods to 300 different nodes, then re-solve warm
        for i in range(sz(300)):
            p = MakePod(f"wchurn-{i}").req({"cpu": "1"}).obj()
            p.spec.node_name = f"node-{sz(300) + i}"
            cache.add_pod(p)
        a, dt = solve_pass()
        placed = int((a >= 0).sum())
        pps = len(pods) / dt
        results["NorthStar_100k_10k_warm"] = {
            "pods_per_sec": round(pps, 1), "wall_s": round(dt, 3),
            "vs_target": round(pps / NORTH_STAR, 2),
            "placed": placed, "pods": len(pods),
            "solver": "waterfill+tensorcache"}
        print(f"{'NorthStar_100k_10k_warm':>28}: {pps:>9.0f} pods/s  "
              f"({placed}/{len(pods)} placed in {dt:.3f}s warm re-solve)",
              file=sys.stderr)
    except Exception as e:
        results["NorthStar_100k_10k_warm"] = {"error": str(e)[:200]}
        print(f"NorthStar_100k_10k_warm: ERROR {e}", file=sys.stderr)


def rung_north_star_endtoend(results):
    """The honest variant BASELINE.json actually defines: BIND 100k pending
    pods onto 10k nodes end-to-end — store watch ingestion (coalesced), bulk
    queue admission, cache, tensorize, device solve, batched Binding writes,
    and the self-bind confirm re-ingest all inside the timed window.

    The timed window runs with the collector frozen+disabled (restored
    after): CPython gen2 sweeps over the ~10M-object store/cache heap
    otherwise add 2x wall that measures the collector, not the pipeline —
    the standard long-lived-heap service configuration."""
    import gc

    from kubernetes_tpu.scheduler import Framework
    from kubernetes_tpu.scheduler.batch import BatchScheduler
    from kubernetes_tpu.scheduler.plugins import default_plugins
    from kubernetes_tpu.store import APIStore
    from kubernetes_tpu.testing import MakePod

    try:
        n_nodes, n_pods = sz(10_000), sz(100_000)
        # warm-up on a THROWAWAY cluster at the real batch shape: the
        # 100k-pod waterfill compiles per pod-axis shape, and a 1-pod warm
        # batch left the full-shape compile inside the timed window
        warm_store = APIStore()
        for n in _nodes(n_nodes, cpu="16", mem="64Gi"):
            warm_store.create("nodes", n)
        # warm-up runs with the flight recorder DISABLED — exercising the
        # recorder-off hot path every bench run (parity with recorder-on is
        # pinned by tests/test_flightrec.py). The pod TRACER stays on so its
        # first-call costs (numpy ufunc warmup, lazy imports, histogram
        # construction) land here, not inside the timed window
        warm = BatchScheduler(warm_store, Framework(default_plugins()),
                              batch_size=n_pods, solver="fast",
                              flight_recorder=False, pod_trace=True)
        warm.sync()
        warm_store.create_many(
            "pods", (MakePod(f"w-{i}").req(
                {"cpu": "500m", "memory": "1Gi"}).obj()
                for i in range(n_pods)), consume=True)
        warm.run_until_idle()
        # the warm cluster must not sit in memory during the timed run
        # (stop() releases the bind worker, which would otherwise pin the
        # whole warm object graph from its parked q.get())
        warm.stop()
        del warm, warm_store

        store = APIStore()
        for n in _nodes(n_nodes, cpu="16", mem="64Gi"):
            store.create("nodes", n)
        sched = BatchScheduler(store, Framework(default_plugins()),
                               batch_size=n_pods, solver="fast")
        sched.sync()
        # bulk write API: one store lock + one coalesced ADDED event per
        # chunk; consume=True transfers ownership (no isolation deepcopy)
        CH = 10_000
        pending = [MakePod(f"e2e-{i}").req(
            {"cpu": "500m", "memory": "1Gi"}).obj() for i in range(n_pods)]
        for lo in range(0, n_pods, CH):
            store.create_many("pods", pending[lo:lo + CH], consume=True)
        gc.collect()
        gc.freeze()
        gc.disable()
        try:
            sched.flightrec.clear()  # stage table covers EXACTLY the window
            sched.podtrace.clear()  # latency histogram + spans likewise
            # jit-cache watermark (ISSUE 5 retrace guard): the warm-up
            # compiled every shape the timed run uses, so a nonzero delta
            # below IS a mid-run retrace — the regression class JT001
            # guards statically
            compiles0 = _solver_jit_cache()

            # the zero-alloc acceptance gauge (ISSUE 16): pod-object
            # materializations across the store + scheduler-cache columnar
            # tables during the timed window — 0 when the end-to-end
            # columnar pipeline (rows + column assume + clone-free
            # dispatch) never builds a per-pod Python object
            def _pod_obj_allocs():
                st = store.columnar_stats() or {}
                return (st.get("materialized_total", 0)
                        + sched.cache.columnar_materialized())

            allocs0 = _pod_obj_allocs()
            t0 = time.perf_counter()
            sched.run_until_idle()
            dt = time.perf_counter() - t0
            pod_obj_allocs = _pod_obj_allocs() - allocs0
        finally:
            # a mid-run failure must not leave the collector off for every
            # later rung (this rung records the error and the ladder
            # continues)
            gc.enable()
            gc.unfreeze()
        jit_cache = _solver_jit_cache()
        compiles_during = {k: v - compiles0.get(k, 0)
                          for k, v in jit_cache.items() if v >= 0}
        bound = sched.scheduled_count
        pps = bound / dt
        # machine-generated stage breakdown (scheduler/flightrec.py): the
        # source of ROADMAP's stage table. Serial rows sum to ~wall; "bind"
        # is the worker's wall, overlapped with the solve. instrumentation_s
        # is the recorder's measured self-time (record building, histogram
        # observation, timing taps) — the only unmeasured cost is the ~10
        # StageClock perf_counter reads per batch. Divided by wall it bounds
        # the overhead budget without differencing two noisy runs.
        table = sched.flightrec.stage_table()
        stages = {k: round(v["total_ms"] / 1000, 4) for k, v in table.items()}
        serial_sum = round(sum(v["total_ms"] for v in table.values()
                               if not v["overlapped"]) / 1000, 4)
        # pod-latency observability (ISSUE 7): per-stage p50/p99 columns,
        # the all-pods submit->bound distribution, sampled-span health, and
        # the declarative SLO gate (scheduler/slo.py) — the BENCH_r* series
        # tracks tails from this run on, not just pods/s
        from kubernetes_tpu.scheduler.slo import NORTH_STAR_SLO, evaluate_slo

        latency = sched.podtrace.latency_stats()
        tsnap = sched.podtrace.snapshot()
        # control-plane observability columns (ISSUE 9): the scheduler's own
        # coalesced subscriber gives the commit->dequeue propagation of the
        # whole ingest path; controller columns are empty here (no
        # controllers in this rung) but published so the schema is uniform
        from kubernetes_tpu.obs.reconcile import reconcile_rollup

        wtel = store.watch_telemetry()
        prop = wtel["propagation"]
        watch_col = {
            "propagation_count": prop["count"],
            "propagation_p50_s": prop["p50_s"],
            "propagation_p99_s": prop["p99_s"],
            "settle_s": prop["settle_seconds"],
            "subscribers": len(wtel["subscribers"]),
            "max_rv_lag": max((s["rv_lag"] for s in wtel["subscribers"]),
                              default=0),
        }
        compiles = sum(compiles_during.values())
        # the <2% budget now covers the new recorders too: inline watch-tap
        # settlement already bills flightrec via the Watch stat_sink. The
        # budget is a FRACTION with a 2ms ABSOLUTE floor: the smoke-shrunk
        # rung's wall is ~45ms (and shrank further with the native commit
        # engine) while the recorder's per-run cost is fixed sub-1ms — a
        # fixed cost that doesn't scale with the run must not read as a
        # budget violation on a run 2000x smaller than production
        instr_s = sched.flightrec.self_seconds
        instr_frac = (instr_s / max(dt, 1e-9)) if instr_s > 0.002 else 0.0
        slo = evaluate_slo(
            {"stages": table, "latency": latency}, NORTH_STAR_SLO,
            extra={"solver_compiles": compiles,
                   "instrumentation_frac": round(instr_frac, 5)})
        results["NorthStar_100k_10k_endtoend"] = {
            "pods_per_sec": round(pps, 1), "wall_s": round(dt, 3),
            "vs_target": round(pps / NORTH_STAR, 2),
            "placed": bound, "pods": n_pods, "solver": "fast+store-binds",
            "stages": stages,
            "stages_p50_ms": {k: v.get("p50_ms") for k, v in table.items()},
            "stages_p99_ms": {k: v.get("p99_ms") for k, v in table.items()},
            "stages_serial_sum_s": serial_sum,
            "latency": latency,
            "trace": {"spans": len(tsnap["spans"]),
                      "complete": sum(1 for s in tsnap["spans"]
                                      if s["complete"]),
                      "evicted_incomplete": tsnap["evicted_incomplete"],
                      "flush_s": tsnap["flush_seconds"]},
            "watch": watch_col,
            "reconcile": reconcile_rollup(),
            "slo": slo,
            "instrumentation_s": round(sched.flightrec.self_seconds, 6),
            "jit_cache": jit_cache,
            # ISSUE 16 acceptance: zero pod-object materializations in the
            # timed window, with the row path demonstrably engaged
            "pod_obj_allocs": pod_obj_allocs,
            "cache_rows": sched.cache.columnar_rows(),
            "solver_compiles_during_run": compiles}
        print(f"{'NorthStar_100k_10k_endtoend':>28}: {pps:>9.0f} pods/s  "
              f"({bound}/{n_pods} BOUND through the store in {dt:.3f}s)",
              file=sys.stderr)
        print("    stages: " + "  ".join(
            f"{k}={v:.3f}s" for k, v in sorted(
                stages.items(), key=lambda kv: -kv[1])), file=sys.stderr)
        print(f"    submit->bound: p50={latency['p50_s']}s "
              f"p99={latency['p99_s']}s over {latency['count']} pods; "
              f"SLO {'PASS' if slo['pass'] else 'FAIL ' + str(slo['failed'])}",
              file=sys.stderr)
        # --- partitioned A/B (ISSUE 12): the SAME workload, same box,
        # through 2 partitioned pipelines — disjoint node shards,
        # hash-routed pods, each partition's GIL-held host stages
        # overlapping the other's GIL-free XLA solve. The 1p run above is
        # the A; this is the B. The 1p heap is released first (the A/B must
        # not measure the winner under the loser's memory pressure), and a
        # warm run compiles the partition-shaped kernels (half-size pod
        # bucket, shard-size node axis — fresh jit shapes).
        share_1p = round(stages.get("bind_wait", 0.0) / max(dt, 1e-9), 4)
        sched.stop()  # release the bind worker so the del really frees
        del sched, store, pending
        try:
            _w = _partitioned_e2e(n_pods, n_nodes, 2, "e2ew")[0]
            _w.stop()
            del _w
            compiles2_0 = _solver_jit_cache()
            # interleaved best-of-2 per mode (the BindCommit discipline):
            # harness co-scheduling drifts minute-to-minute on this rig
            # (same-code 1p walls vary +-30%), and alternating the modes
            # keeps the drift from landing entirely on one column. The main
            # 1p run above stays the official 1p number; its wall joins the
            # 1p sample set here.
            from kubernetes_tpu.obs import ResourceSampler
            judge = len(os.sched_getaffinity(0)) >= 2
            best = None
            walls_1p, walls_2p = [dt], []
            for i in range(2):
                samp = ResourceSampler(interval_s=0.05) if judge else None
                c, st2c, d2, b2 = _partitioned_e2e(
                    n_pods, n_nodes, 2, f"e2eb{i}", sampler=samp)
                walls_2p.append(d2)
                osum = samp.summary() if samp is not None else None
                if best is None or d2 < best[2]:
                    if best is not None:
                        best[0].stop()
                    best = (c, st2c, d2, b2, osum)  # rebind drops old best
                else:
                    c.stop()
                    del c, st2c
                _s1, _st1, d1, _b1 = _partitioned_e2e(
                    n_pods, n_nodes, 1, f"e2ea{i}")
                _s1.stop()
                del _s1, _st1
                walls_1p.append(d1)
            coord, store2, dt2, bound2, osum2 = best
            compiles_2p = sum(
                v - compiles2_0.get(k, 0)
                for k, v in _solver_jit_cache().items() if v >= 0)
            dt1_best = min(walls_1p)
            pps1b = n_pods / dt1_best  # best-of 1p for the A/B columns
            dt2 = min(walls_2p)
            pps2 = bound2 / dt2
            # bind_wait share of wall: mean over pipelines of that
            # pipeline's scheduling-thread stall — the acceptance lever
            # (partitioning exists to give the stall something to overlap
            # with)
            waits = [(p.flightrec.stage_table().get("bind_wait", {})
                      .get("total_ms", 0.0) or 0.0) / 1000.0
                     for p in coord.pipelines]
            share_2p = round((sum(waits) / max(len(waits), 1))
                             / max(dt2, 1e-9), 4)
            cores = len(os.sched_getaffinity(0))
            results["NorthStar_100k_10k_endtoend"]["partitioned"] = {
                "partitions": 2,
                "pods_per_sec_2p": round(pps2, 1),
                "wall_s_2p": round(dt2, 3),
                "placed_2p": bound2,
                "pods_per_sec_1p_best": round(pps1b, 1),
                "speedup_vs_1p": round(pps2 / max(pps1b, 1e-9), 3),
                "walls_1p": [round(w, 3) for w in walls_1p],
                "walls_2p": [round(w, 3) for w in walls_2p],
                "cores": cores,
                "ab_comparable": cores >= 2,
                # measured concurrency (ISSUE 19 satellite): overlap_cpu_s
                # sampled inside the winning 2p window; None = 1-core rig
                "overlap_cpu_s": (osum2["overlap_cpu_s"] if osum2
                                  else None),
                "concurrency_verdict": _overlap_verdict(
                    osum2["overlap_cpu_s"] if osum2 else None, dt2),
                "concurrent_drive": coord.concurrent_drive,
                "bind_wait_share_1p": share_1p,
                "bind_wait_share_2p": share_2p,
                "conflicts": coord.conflicts_total,
                "reroutes": coord.reroutes_total,
                "solver_compiles_during_run": compiles_2p,
                "per_partition": [
                    {"index": r["index"], "nodes": r["nodes"],
                     "scheduled": r["scheduled"]}
                    for r in coord.sched_stats()["rows"]],
            }
            print(f"    partitioned A/B (best-of-interleaved): "
                  f"1p {pps1b:.0f} vs 2p {pps2:.0f} pods/s "
                  f"(speedup {pps2 / max(pps1b, 1e-9):.2f}x; bind_wait "
                  f"share {share_1p:.3f} -> {share_2p:.3f}; "
                  f"compiles_2p={compiles_2p})", file=sys.stderr)
            coord.stop()  # release bind workers before later rungs
        except Exception as e:  # the A/B must not void the 1p result
            results["NorthStar_100k_10k_endtoend"]["partitioned"] = {
                "error": str(e)[:200]}
            print(f"    partitioned A/B: ERROR {e}", file=sys.stderr)
    except Exception as e:
        results["NorthStar_100k_10k_endtoend"] = {"error": str(e)[:200]}
        print(f"NorthStar_100k_10k_endtoend: ERROR {e}", file=sys.stderr)


def _partitioned_e2e(n_pods, n_nodes, partitions, prefix, batch_size=None,
                     sampler=None):
    """One end-to-end bind run (fresh store, GC-frozen timed window) through
    a 1-partition BatchScheduler or an N-partition PartitionedScheduler —
    the shared body of the Partitioned_2x rung and the NorthStar A/B column
    (ISSUE 12). Returns (sched, store, dt, bound). sampler: an
    obs/resource.py ResourceSampler started around the timed window only —
    the >=2-core A/B re-judge (ISSUE 19 satellite) reads its overlap_cpu_s
    to judge the speedup column from MEASURED parallelism, not wall ratios.
    """
    import gc

    from kubernetes_tpu.scheduler import Framework
    from kubernetes_tpu.scheduler.batch import BatchScheduler
    from kubernetes_tpu.scheduler.partition import PartitionedScheduler
    from kubernetes_tpu.scheduler.plugins import default_plugins
    from kubernetes_tpu.store import APIStore
    from kubernetes_tpu.testing import MakePod

    bs = batch_size or n_pods
    store = APIStore()
    for n in _nodes(n_nodes, cpu="16", mem="64Gi"):
        store.create("nodes", n)
    if partitions == 1:
        sched = BatchScheduler(store, Framework(default_plugins()),
                               batch_size=bs, solver="fast")
    else:
        sched = PartitionedScheduler(
            store, lambda: Framework(default_plugins()),
            partitions=partitions, batch_size=bs, solver="fast")
    sched.sync()
    CH = 10_000
    pending = [MakePod(f"{prefix}-{i}").req(
        {"cpu": "500m", "memory": "1Gi"}).obj() for i in range(n_pods)]
    for lo in range(0, n_pods, CH):
        store.create_many("pods", pending[lo:lo + CH], consume=True)
    gc.collect()
    gc.freeze()
    gc.disable()
    if sampler is not None:
        sched.attach_resource_sampler(sampler)
        sampler.start()
    try:
        t0 = time.perf_counter()
        sched.run_until_idle()
        dt = time.perf_counter() - t0
    finally:
        if sampler is not None:
            sampler.stop()
        gc.enable()
        gc.unfreeze()
    sched.flush_binds()
    return sched, store, dt, sched.scheduled_count


def rung_partitioned(results):
    """Partitioned_2x (ISSUE 12): the SAME constraint-free bind workload
    through ONE pipeline and through TWO partitioned pipelines on the same
    box — disjoint node shards, hash-routed pods, each partition's
    tensorize/assume/bind overlapping the other's GIL-free XLA solve. The
    quick-tier sibling of the NorthStar A/B column; publishes speedup,
    absorbed conflicts/reroutes, per-partition rows, and the conservation
    verdict (tests/test_bench_quick.py asserts correctness columns; the
    speedup itself is recorded, not tier-1-gated — a co-scheduled 2-core CI
    box is not the bench rig)."""
    from kubernetes_tpu.testing import pod_conservation_report

    try:
        n_pods = sz(20_000, floor=2000)
        n_nodes = sz(1000, floor=64)
        # warm-up BOTH configurations on throwaway clusters: the partitioned
        # run solves shard-sized batches on shard-sized node sets — fresh
        # jit shapes that must compile before the timed windows
        for parts in (1, 2):
            _w = _partitioned_e2e(n_pods, n_nodes, parts, f"pw{parts}")[0]
            _w.stop()
            del _w
        compiles0 = _solver_jit_cache()
        # >=2-core re-judge (ISSUE 19 satellite): a per-thread CPU sampler
        # rides every 2p timed window so the speedup column is judged from
        # measured overlap_cpu_s, never inferred from wall ratios
        from kubernetes_tpu.obs import ResourceSampler
        judge = len(os.sched_getaffinity(0)) >= 2
        # interleaved best-of-2 per mode (the BindCommit discipline): the
        # co-scheduled rig drifts, alternating keeps the drift off one column
        runs_1p = []  # (wall, bound) pairs — picked together, never mixed
        walls_2p = []
        best2 = None
        for i in range(2):
            _s1, _st1, d1, b1i = _partitioned_e2e(
                n_pods, n_nodes, 1, f"pa{i}")
            _s1.stop()
            del _s1, _st1
            runs_1p.append((d1, b1i))
            samp = ResourceSampler(interval_s=0.05) if judge else None
            c2, stc2, d2, b2 = _partitioned_e2e(
                n_pods, n_nodes, 2, f"pb{i}", sampler=samp)
            walls_2p.append(d2)
            osum = samp.summary() if samp is not None else None
            if best2 is None or d2 < best2[2]:
                if best2 is not None:
                    best2[0].stop()
                best2 = (c2, stc2, d2, b2, f"pb{i}", osum)
            else:
                c2.stop()
                del c2, stc2
        s2, st2, _d2, b2, pfx2, osum2 = best2
        dt1, b1 = min(runs_1p)
        walls_1p = [w for w, _b in runs_1p]
        dt2 = min(walls_2p)
        compiles = sum(v - compiles0.get(k, 0)
                       for k, v in _solver_jit_cache().items() if v >= 0)
        pps1, pps2 = b1 / dt1, b2 / dt2
        rep = pod_conservation_report(
            st2, s2, [f"default/{pfx2}-{i}" for i in range(n_pods)])
        rows = s2.sched_stats()["rows"]
        cores = len(os.sched_getaffinity(0))
        results["Partitioned_2x"] = {
            "pods_per_sec": round(pps2, 1), "wall_s": round(dt2, 3),
            "pods": n_pods, "nodes": n_nodes, "placed": b2,
            "pods_per_sec_1p": round(pps1, 1), "wall_s_1p": round(dt1, 3),
            "speedup_vs_1p": round(pps2 / pps1, 3),
            "walls_1p": [round(w, 3) for w in walls_1p],
            "walls_2p": [round(w, 3) for w in walls_2p],
            # the A/B is a CONCURRENCY claim: on a 1-core box the pipelines
            # time-slice and the speedup column measures overhead+noise,
            # not overlap — publish the cores so the number is interpretable
            # (ROADMAP direction 3 judges scaling on a >=2-core rig)
            "cores": cores,
            "ab_comparable": cores >= 2,
            # measured concurrency (ISSUE 19 satellite): cpu beyond wall
            # inside the winning 2p window; None = 1-core rig, not judged
            "overlap_cpu_s": (osum2["overlap_cpu_s"] if osum2 else None),
            "concurrency_verdict": _overlap_verdict(
                osum2["overlap_cpu_s"] if osum2 else None, dt2),
            "concurrent_drive": s2.concurrent_drive,
            "conflicts": s2.conflicts_total,
            "reroutes": s2.reroutes_total,
            "residual_passes": s2.residual_passes,
            "conservation": rep["counts"],
            "conservation_ok": (rep["counts"]["lost"] == 0
                                and rep["counts"]["double_bound"] == 0
                                and rep["counts"]["bound"] == n_pods),
            "solver_compiles_during_run": compiles,
            "per_partition": [{"index": r["index"], "nodes": r["nodes"],
                               "scheduled": r["scheduled"],
                               "conflicts": r["conflicts"],
                               "reroutes": r["reroutes"],
                               "breaker": r["breaker"]} for r in rows],
            "solver": "fast+partitioned"}
        s2.stop()  # release bind workers before later rungs
        print(f"{'Partitioned_2x':>28}: {pps2:>9.0f} pods/s  "
              f"({b2}/{n_pods} bound; 1p {pps1:.0f} pods/s, "
              f"speedup {pps2 / pps1:.2f}x, "
              f"conflicts={s2.conflicts_total} "
              f"reroutes={s2.reroutes_total})", file=sys.stderr)
    except Exception as e:
        results["Partitioned_2x"] = {"error": str(e)[:200]}
        print(f"Partitioned_2x: ERROR {e}", file=sys.stderr)


def _solver_jit_cache():
    """Per-solver compiled-variant counts (jax's per-function jit cache).
    Stable counts across same-bucket batches = the cache is hot; a growing
    count is retrace churn (tens of seconds per compile at TPU scale).
    -1 when the introspection API is unavailable."""
    from kubernetes_tpu.models.defrag import defrag_assign
    from kubernetes_tpu.models.gangcover import cover_curve, rank_align_kernel
    from kubernetes_tpu.models.repair import repair_check
    from kubernetes_tpu.models.transport import _auction_phase, _sinkhorn_iters
    from kubernetes_tpu.models.waterfill import waterfill_group
    from kubernetes_tpu.ops.solver import greedy_scan_solve

    out = {}
    for name, fn in (("waterfill_group", waterfill_group),
                     ("greedy_scan_solve", greedy_scan_solve),
                     ("repair_check", repair_check),
                     ("auction_phase", _auction_phase),
                     ("sinkhorn_iters", _sinkhorn_iters),
                     ("cover_curve", cover_curve),
                     ("rank_align_kernel", rank_align_kernel),
                     ("defrag_assign", defrag_assign)):
        try:
            out[name] = int(fn._cache_size())
        except Exception:
            out[name] = -1
    return out


def _rig_info():
    """Honesty columns every rung carries (ISSUE 13 satellite): this series
    has crossed containers with 2 -> 1 cores (BENCH_r07..r11) and cross-run
    comparisons kept tripping on it — the rig's core count and cgroup cpu
    quota are now part of every workload's JSON, not just the A/B columns."""
    try:
        cores = len(os.sched_getaffinity(0))
    except Exception:
        cores = os.cpu_count() or 0
    quota = None
    try:  # cgroup v2
        raw = open("/sys/fs/cgroup/cpu.max").read().split()
        if raw and raw[0] != "max":
            quota = round(int(raw[0]) / int(raw[1]), 2)
    except Exception:
        try:  # cgroup v1
            q = int(open("/sys/fs/cgroup/cpu/cpu.cfs_quota_us").read())
            p = int(open("/sys/fs/cgroup/cpu/cpu.cfs_period_us").read())
            if q > 0:
                quota = round(q / p, 2)
        except Exception:
            pass
    return {"cores": cores, "cpu_quota": quota}


def _overlap_verdict(overlap_cpu_s, wall_s):
    """The >=2-core A/B judge (ISSUE 19 satellite): a speedup column is
    believable only when MEASURED cpu-beyond-wall says the pipelines truly
    ran in parallel — wall-clock ratios on a co-scheduled rig can say
    anything. None = not judged (no sampler / 1-core rig)."""
    if overlap_cpu_s is None or wall_s <= 0:
        return None
    return "parallel" if overlap_cpu_s >= 0.05 * wall_s else "serialized"


def rung_north_star_soak(results):
    """NorthStar_1M (ISSUE 13): the soak rung — the control plane the paper
    describes runs FOREVER, so this rung measures steady state, not a
    single drain: a fixed pod population under sustained create/bind/delete
    churn, with the windowed time-series (obs/timeseries.py) and resource
    sampler (obs/resource.py) watching every window and the trend/leak SLO
    keys (scheduler/slo.py SOAK_SLO) gating the run's SHAPE — per-window
    stage p99 ceilings, RSS + live-object slope, p99 drift — plus zero
    post-warmup solver recompiles. Warmup (initial fill + churn cycles at
    the real shapes) is excluded via the clear()/reset() idiom. The quick
    variant is time-compressed (small windows, seconds of churn); the full
    variant churns ~1M pods through the same steady-state loop."""
    import gc

    from kubernetes_tpu.obs.resource import ResourceSampler
    from kubernetes_tpu.scheduler import Framework
    from kubernetes_tpu.scheduler.batch import BatchScheduler
    from kubernetes_tpu.scheduler.plugins import default_plugins
    from kubernetes_tpu.scheduler.slo import SOAK_SLO, evaluate_slo
    from kubernetes_tpu.store import APIStore
    from kubernetes_tpu.testing import MakePod

    try:
        if SMOKE:
            # time-compressed: small windows, the clock (not a pod count)
            # ends the run — the gate needs enough windows for real trend
            # verdicts, not a churn total
            n_nodes, steady, wave = 256, 2000, 500
            window_s, sample_s, soak_s = 0.5, 0.1, 10.0
            target_churn = None
        else:
            n_nodes, steady, wave = 10_000, 100_000, 25_000
            window_s, sample_s, soak_s = 5.0, 1.0, 300.0
            target_churn = 1_000_000
        soak_s = min(soak_s, max(6.0, budget_left() - 45.0))
        min_windows = 8  # trends over fewer windows are opinions

        # steady-state history bound: the watch-replay log pins one object
        # clone per retained event, so at churn rate it IS the store's
        # resident memory — size it to a few waves of events (~3 events per
        # pod life: create/bind/delete) so memory plateaus during warmup
        # and the rss/alloc slope gates measure the SCHEDULER's behavior,
        # not the log filling up. The soak rung found this: with the
        # 200k-event default the quick run grew ~40MB/s of nothing but
        # history.
        store = APIStore(history_limit=9 * wave if SMOKE else 200_000)
        for n in _nodes(n_nodes, cpu="16", mem="64Gi"):
            store.create("nodes", n)
        sched = BatchScheduler(store, Framework(default_plugins()),
                               batch_size=max(steady, wave), solver="fast",
                               ts_window_s=window_s)
        sampler = ResourceSampler(interval_s=sample_s)
        sched.attach_resource_sampler(sampler)
        sampler.register_thread("sched")  # this (driving) thread
        sampler.start()
        sched.sync()

        seq = 0
        live: list = []  # pod names in creation order (delete oldest first)

        def create_wave(n):
            nonlocal seq
            names = [f"soak-{seq + i}" for i in range(n)]
            seq += n
            store.create_many(
                "pods", (MakePod(nm).req({"cpu": "500m", "memory": "1Gi"})
                         .obj() for nm in names), consume=True)
            live.extend(names)

        def drain_wave(n):
            victims = live[:n]
            del live[:n]
            # chunked like the bind path: delete_pods holds one critical
            # section per call, and a 25k-victim wave must not starve every
            # other store consumer behind one lock hold
            for lo in range(0, len(victims), 4096):
                store.delete_pods([f"default/{nm}"
                                   for nm in victims[lo:lo + 4096]])
            return len(victims)

        # -- warmup: initial fill + churn cycles at the REAL shapes (both
        # pod-axis buckets: the steady fill and the wave) so the measured
        # soak compiles nothing
        create_wave(steady)
        sched.run_until_idle()
        # churn until the ALLOCATOR plateaus, not a fixed cycle count: the
        # first churn cycles keep growing RSS (fresh obmalloc arenas for the
        # transient wave peaks) and a measured window that inherits that
        # warm-up growth fails the slope gate on allocator behavior instead
        # of a leak. Two consecutive stable reads = steady state.
        stable, prev_rss = 0, sampler.rss_mb()
        for _ in range(32):
            drain_wave(wave)
            create_wave(wave)
            sched.run_until_idle()
            cur = sampler.rss_mb()
            stable = stable + 1 if cur - prev_rss < 0.75 else 0
            prev_rss = cur
            if stable >= 2:
                break
        sched.flush_binds()

        # -- measured soak starts here (warmup excluded, the clear idiom).
        # GC stays ENABLED (a forever-running service collects its churn
        # garbage and the sampler measures the pauses) but the steady-state
        # heap is FROZEN: without freeze(), every gen2 pass re-scans the
        # ~stable store/cache graph and lands a 100-300ms pause in whatever
        # stage is running — honest for an untuned process, but the
        # documented long-lived-heap configuration (the NorthStar rung's
        # freeze+disable, minus the disable) is what a production soak runs.
        gc.collect()
        gc.freeze()
        # every post-freeze step runs under the unfreeze finally: an
        # exception escaping with the process frozen would corrupt every
        # later rung's memory/GC behavior
        try:
            # the collect above RETURNS arenas to the OS — a window opened
            # at that trough measures the first seconds re-acquiring the
            # working high-water as "growth" (~100MB/2s observed).
            # Re-churn until RSS is stable again so the measured series
            # starts AT steady state.
            stable, prev_rss = 0, sampler.rss_mb()
            for _ in range(24):
                drain_wave(wave)
                create_wave(wave)
                sched.run_until_idle()
                cur = sampler.rss_mb()
                stable = stable + 1 if abs(cur - prev_rss) < 0.75 else 0
                prev_rss = cur
                if stable >= 2:
                    break
            sched.flightrec.clear()
            sched.podtrace.clear()
            sched.timeseries.clear()
            sampler.reset()
            compiles0 = _solver_jit_cache()
            churned = 0
            t0 = time.perf_counter()
            deadline = t0 + soak_s
            while time.perf_counter() < deadline:
                if (target_churn is not None and churned >= target_churn
                        and sched.timeseries.windows_closed >= min_windows):
                    break  # full-size: 1M churned and a real trend axis
                drain_wave(wave)
                create_wave(wave)
                sched.run_until_idle()
                churned += wave
        finally:
            gc.unfreeze()
        dt = time.perf_counter() - t0
        sampler.stop()
        windows = sched.timeseries.windows()
        compiles = sum(v - compiles0.get(k, 0)
                       for k, v in _solver_jit_cache().items() if v >= 0)

        spec = dict(SOAK_SLO)
        if SMOKE:
            # time compression divides the same absolute allocator noise by
            # a baseline ~30x shorter: one ~25MB obmalloc arena step
            # anywhere in a 10s axis reads ~150MB/min, and such steps DO
            # happen at steady state (measured run to run). Size the quick
            # ceiling above the step noise — a real pin (one leaked
            # scheduler graph per window) reads thousands of MB/min, still
            # an order of magnitude past this — and let the alloc-blocks
            # gate keep the deterministic live-object precision
            spec["rss_slope_mb_per_min"] = 300.0
            spec["alloc_block_slope_per_s"] = 500_000.0
        # the NEW layers' measured overhead gates the <2% budget (ISSUE 13
        # acceptance): timeseries taps + sampler ticks — deterministic
        # costs. The flight recorder's own self-time is published beside it
        # but gated by the NorthStar rung, where production batch sizes
        # amortize it: at smoke's 500-pod batches its tiny wall-clock
        # windows mostly measure 1-core co-scheduling preemption noise.
        instr_s = sched.timeseries.self_seconds + sampler.self_seconds
        instr_frac = (instr_s / max(dt, 1e-9)) if instr_s > 0.002 else 0.0
        spec["instrumentation_frac"] = 0.02
        slo = evaluate_slo(
            {"windows": windows}, spec,
            extra={"solver_compiles": compiles,
                   "instrumentation_frac": round(instr_frac, 5)})
        # the gate the tier asserts: windowed SLOs PASS with the trend
        # checks REAL (enough windows to fit a slope), zero recompiles
        trend_real = not any(c.startswith(("rss_slope", "alloc_block",
                                           "p99_drift"))
                             for c in slo["skipped"])
        res = sampler.summary()
        # the per-window zero-alloc gauge (ISSUE 16): under full churn the
        # DELETED-event contract materializes every drained victim (honest
        # column — the scheduling path itself allocates nothing), so the
        # soak publishes the distribution rather than gating on zero
        alloc_vals = [a for a in
                      ((w.get("alloc") or {}).get("pod_obj_allocs")
                       for w in windows) if a is not None]
        results["NorthStar_1M"] = {
            "pods_per_sec": round(churned / dt, 1), "wall_s": round(dt, 3),
            "pods": churned, "steady_pods": steady, "wave": wave,
            "nodes": n_nodes, "placed": churned,
            "windows": len(windows),
            "window_s": window_s,
            "windows_sample": windows[-3:],
            "pod_obj_allocs": {
                "windows_counted": len(alloc_vals),
                "zero_windows": sum(1 for a in alloc_vals if a == 0),
                "max_per_window": max(alloc_vals) if alloc_vals else None,
                "total": sum(alloc_vals) if alloc_vals else None,
            },
            "resource": res,
            "slo": slo, "soak_ok": bool(slo["pass"] and trend_real
                                        and compiles == 0),
            "solver_compiles_during_run": compiles,
            "instrumentation_s": round(instr_s, 6),
            "instrumentation_frac": round(instr_frac, 5),
            "flightrec_self_s": round(sched.flightrec.self_seconds, 6),
            "sampler_overhead_frac": res["overhead_frac"],
            "clock_source": res["clock_source"],
            "clock_resolution_s": res["clock_resolution_s"],
            "solver": "fast+store-binds+churn"}
        sched.stop()
        print(f"{'NorthStar_1M':>28}: {churned / dt:>9.0f} pods/s sustained "
              f"({churned} churned over {len(windows)} windows in {dt:.1f}s; "
              f"rss {res['rss_mb']}MB (+{res['rss_growth_mb']}), "
              f"SLO {'PASS' if slo['pass'] else 'FAIL ' + str(slo['failed'])}"
              f", compiles={compiles})", file=sys.stderr)
    except Exception as e:
        # a failed rung must not leave ITS threads churning (or its
        # sampler ticking) through every later rung's timed window
        for owner in (locals().get("sampler"), locals().get("sched")):
            try:
                if owner is not None:
                    owner.stop()
            except Exception:
                pass
        results["NorthStar_1M"] = {"error": str(e)[:200]}
        print(f"NorthStar_1M: ERROR {e}", file=sys.stderr)


def rung_schedlint(results):
    """SchedLint_tree: the static-analysis gate's whole-tree self-time. The
    analyzer runs inside tier-1 (tests/test_schedlint.py), so its wall time
    is a budget like the flight recorder's: tests/test_bench_quick.py
    asserts it stays cheap AND clean (0 findings) so the gate can't quietly
    become the slowest — or a red — part of tier-1."""
    from kubernetes_tpu.analysis.schedlint import package_root, run_paths

    try:
        t0 = time.perf_counter()
        findings, stats = run_paths([package_root()])
        dt = time.perf_counter() - t0
        results["SchedLint_tree"] = {
            "wall_s": round(dt, 3), "findings": len(findings),
            "suppressed": stats["suppressed"], "files": stats["files"],
            # interprocedural closure shape (ISSUE 20): edge count and the
            # deepest chain any rule actually walked, so a regression in
            # resolution (edges collapsing to ~0) or a blow-up (depth
            # hitting the cap) is visible in BENCH history
            "callgraph_edges": stats["callgraph_edges"],
            "resolve_depth": stats["resolve_depth"],
            # the published hard budget tests/test_bench_quick.py asserts
            "budget_s": 15.0}
        print(f"{'SchedLint_tree':>28}: {stats['files']} files, "
              f"{len(findings)} findings, {stats['suppressed']} suppressed, "
              f"{stats['callgraph_edges']} call edges (depth "
              f"{stats['resolve_depth']}) in {dt:.2f}s", file=sys.stderr)
    except Exception as e:
        results["SchedLint_tree"] = {"error": str(e)[:200]}
        print(f"SchedLint_tree: ERROR {e}", file=sys.stderr)


def rung_bind_commit(results):
    """BindCommit_20k: store.bind_many throughput in ISOLATION (the PR 4
    clone-free commit path) — 20k pending pods bound in bind-worker-sized
    chunks with only a coalescing watcher subscribed (the scheduler steady
    state: lazy shared events, no per-object clones, sharded lock), no
    scheduler and no flight recorder involved. Fixed-size like the gang
    rung: 20k binds run in a fraction of a second, so the rung doubles as
    the quick-tier smoke for the store commit hot path."""
    from kubernetes_tpu.store import APIStore
    from kubernetes_tpu.testing import MakePod

    try:
        import gc

        from kubernetes_tpu.native import hostcommit

        n, chunk = 20_000, 4096

        def run_once(native, columnar=False):
            # columnar=False pins the DICT commit path for the legacy
            # python-vs-native columns; the columnar legs (ISSUE 15) run
            # the same workload through the column-write commit
            store = APIStore(native_commit=native, columnar=columnar)
            w = store.watch(kind=("pods",), coalesce=True)
            store.create_many(
                "pods", (MakePod(f"bc-{i}").req({"cpu": "100m"}).obj()
                         for i in range(n)), consume=True)
            w.drain()
            triples = [("default", f"bc-{i}", f"node-{i % 512}")
                       for i in range(n)]
            # timed window with the collector frozen+disabled, like the
            # NorthStar rung: gen2 sweeps over the 20k-pod heap otherwise
            # dominate (and randomize) the ~µs/pod commit numbers the
            # python-vs-native columns exist to compare. try/finally: an
            # assert/bind failure must not leave GC off for every later rung
            gc.collect()
            gc.freeze()
            gc.disable()
            try:
                t0 = time.perf_counter()
                bound = 0
                for lo in range(0, n, chunk):
                    b, errs = store.bind_many(triples[lo:lo + chunk],
                                              origin="bench")
                    bound += b
                    assert not errs, errs[:3]
                dt = time.perf_counter() - t0
            finally:
                gc.enable()
                gc.unfreeze()
            return bound, dt

        # python-vs-native columns (ISSUE 11): the SAME workload through the
        # Python oracle and the C-API commit engine — the before/after pair
        # for the native commit-loop port, asserted by test_bench_quick.py.
        # Interleaved best-of-2 per mode (P,N,P,N): harness co-scheduling
        # drifts on a 2-core rig, and alternating the modes keeps the drift
        # from landing entirely on one column.
        from kubernetes_tpu.store import columnar as _columnar_mod

        native_ok = hostcommit.available()
        columnar_ok = (_columnar_mod.numpy_available()
                       and _columnar_mod.env_enabled())
        bound, _warm = run_once(native_ok)  # warm-up (faults obmalloc arenas)
        # interleaved best-of-2 per mode (the BindCommit discipline), the
        # columnar A/B leg riding the same rounds: dict-python, dict-native,
        # columnar — the µs/pod dict-vs-columnar pair is a SAME-BOX
        # interleaved A/B by construction (BENCH_r12 discipline: rig core
        # counts vary across the series, so only same-box pairs compare)
        py_runs, nat_runs, col_runs = [], [], []
        for _ in range(2):
            py_runs.append(run_once(False)[1])
            if native_ok:
                nat_runs.append(run_once(True)[1])
            if columnar_ok:
                col_runs.append(run_once(native_ok, columnar=True)[1])
        dt_py = min(py_runs)
        dt = min(nat_runs) if native_ok else dt_py
        dt_col = min(col_runs) if columnar_ok else None
        us_dict = dt / n * 1e6
        pps = n / (dt_col if dt_col is not None else dt)
        results["BindCommit_20k"] = {
            "pods_per_sec": round(pps, 1),
            "wall_s": round(dt_col if dt_col is not None else dt, 4),
            "placed": bound, "pods": n,
            "us_per_pod": round((dt_col if dt_col is not None else dt)
                                / n * 1e6, 2),
            "native": {
                "available": native_ok,
                "us_per_pod_python": round(dt_py / n * 1e6, 2),
                "us_per_pod_native": (round(dt / n * 1e6, 2)
                                      if native_ok else None),
            },
            # columnar pod-row store (ISSUE 15): dict vs columnar on the
            # SAME box, interleaved; honesty flags per the r12 discipline
            "columnar": dict({
                "available": columnar_ok,
                "us_per_pod_dict": round(us_dict, 2),
                "us_per_pod_columnar": (round(dt_col / n * 1e6, 2)
                                        if dt_col is not None else None),
                "speedup": (round(dt / dt_col, 2)
                            if dt_col is not None else None),
                "ab_comparable": True,  # interleaved same-box by design
            }, **_rig_info()),
            "solver": ("bind_many-columnar" if columnar_ok
                       else "bind_many-native" if native_ok
                       else "bind_many-python")}
        print(f"{'BindCommit_20k':>28}: {pps:>9.0f} pods/s  "
              f"({bound}/{n} bound, python {dt_py / n * 1e6:.1f}us/pod"
              + (f", native {us_dict:.1f}us/pod" if native_ok
                 else ", native unavailable")
              + (f", columnar {dt_col / n * 1e6:.2f}us/pod"
                 if dt_col is not None else ", columnar unavailable")
              + ")", file=sys.stderr)
    except Exception as e:
        results["BindCommit_20k"] = {"error": str(e)[:200]}
        print(f"BindCommit_20k: ERROR {e}", file=sys.stderr)


def rung_sched_stages(results):
    """SchedStages_8k (ISSUE 16): per-stage same-box A/B columns for the
    four steady-state stages the end-to-end columnar pipeline rewrote, each
    measured columnar-vs-object under the BindCommit discipline (interleaved
    best-of-2, GC frozen, rig honesty flags):

      build_pod_batch  store sig-column memo re-seed vs object signature walk
      assume           column insert (assume_pods_columnar) vs per-pod
                       structural PodInfo appends (both phase-1-only; phase 2
                       is the shared scatter either way)
      tensorize        dirty-name diff (changed_names) vs identity walk over
                       every node, at the steady-state delta shape (a few
                       dirty nodes out of the fleet)
      dispatch         clone-free handoff vs pod_bind_clone per pod
    """
    import gc

    from kubernetes_tpu.scheduler import Framework
    from kubernetes_tpu.scheduler.batch import BatchScheduler
    from kubernetes_tpu.scheduler.cache import Cache
    from kubernetes_tpu.scheduler.plugins import default_plugins
    from kubernetes_tpu.snapshot.tensorizer import (TensorCache,
                                                    build_cluster_tensors,
                                                    build_pod_batch)
    from kubernetes_tpu.store import APIStore
    from kubernetes_tpu.store.store import pod_bind_clone
    from kubernetes_tpu.testing import MakePod

    try:
        n_pods, n_nodes = sz(8000, floor=128), sz(256, floor=16)
        store = APIStore()
        nodes = _nodes(n_nodes, cpu="64", mem="256Gi")
        for nd in nodes:
            store.create("nodes", nd)
        node_names = [nd.metadata.name for nd in nodes]
        sched = BatchScheduler(store, Framework(default_plugins()),
                               batch_size=n_pods, solver="exact",
                               columnar=True)
        sched.sync()
        store.create_many(
            "pods", (MakePod(f"ss-{i}").req({"cpu": "100m", "memory": "64Mi"})
                     .obj() for i in range(n_pods)), consume=True)
        sched.pump_events()
        snap = sched.cache.update_snapshot()
        cluster = build_cluster_tensors(snap)
        pods = sorted(store.list("pods")[0], key=lambda p: p.key)
        getcols = getattr(store, "pod_columns", None)
        store_cols = getcols() if getcols else None

        def strip_memos():
            for p in pods:
                p.__dict__.pop("_class_sig", None)
                p.__dict__.pop("_req_sig", None)

        def t_build(cols):
            strip_memos()
            t0 = time.perf_counter()
            build_pod_batch(pods, snap, cluster, store_cols=cols)
            return time.perf_counter() - t0

        assume_pairs = [(p, node_names[i % n_nodes])
                        for i, p in enumerate(pods)]

        def t_assume(columnar):
            cache = Cache()
            for nd in nodes:
                cache.add_node(nd)
            t0 = time.perf_counter()
            if columnar:
                bad = cache.assume_pods_columnar(assume_pairs)
            else:
                bad = cache.assume_pods_structural(assume_pairs)
            dt = time.perf_counter() - t0
            assert not bad, bad[:3]
            return dt

        # steady-state delta shape: a handful of dirty nodes out of the fleet
        k_dirty = max(1, n_nodes // 32)
        extra = [MakePod(f"ssx-{i}").req({"cpu": "50m"}).obj()
                 for i in range(k_dirty)]
        sched.cache.assume_pods(
            [(p, node_names[i]) for i, p in enumerate(extra)])
        snap2 = sched.cache.update_snapshot()

        def t_tensorize(incremental):
            tc = TensorCache()
            tc.cluster_tensors(snap)  # re-base off the pre-delta snapshot
            saved = snap2.changed_names
            if not incremental:
                snap2.changed_names = None  # force the identity-walk oracle
            try:
                t0 = time.perf_counter()
                tc.cluster_tensors(snap2)
                return time.perf_counter() - t0
            finally:
                snap2.changed_names = saved

        def t_dispatch(clone):
            t0 = time.perf_counter()
            if clone:
                out = [pod_bind_clone(p) for p in pods]
            else:
                out = list(pods)
            dt = time.perf_counter() - t0
            assert len(out) == n_pods
            return dt

        stages = {"build_pod_batch": (t_build, store_cols, None),
                  "assume": (t_assume, True, False),
                  "tensorize": (t_tensorize, True, False),
                  "dispatch": (t_dispatch, False, True)}
        gc.collect()
        gc.freeze()
        gc.disable()
        try:
            cols_out = {}
            for name, (fn, col_arg, obj_arg) in stages.items():
                fn(col_arg)  # warm-up
                col_runs, obj_runs = [], []
                for _ in range(2):  # interleaved best-of-2 per mode
                    col_runs.append(fn(col_arg))
                    obj_runs.append(fn(obj_arg))
                dt_c, dt_o = min(col_runs), min(obj_runs)
                per = n_pods if name != "tensorize" else 1
                unit = "us_per_pod" if name != "tensorize" else "us_per_diff"
                cols_out[name] = {
                    f"{unit}_columnar": round(dt_c / per * 1e6, 3),
                    f"{unit}_object": round(dt_o / per * 1e6, 3),
                    "speedup": round(dt_o / dt_c, 2) if dt_c > 0 else None,
                }
        finally:
            gc.enable()
            gc.unfreeze()
        results["SchedStages_8k"] = dict({
            "pods": n_pods, "nodes": n_nodes, "dirty_nodes": k_dirty,
            "store_cols": store_cols is not None,
            "stages": cols_out,
            "ab_comparable": True,  # interleaved same-box by design
        }, **_rig_info())
        print(f"{'SchedStages_8k':>28}: "
              + "  ".join(f"{k} x{v['speedup']}"
                          for k, v in cols_out.items()), file=sys.stderr)
    except Exception as e:
        results["SchedStages_8k"] = {"error": str(e)[:200]}
        print(f"SchedStages_8k: ERROR {e}", file=sys.stderr)


def _gang_adjacency(store, sched):
    """Placement-quality column (ISSUE 14): mean intra-gang neighbor ring
    distance of the BOUND members, measured from the STORE (labels + node
    topology), independent of the scheduler's own stats."""
    from kubernetes_tpu.api.podgroup import pod_gang_rank, pod_group_key
    from kubernetes_tpu.models.gangcover import mean_neighbor_distance
    from kubernetes_tpu.scheduler.gang import node_slice_positions, \
        ring_lengths
    from kubernetes_tpu.snapshot.tensorizer import build_cluster_tensors

    cl = build_cluster_tensors(sched.cache.update_snapshot())
    slice_ids, pos = node_slice_positions(cl)
    if slice_ids is None:
        return None
    node_idx = {n: i for i, n in enumerate(cl.node_names)}
    gids, groups, ranks, slices, poss = {}, [], [], [], []
    for p in store.list("pods")[0]:
        g = pod_group_key(p)
        if not g or not p.spec.node_name:
            continue
        ni = node_idx[p.spec.node_name]
        gids.setdefault(g, len(gids))
        groups.append(gids[g])
        ranks.append(pod_gang_rank(p))
        slices.append(int(slice_ids[ni]))
        poss.append(int(pos[ni]))
    return mean_neighbor_distance(groups, ranks, slices, poss,
                                  ring_lengths(slice_ids, pos))


def rung_gang(results):
    """GangScheduling_2k_250: 8 PodGroups x 250 RANKED members bound
    end-to-end — store ingest, queue gang staging, the all-or-nothing veto,
    slice-packing score, rank alignment, and batched binds all inside the
    timed window. Publishes the adjacency placement-quality column (ISSUE
    14): mean intra-gang neighbor ring distance, rank-aligned vs the
    rank-blind baseline (same workload, rank_align=False). Fixed-size (no
    SMOKE shrink): the rung IS the quick-tier gang smoke and 2k pods solves
    in a few seconds on the CPU rig."""
    from kubernetes_tpu.scheduler import Framework
    from kubernetes_tpu.scheduler.batch import BatchScheduler
    from kubernetes_tpu.scheduler.plugins import default_plugins
    from kubernetes_tpu.store import APIStore
    from kubernetes_tpu.testing import MakeNode, MakePod, make_pod_group

    try:
        n_gangs, members, n_nodes, n_slices = 8, 250, 256, 4

        def gang_nodes():
            return [MakeNode(f"node-{i}")
                    .tpu_slice(i % n_slices, index=i // n_slices)
                    .capacity({"cpu": "16", "memory": "64Gi",
                               "pods": "110"}).obj() for i in range(n_nodes)]

        def gang_pods():
            return [MakePod(f"gp-{g}-{i}").gang(f"train-{g}", rank=i)
                    .req({"cpu": "500m", "memory": "1Gi"}).obj()
                    for g in range(n_gangs) for i in range(members)]

        def run_once(rank_align=True):
            store = APIStore()
            for n in gang_nodes():
                store.create("nodes", n)
            sched = BatchScheduler(store, Framework(default_plugins()),
                                   batch_size=4096, solver="fast",
                                   rank_align=rank_align)
            sched.sync()
            for g in range(n_gangs):
                store.create("podgroups", make_pod_group(f"train-{g}", members))
            store.create_many("pods", gang_pods(), consume=True)
            t0 = time.perf_counter()
            sched.run_until_idle()
            dt = time.perf_counter() - t0
            return sched, store, dt

        wsched, _wstore, _wdt = run_once()  # warm-up: compile at real shapes
        wsched.stop()  # release the bind worker (PR 11 discard hygiene)
        sched, store, dt = run_once()
        adjacency = _gang_adjacency(store, sched)
        # rank-blind baseline: the SAME workload with the alignment pass off
        # — what greedy water-filling alone gives consecutive ranks
        bsched, bstore, _bdt = run_once(rank_align=False)
        adjacency_blind = _gang_adjacency(bstore, bsched)
        bsched.stop()
        sched.stop()
        n_pods = n_gangs * members
        bound = sched.scheduled_count
        pps = bound / dt if dt > 0 else 0.0
        results["GangScheduling_2k_250"] = {
            "pods_per_sec": round(pps, 1), "wall_s": round(dt, 3),
            "placed": bound, "pods": n_pods, "gangs": n_gangs,
            "gang_vetoes": sched.gang_vetoes,
            "adjacency": {
                "mean_neighbor_distance": (round(adjacency, 3)
                                           if adjacency is not None
                                           else None),
                "mean_neighbor_distance_rank_blind": (
                    round(adjacency_blind, 3)
                    if adjacency_blind is not None else None),
                "placed_rank_blind": bsched.scheduled_count,
            },
            "solver": "fast+gang+rank-align+store-binds"}
        print(f"{'GangScheduling_2k_250':>28}: {pps:>9.0f} pods/s  "
              f"({bound}/{n_pods} bound in {n_gangs} gangs, "
              f"{sched.gang_vetoes} vetoes, adjacency "
              f"{adjacency if adjacency is None else round(adjacency, 3)} vs "
              f"rank-blind "
              f"{adjacency_blind if adjacency_blind is None else round(adjacency_blind, 3)}, "
              f"{dt:.3f}s)", file=sys.stderr)
    except Exception as e:
        results["GangScheduling_2k_250"] = {"error": str(e)[:200]}
        print(f"GangScheduling_2k_250: ERROR {e}", file=sys.stderr)


def rung_gang_preempt(results):
    """GangPreemption (ISSUE 14): the victim-cover rung, quick tier. A
    2-slice cluster full of low-priority fillers takes a high-priority gang
    that cannot fit anywhere: the preemptor must select the MIN-COST victim
    set whose release fits the entire quorum on one slice (6 of 8 fillers,
    not all 8), delete it through the batched store path, park the gang,
    and place it WHOLE on release — inside a bounded wall with zero mid-run
    solver compiles. A second, larger gang has only PARTIAL room on every
    slice: it must be vetoed with a narrated event and ZERO further
    evictions. Pod conservation asserted over both gangs."""
    from kubernetes_tpu.scheduler import Framework
    from kubernetes_tpu.scheduler.batch import BatchScheduler
    from kubernetes_tpu.scheduler.plugins import default_plugins
    from kubernetes_tpu.store import APIStore
    from kubernetes_tpu.testing import (MakeNode, MakePod, make_pod_group,
                                        pod_conservation_report)

    try:
        n_slices, per_slice = 2, 8
        gang_n, big_n = 12, 40  # 12 fits one slice after 6 evictions; 40 never

        def build():
            store = APIStore()
            for s in range(n_slices):
                for i in range(per_slice):
                    store.create("nodes", MakeNode(f"node-{s}-{i}")
                                 .tpu_slice(s, index=i)
                                 .capacity({"cpu": "8", "memory": "32Gi",
                                            "pods": "110"}).obj())
            for s in range(n_slices):
                for i in range(per_slice):
                    low = MakePod(f"low-{s}-{i}").priority(1).req(
                        {"cpu": "6"}).obj()
                    low.spec.node_name = f"node-{s}-{i}"
                    store.create("pods", low)
            sched = BatchScheduler(store, Framework(default_plugins()),
                                   batch_size=1024, solver="fast",
                                   pod_initial_backoff=0.05,
                                   pod_max_backoff=0.2)
            sched.sync()
            return store, sched

        def gang_pods(name, n):
            return [MakePod(f"{name}-{i}").gang(name, rank=i).priority(100)
                    .req({"cpu": "3"}).obj() for i in range(n)]

        def drive(store, sched, prefix, want, deadline_s):
            bound = 0
            deadline = time.perf_counter() + deadline_s
            while time.perf_counter() < deadline:
                sched.run_until_idle()
                sched.queue.flush_backoff_completed()
                sched.pump_events()
                bound = sum(1 for p in store.list("pods")[0]
                            if p.metadata.name.startswith(f"{prefix}-")
                            and p.spec.node_name)
                if bound >= want:
                    return bound
                time.sleep(0.02)
            return bound

        def run_once():
            store, sched = build()
            store.create("podgroups", make_pod_group("gp", gang_n))
            pods = gang_pods("gp", gang_n)
            store.create_many("pods", pods, consume=True)
            t0 = time.perf_counter()
            bound = drive(store, sched, "gp", gang_n,
                          20.0 if SMOKE else 60.0)
            dt = time.perf_counter() - t0
            return store, sched, pods, bound, dt

        # warm-up: compile the cover/alignment kernels at the run's shapes
        _wst, wsched, _wp, _wb, _wdt = run_once()
        wsched.stop()
        compiles0 = _solver_jit_cache()
        store, sched, pods, bound, dt = run_once()
        # watermark read HERE: the veto leg below runs new shapes (a
        # 40-member alignment axis) by design — the zero-compile claim is
        # about the preemption run the warm-up covered
        compiles = sum(v - compiles0.get(k, 0)
                       for k, v in _solver_jit_cache().items() if v >= 0)
        stats = sched.gangpreempt.stats()
        fillers_left = sorted(p.metadata.name for p in store.list("pods")[0]
                              if p.metadata.name.startswith("low-"))
        slices_used = {n.spec.node_name.split("-")[1]
                       for n in store.list("pods")[0]
                       if n.metadata.name.startswith("gp-")
                       and n.spec.node_name}
        adjacency = _gang_adjacency(store, sched)
        rep = pod_conservation_report(store, sched, [p.key for p in pods])

        # --- partial-room leg: a gang NO slice can host even after evicting
        # every remaining filler — vetoed, narrated, zero evictions
        pods_before = len(store.list("pods")[0])
        store.create("podgroups", make_pod_group("big", big_n))
        big = gang_pods("big", big_n)
        store.create_many("pods", big, consume=True)
        sched.run_until_idle()
        sched.pump_events()
        veto_stats = sched.gangpreempt.stats()
        big_bound = sum(1 for p in store.list("pods")[0]
                        if p.metadata.name.startswith("big-")
                        and p.spec.node_name)
        evictions_after_veto = (pods_before + big_n
                                - len(store.list("pods")[0]))
        veto_events = sum(1 for e in store.list("events")[0]
                          if (e.reason or "") == "GangPreemptionVetoed")
        rep_big = pod_conservation_report(
            store, sched, [p.key for p in pods + big])
        sched.stop()
        c = rep["counts"]
        ok = (bound == gang_n and len(slices_used) == 1
              and stats["preempted"] == 1 and stats["victims"] == 6
              and len(fillers_left) == per_slice * n_slices - 6
              and c["lost"] == 0 and c["double_bound"] == 0
              and big_bound == 0 and evictions_after_veto == 0
              and veto_stats["vetoed_partial"] >= 1 and veto_events >= 1
              and rep_big["counts"]["lost"] == 0
              and rep_big["counts"]["double_bound"] == 0)
        results["GangPreemption"] = {
            "wall_s": round(dt, 3), "placed": bound, "pods": gang_n,
            "victims": stats["victims"],
            "cover_cost": stats["cover_cost"],
            "slices_ripped": stats["slices_ripped"],
            "vetoed_partial": veto_stats["vetoed_partial"],
            "veto_evictions": evictions_after_veto,
            "veto_narrated": veto_events,
            "adjacency_mean_neighbor_distance": (
                round(adjacency, 3) if adjacency is not None else None),
            "conservation": c, "conservation_ok": ok,
            "solver_compiles_during_run": compiles,
            "preempt_ok": ok,
            "solver": "fast+gang-preempt+victim-cover"}
        print(f"{'GangPreemption':>28}: {bound}/{gang_n} placed whole via "
              f"{stats['victims']}-victim cover in {dt:.3f}s "
              f"(cost {stats['cover_cost']}, compiles={compiles}; "
              f"partial-room gang vetoed: {veto_stats['vetoed_partial']} "
              f"veto(s), {evictions_after_veto} evictions)", file=sys.stderr)
    except Exception as e:
        results["GangPreemption"] = {"error": str(e)[:200]}
        print(f"GangPreemption: ERROR {e}", file=sys.stderr)


def rung_defrag(results):
    """Defrag (ISSUE 17): the rebalancer A/B, quick tier. Churn smears one
    low-priority filler onto every node of a 2-slice cluster — each node is
    half-full, no node can host a gang member, and an arriving gang's ONLY
    path is destroying work through preemption. Same box, same scheduler
    config, two legs: OFF (no rebalancer — the gang admits via the victim
    cover, evicting fillers) vs ON (the background rebalancer consolidates
    the fillers into one slice between workloads, inside the hard per-cycle
    migration budget, so the SAME gang admits with ZERO preemptions). Gates:
    preemption rate AND admission latency improve ON vs OFF, the migration
    budget is never exceeded (checked per cycle, not just in aggregate),
    pod conservation holds through the migration chain, the windowed SLO
    verdict passes on both legs, and the timed window compiles nothing
    (the warm-up leg covers the defrag kernel's pow2 buckets)."""
    from kubernetes_tpu.scheduler import Framework
    from kubernetes_tpu.scheduler.batch import BatchScheduler
    from kubernetes_tpu.scheduler.plugins import default_plugins
    from kubernetes_tpu.scheduler.slo import evaluate_slo
    from kubernetes_tpu.store import APIStore
    from kubernetes_tpu.testing import (MakeNode, MakePod, make_pod_group,
                                        pod_conservation_report)

    DEFRAG_SLO = {"submit_to_bound_p99_s": 30.0}

    try:
        n_slices, per_slice, gang_n = 2, 4, 4
        budget_wave, budget_cycle = 2, 8

        def build():
            store = APIStore()
            for s in range(n_slices):
                for i in range(per_slice):
                    store.create("nodes", MakeNode(f"node-{s}-{i}")
                                 .tpu_slice(s, index=i)
                                 .capacity({"cpu": "8", "memory": "32Gi",
                                            "pods": "110"}).obj())
            fillers = []
            for s in range(n_slices):
                for i in range(per_slice):
                    low = MakePod(f"low-{s}-{i}").priority(1).req(
                        {"cpu": "3"}).obj()
                    low.spec.node_name = f"node-{s}-{i}"
                    store.create("pods", low)
                    fillers.append(low)
            sched = BatchScheduler(store, Framework(default_plugins()),
                                   batch_size=1024, solver="fast",
                                   pod_initial_backoff=0.05,
                                   pod_max_backoff=0.2)
            sched.sync()
            return store, sched, fillers

        def drive(store, sched, want, deadline_s):
            bound = 0
            deadline = time.perf_counter() + deadline_s
            while time.perf_counter() < deadline:
                sched.run_until_idle()
                sched.queue.flush_backoff_completed()
                sched.pump_events()
                bound = sum(1 for p in store.list("pods")[0]
                            if p.metadata.name.startswith("gang-")
                            and p.spec.node_name)
                if bound >= want:
                    break
                time.sleep(0.02)
            return bound

        def run_leg(rebalance):
            store, sched, fillers = build()
            rb = None
            budget_ok = True
            frag_before = frag_after = 0.0
            if rebalance:
                def probe():
                    # the mid-plan abort hook, wired to the REAL windowed
                    # SLO verdict (skipped checks pass; a degraded tail
                    # stops the remaining waves)
                    return evaluate_slo(sched.sched_stats(),
                                        DEFRAG_SLO)["pass"]

                rb = sched.enable_rebalancer(
                    frag_threshold=0.25, budget_per_wave=budget_wave,
                    budget_per_cycle=budget_cycle, priority_ceiling=50,
                    slo_probe=probe)
                # background consolidation between workloads: cycle to the
                # no-op steady state, auditing the budget on EVERY cycle
                for ci in range(8):
                    r = rb.cycle()
                    budget_ok &= (r.get("migrations", 0) <= budget_cycle)
                    if ci == 0:
                        frag_before = r.get("frag", 0.0)
                    sched.pump_events()
                    if not r.get("migrations"):
                        frag_after = r.get("frag", frag_before)
                        break
            store.create("podgroups", make_pod_group("gang", gang_n))
            gang = [MakePod(f"gang-{i}").gang("gang", rank=i).priority(100)
                    .req({"cpu": "6"}).obj() for i in range(gang_n)]
            t0 = time.perf_counter()
            store.create_many("pods", gang, consume=True)
            bound = drive(store, sched, gang_n, 20.0 if SMOKE else 60.0)
            dt = time.perf_counter() - t0
            victims = sched.gangpreempt.stats()["victims"]
            # conservation through the migration chain: ON leg fillers may
            # have been re-placed under -mgN names (resolve_keys follows
            # the victim->replacement chain); OFF leg fillers are LEGALLY
            # destroyed by preemption, so only the gang is gated there
            keys = [p.key for p in gang]
            if rb is not None:
                keys += rb.resolve_keys([p.key for p in fillers])
            rep = pod_conservation_report(store, sched, keys)
            slo = evaluate_slo(sched.sched_stats(), DEFRAG_SLO)
            stats = rb.stats() if rb is not None else {}
            sched.stop()
            if rb is not None:
                rb.release()
            return {"bound": bound, "wall_s": dt, "victims": victims,
                    "conservation": rep["counts"], "slo": slo,
                    "budget_ok": budget_ok, "frag_before": frag_before,
                    "frag_after": frag_after, "rebalance": stats}

        # warm-up: both legs compile their kernels at the run's shapes (the
        # defrag scan's pow2 buckets AND the victim-cover shapes)
        run_leg(True)
        run_leg(False)
        compiles0 = _solver_jit_cache()
        on = run_leg(True)
        off = run_leg(False)
        compiles = sum(v - compiles0.get(k, 0)
                       for k, v in _solver_jit_cache().items() if v >= 0)
        conserved = all(
            leg["conservation"]["lost"] == 0
            and leg["conservation"]["double_bound"] == 0
            for leg in (on, off))
        latency_improved = on["wall_s"] < off["wall_s"]
        preempt_improved = (on["victims"] == 0 and off["victims"] > 0)
        ok = (on["bound"] == gang_n and off["bound"] == gang_n
              and preempt_improved and latency_improved
              and on["budget_ok"] and conserved
              and on["rebalance"].get("migrations", 0) > 0
              and on["frag_after"] < 0.25 <= on["frag_before"]
              and on["slo"]["pass"] and off["slo"]["pass"]
              and compiles == 0)
        results["Defrag"] = {
            "admission_s_on": round(on["wall_s"], 3),
            "admission_s_off": round(off["wall_s"], 3),
            "preemptions_on": on["victims"],
            "preemptions_off": off["victims"],
            "migrations": on["rebalance"].get("migrations", 0),
            "waves": on["rebalance"].get("waves", 0),
            "frag_before": round(on["frag_before"], 3),
            "frag_after": round(on["frag_after"], 3),
            "budget_per_cycle": budget_cycle,
            "budget_ok": on["budget_ok"],
            "latency_improved": latency_improved,
            "preempt_improved": preempt_improved,
            "conservation_on": on["conservation"],
            "conservation_off": off["conservation"],
            "conservation_ok": conserved,
            "slo_pass_on": on["slo"]["pass"],
            "slo_pass_off": off["slo"]["pass"],
            "solver_compiles_during_run": compiles,
            "ab_comparable": True,  # same box, same process, interleaved
            "defrag_ok": ok,
            "solver": "fast+rebalance+defrag-scan"}
        print(f"{'Defrag':>28}: gang admitted in {on['wall_s']:.3f}s/"
              f"{on['victims']} evictions (rebalancer ON, "
              f"{on['rebalance'].get('migrations', 0)} migrations, frag "
              f"{on['frag_before']:.2f}->{on['frag_after']:.2f}) vs "
              f"{off['wall_s']:.3f}s/{off['victims']} evictions OFF "
              f"(compiles={compiles}, ok={ok})", file=sys.stderr)
    except Exception as e:
        results["Defrag"] = {"error": str(e)[:200]}
        print(f"Defrag: ERROR {e}", file=sys.stderr)


def rung_chaos_churn(results):
    """ChaosChurn_20k: the failure-domain rung (ISSUE 6) — bind 20k pods
    end-to-end WHILE the fault injector fails the first solves (tripping the
    solver circuit breaker to the exact scan oracle), fails store.bind_many
    transiently at a seeded rate (exercising the bind retry/backoff), and
    hard-kills the bind worker once mid-run (exercising the dead-worker
    liveness recovery); a crash resync_from_store runs at the halfway mark.
    Asserts the pod-conservation invariant — every submitted pod bound, 0
    lost, 0 double-bound — and that the breaker tripped AND recovered to the
    fast solver within the run. Also publishes the measured cost of the
    DISABLED injector guard so tests can bound its NorthStar overhead <1%
    from a measurement instead of differencing two noisy runs."""
    from kubernetes_tpu.chaos import faultinject as fi
    from kubernetes_tpu.scheduler import Framework
    from kubernetes_tpu.scheduler.batch import BatchScheduler
    from kubernetes_tpu.scheduler.plugins import default_plugins
    from kubernetes_tpu.store import APIStore
    from kubernetes_tpu.testing import MakePod, pod_conservation_report

    try:
        n_pods = sz(20_000, floor=2000)
        n_nodes = sz(1000, floor=128)
        batch = 2048
        waves = 4

        def build():
            store = APIStore()
            for n in _nodes(n_nodes, cpu="16", mem="64Gi"):
                store.create("nodes", n)
            sched = BatchScheduler(
                store, Framework(default_plugins()), batch_size=batch,
                solver="fast", breaker_threshold=3, breaker_cooldown_s=0.5,
                bind_retry_base_s=0.01,
                pod_initial_backoff=0.05, pod_max_backoff=0.2)
            # small commit chunks: the chaos plans need MANY bind_many calls
            # and worker cycles to bite (one merged 20k-pod cycle would see
            # the rate fault twice)
            sched.bind_chunk = 256
            sched.sync()
            return store, sched

        def mk(prefix, n):
            return [MakePod(f"{prefix}-{i}").req(
                {"cpu": "500m", "memory": "1Gi"}).obj() for i in range(n)]

        # warm-up: compile BOTH solvers at the run's shapes — the breaker
        # drives the scan oracle mid-run, and a cold compile inside the
        # chaos window would be measured as recovery latency
        wstore, wsched = build()
        wstore.create_many("pods", mk("w", min(n_pods, 2 * batch)),
                           consume=True)
        wsched.run_until_idle()
        wsched.solver = "exact"
        wstore.create_many("pods", mk("wx", batch), consume=True)
        wsched.run_until_idle()
        wsched.flush_binds()
        wsched.stop()
        del wstore, wsched

        store, sched = build()
        keys = [f"default/cc-{i}" for i in range(n_pods)]
        pending = mk("cc", n_pods)
        from kubernetes_tpu.native import hostcommit

        plans = [
            fi.FaultPlan("solver.solve", "fail", count=3),
            fi.FaultPlan("store.bind_many", "rate", rate=0.3, seed=1234),
            fi.FaultPlan("bind.worker", "kill", after=1),
        ]
        native_leg = hostcommit.available()
        if native_leg:
            # ISSUE 11 satellite: mid-chunk NATIVE commit failure (fires in
            # bind_many's phase gap — clones made, nothing committed) must
            # ride the same supervised-worker requeue and conserve every pod
            plans.append(fi.FaultPlan("native.commit", "fail", count=3,
                                      after=2))
        fi.arm(plans)
        t0 = time.perf_counter()
        deadline = t0 + (40.0 if SMOKE else 240.0)
        resynced = False
        bound = 0
        per_wave = (n_pods + waves - 1) // waves
        next_wave = 0
        injected = {}
        try:
            while time.perf_counter() < deadline:
                if next_wave < n_pods:
                    store.create_many(
                        "pods", pending[next_wave:next_wave + per_wave],
                        consume=True)
                    next_wave += per_wave
                sched.run_until_idle()
                sched.queue.flush_backoff_completed()
                sched.queue.move_all_to_active_or_backoff()
                bound = sum(1 for p in store.list("pods")[0]
                            if p.metadata.name.startswith("cc-")
                            and p.spec.node_name)
                if not resynced and bound >= n_pods // 2:
                    # settle the pre-crash subscriber's propagation ops into
                    # the store histograms BEFORE the resync discards the
                    # subscription (a mid-run /metrics scrape would do the
                    # same): the post-crash watch starts a fresh baseline,
                    # and with the native commit path the whole backlog can
                    # bind pre-resync — without this read the rung's
                    # propagation column could legitimately read 0
                    store.watch_telemetry()
                    sched.resync_from_store()  # simulated crash restart
                    resynced = True
                if bound >= n_pods and next_wave >= n_pods:
                    if sched.breaker.state == "closed":
                        break
                    # all work drained while the breaker was still open: the
                    # half-open probe needs a REAL batch — submit a few
                    # probe pods (tracked by the conservation check too)
                    extra = mk(f"probe{len(keys)}", 8)
                    keys.extend(p.key for p in extra)
                    store.create_many("pods", extra, consume=True)
                    time.sleep(sched.breaker.cooldown_s / 2)
                time.sleep(0.02)
            injected = (fi.ACTIVE.stats() if fi.ACTIVE is not None else {})
        finally:
            fi.disarm()
        # settle: with the injector gone, drain every tier to quiescence so
        # the conservation check reads a stable partition
        for _ in range(40):
            sched.flush_binds()
            sched.queue.flush_backoff_completed()
            sched.queue.move_all_to_active_or_backoff()
            sched.run_until_idle()
            if all(p.spec.node_name for p in store.list("pods")[0]
                   if not p.metadata.name.startswith(("w-", "wx-"))):
                break
            time.sleep(0.05)
        dt = time.perf_counter() - t0
        rep = pod_conservation_report(store, sched, keys)
        c = rep["counts"]
        brk = sched.breaker
        ok = (c["lost"] == 0 and c["double_bound"] == 0
              and c["bound"] == len(keys) and brk.trips >= 1
              and brk.recoveries >= 1 and brk.state == "closed"
              and injected.get("bind.worker", {}).get("injected", 0) >= 1
              and sched.bind_worker_restarts >= 1)
        # ISSUE 7: the breaker trip must SHOW UP as a latency excursion in
        # the trace without breaking the tracer — at quiescence every pod is
        # bound, so every surviving sampled span must be complete, the
        # submit->bound p99 must sit above the median (the faulted/backoff
        # pods ARE the tail) yet inside the chaos SLO ceiling
        from kubernetes_tpu.scheduler.slo import CHAOS_SLO, evaluate_slo

        latency = sched.podtrace.latency_stats()
        tsnap = sched.podtrace.snapshot()
        n_spans = len(tsnap["spans"])
        n_complete = sum(1 for s in tsnap["spans"] if s["complete"])
        # watch-propagation column (ISSUE 9): chaos drops and the breaker
        # excursion show up as commit->dequeue tail + counted drops
        wtel = store.watch_telemetry()
        prop = wtel["propagation"]
        watch_col = {
            "propagation_count": prop["count"],
            "propagation_p50_s": prop["p50_s"],
            "propagation_p99_s": prop["p99_s"],
            "subscribers": len(wtel["subscribers"]),
            "dropped": wtel["dropped"],
        }
        slo = evaluate_slo({"latency": latency}, CHAOS_SLO)
        trace_ok = (n_spans > 0 and n_complete == n_spans
                    and latency["count"] > 0
                    and latency["p99_s"] >= latency["p50_s"]
                    and slo["pass"])
        # --- partition hard-kill leg (ISSUE 12 satellite): the same churn
        # through a 2-partition scheduler, with partition 1 HARD-KILLED
        # mid-run by the partition.dispatch chaos site. The survivor must
        # absorb the dead shard — router remap + resync_from_store — and
        # every pod must still be conserved (the dead pipeline's in-flight
        # binds reconcile through the conflict machinery).
        from kubernetes_tpu.scheduler.partition import PartitionedScheduler
        pk = {}
        try:
            pstore = APIStore()
            for n in _nodes(n_nodes, cpu="16", mem="64Gi"):
                pstore.create("nodes", n)
            coord = PartitionedScheduler(
                pstore, lambda: Framework(default_plugins()), partitions=2,
                batch_size=batch, solver="fast",
                pod_initial_backoff=0.05, pod_max_backoff=0.2)
            coord.sync()
            pkeys = [f"default/pk-{i}" for i in range(n_pods)]
            ppods = mk("pk", n_pods)
            fi.arm([fi.FaultPlan("partition.dispatch", "kill",
                                 match="partition-1", after=1)])
            t0p = time.perf_counter()
            deadline_p = t0p + (30.0 if SMOKE else 120.0)
            try:
                sent = 0
                pbound = 0
                while time.perf_counter() < deadline_p:
                    if sent < n_pods:
                        pstore.create_many(
                            "pods", ppods[sent:sent + per_wave],
                            consume=True)
                        sent += per_wave
                    coord.run_until_idle()
                    coord.flush_queues()
                    pbound = sum(1 for p in pstore.list("pods")[0]
                                 if p.metadata.name.startswith("pk-")
                                 and p.spec.node_name)
                    if pbound >= n_pods and sent >= n_pods:
                        break
                    time.sleep(0.02)
            finally:
                fi.disarm()
            coord.run_until_idle()
            coord.flush_binds()
            prep = pod_conservation_report(pstore, coord, pkeys)
            pc = prep["counts"]
            pk = {"pods": n_pods, "bound": pc["bound"], "lost": pc["lost"],
                  "double_bound": pc["double_bound"],
                  "partitions_absorbed": coord.partitions_absorbed,
                  "conflicts": coord.conflicts_total,
                  "reroutes": coord.reroutes_total,
                  "wall_s": round(time.perf_counter() - t0p, 3),
                  "ok": (pc["bound"] == len(pkeys) and pc["lost"] == 0
                         and pc["double_bound"] == 0
                         and coord.partitions_absorbed == 1)}
            coord.stop()
        except Exception as e:  # the leg must not void the main chaos run
            fi.disarm()
            pk = {"error": str(e)[:200]}
        # --- gang-preemption leg (ISSUE 14 satellite): a victim cover under
        # injected bind + native-commit faults AND a mid-run bind-worker
        # kill. The invariants: pod conservation clean over gang AND
        # surviving fillers, the gang is never half-bound (0 or all), and a
        # cover never half-fires without the gang eventually landing whole.
        gp = {}
        try:
            from kubernetes_tpu.testing import MakeNode, make_pod_group

            gstore = APIStore()
            for s in range(2):
                for i in range(8):
                    gstore.create("nodes", MakeNode(f"node-{s}-{i}")
                                  .tpu_slice(s, index=i)
                                  .capacity({"cpu": "8", "memory": "32Gi",
                                             "pods": "110"}).obj())
            filler_keys = []
            for s in range(2):
                for i in range(8):
                    low = MakePod(f"low-{s}-{i}").priority(1).req(
                        {"cpu": "6"}).obj()
                    low.spec.node_name = f"node-{s}-{i}"
                    gstore.create("pods", low)
                    filler_keys.append(low.key)
            gsched = BatchScheduler(
                gstore, Framework(default_plugins()), batch_size=1024,
                solver="fast", breaker_threshold=3, breaker_cooldown_s=0.5,
                bind_retry_base_s=0.01,
                pod_initial_backoff=0.05, pod_max_backoff=0.2)
            gsched.bind_chunk = 4
            gsched.sync()
            gstore.create("podgroups", make_pod_group("cg", 12))
            gpods = [MakePod(f"cg-{i}").gang("cg", rank=i).priority(100)
                     .req({"cpu": "3"}).obj() for i in range(12)]
            gplans = [fi.FaultPlan("store.bind_many", "rate", rate=0.25,
                                   seed=77),
                      fi.FaultPlan("bind.worker", "kill", after=1)]
            if native_leg:
                gplans.append(fi.FaultPlan("native.commit", "fail", count=2))
            fi.arm(gplans)
            t0g = time.perf_counter()
            deadline_g = t0g + (25.0 if SMOKE else 90.0)
            gbound = 0
            try:
                gstore.create_many("pods", gpods, consume=True)
                while time.perf_counter() < deadline_g:
                    gsched.run_until_idle()
                    gsched.queue.flush_backoff_completed()
                    gsched.pump_events()
                    gbound = sum(1 for p in gstore.list("pods")[0]
                                 if p.metadata.name.startswith("cg-")
                                 and p.spec.node_name)
                    if gbound >= 12:
                        break
                    time.sleep(0.02)
            finally:
                fi.disarm()
            # settle to quiescence with the injector gone
            for _ in range(40):
                gsched.run_until_idle()
                gsched.queue.flush_backoff_completed()
                gsched.pump_events()
                gbound = sum(1 for p in gstore.list("pods")[0]
                             if p.metadata.name.startswith("cg-")
                             and p.spec.node_name)
                if gbound >= 12:
                    break
                time.sleep(0.05)
            gstats = gsched.gangpreempt.stats()
            # conservation over the gang + every filler the cover did NOT
            # delete (a deleted victim is the cover's documented outcome)
            live_fillers = [k for k in filler_keys
                            if any(p.key == k
                                   for p in gstore.list("pods")[0])]
            grep_ = pod_conservation_report(
                gstore, gsched, [p.key for p in gpods] + live_fillers)
            gc_ = grep_["counts"]
            gsched.stop()
            gp = {"pods": 12, "bound": gbound,
                  "lost": gc_["lost"], "double_bound": gc_["double_bound"],
                  "preempted": gstats["preempted"],
                  "victims": gstats["victims"],
                  "expired_covers": gstats["expired"],
                  "wall_s": round(time.perf_counter() - t0g, 3),
                  "ok": (gbound == 12 and gc_["lost"] == 0
                         and gc_["double_bound"] == 0
                         and gstats["preempted"] >= 1)}
        except Exception as e:  # the leg must not void the main chaos run
            fi.disarm()
            gp = {"error": str(e)[:200]}
        # --- mp worker-kill leg (ISSUE 19 satellite): the same churn
        # through a 2-process MPScheduler with worker 1 HARD-KILLED
        # (SIGKILL — a process failure domain, not an exception) mid-run by
        # the process.worker chaos site. The supervisor must detect the
        # death, respawn the slot, resync the estate, and conserve every
        # pod; the dead worker's in-flight intents die with its queue and
        # the rv re-validation absorbs anything already submitted.
        mpk = {}
        try:
            from kubernetes_tpu.scheduler.mpsched import MPScheduler
            from kubernetes_tpu.store import shm as _shm_mod

            if not _shm_mod.available():
                mpk = {"skipped": "shared memory unavailable"}
            else:
                mstore = APIStore()
                for n in _nodes(n_nodes, cpu="16", mem="64Gi"):
                    mstore.create("nodes", n)
                msched = MPScheduler(mstore, processes=2)
                mkeys = [f"default/mpk-{i}" for i in range(n_pods)]
                mpods = mk("mpk", n_pods)
                fi.arm([fi.FaultPlan("process.worker", "kill",
                                     match="worker-1", after=1)])
                t0m = time.perf_counter()
                deadline_m = t0m + (30.0 if SMOKE else 120.0)
                try:
                    sent = 0
                    mbound = 0
                    while time.perf_counter() < deadline_m:
                        if sent < n_pods:
                            mstore.create_many(
                                "pods", mpods[sent:sent + per_wave],
                                consume=True)
                            sent += per_wave
                        msched.run_until_idle()
                        mbound = sum(1 for pd in mstore.list("pods")[0]
                                     if pd.metadata.name.startswith("mpk-")
                                     and pd.spec.node_name)
                        if mbound >= n_pods and sent >= n_pods:
                            break
                        time.sleep(0.02)
                finally:
                    fi.disarm()
                msched.run_until_idle()
                msched.flush_binds()
                mrep = pod_conservation_report(mstore, msched, mkeys)
                mc = mrep["counts"]
                mst = msched.sched_stats()["processes"]
                mpk = {"pods": n_pods, "bound": mc["bound"],
                       "lost": mc["lost"],
                       "double_bound": mc["double_bound"],
                       "worker_restarts": mst["worker_restarts"],
                       "stale_intents": mst["stale_intents"],
                       "bind_conflicts": mst["bind_conflicts"],
                       "rounds": mst["rounds"],
                       "wall_s": round(time.perf_counter() - t0m, 3),
                       "ok": (mc["bound"] == len(mkeys) and mc["lost"] == 0
                              and mc["double_bound"] == 0
                              and mst["worker_restarts"] >= 1)}
                msched.stop()
        except Exception as e:  # the leg must not void the main chaos run
            fi.disarm()
            mpk = {"error": str(e)[:200]}
        results["ChaosChurn_20k"] = {
            "pods_per_sec": round(n_pods / dt, 1), "wall_s": round(dt, 3),
            "placed": c["bound"], "pods": len(keys),
            "conservation": c, "conservation_ok": ok,
            "breaker_trips": brk.trips, "breaker_recoveries": brk.recoveries,
            "breaker_state": brk.state,
            "bind_worker_restarts": sched.bind_worker_restarts,
            "resynced": resynced, "injected": injected,
            "latency": latency,
            "trace": {"spans": n_spans, "complete": n_complete,
                      "evicted_incomplete": tsnap["evicted_incomplete"]},
            "watch": watch_col,
            "trace_ok": trace_ok, "slo": slo,
            "disabled_check_ns": round(fi.disabled_check_cost_ns(), 2),
            "native_commit_faults": injected.get("native.commit",
                                                 {}).get("injected", 0),
            "native_commit": native_leg,
            "partition_kill": pk,
            "mp_worker_kill": mpk,
            "gang_preemption": gp,
            "solver": "fast+breaker+chaos"}
        print(f"{'ChaosChurn_20k':>28}: {n_pods / dt:>9.0f} pods/s  "
              f"({c['bound']}/{n_pods} bound under chaos, "
              f"{c['lost']} lost, {c['double_bound']} double-bound, "
              f"breaker trips={brk.trips} recoveries={brk.recoveries}, "
              f"worker restarts={sched.bind_worker_restarts}, {dt:.1f}s; "
              f"p50={latency['p50_s']}s p99={latency['p99_s']}s, "
              f"{n_complete}/{n_spans} spans complete)",
              file=sys.stderr)
        if "error" in pk:
            print(f"    partition-kill leg: ERROR {pk['error']}",
                  file=sys.stderr)
        else:
            print(f"    partition-kill leg: {pk['bound']}/{pk['pods']} "
                  f"conserved after absorbing partition 1 "
                  f"(absorbed={pk['partitions_absorbed']}, "
                  f"conflicts={pk['conflicts']}, "
                  f"reroutes={pk['reroutes']}, {pk['wall_s']}s)",
                  file=sys.stderr)
        if "error" in mpk:
            print(f"    mp worker-kill leg: ERROR {mpk['error']}",
                  file=sys.stderr)
        elif "skipped" in mpk:
            print(f"    mp worker-kill leg: SKIPPED {mpk['skipped']}",
                  file=sys.stderr)
        else:
            print(f"    mp worker-kill leg: {mpk['bound']}/{mpk['pods']} "
                  f"conserved after SIGKILLing worker 1 "
                  f"(restarts={mpk['worker_restarts']}, "
                  f"stale_intents={mpk['stale_intents']}, "
                  f"rounds={mpk['rounds']}, {mpk['wall_s']}s)",
                  file=sys.stderr)
        if "error" in gp:
            print(f"    gang-preemption leg: ERROR {gp['error']}",
                  file=sys.stderr)
        else:
            print(f"    gang-preemption leg: {gp['bound']}/{gp['pods']} "
                  f"placed whole under faults "
                  f"(covers={gp['preempted']}, victims={gp['victims']}, "
                  f"expired={gp['expired_covers']}, {gp['lost']} lost, "
                  f"{gp['wall_s']}s)", file=sys.stderr)
    except Exception as e:
        from kubernetes_tpu.chaos import faultinject as fi

        fi.disarm()  # never leak an armed injector into later rungs
        results["ChaosChurn_20k"] = {"error": str(e)[:200]}
        print(f"ChaosChurn_20k: ERROR {e}", file=sys.stderr)


def rung_control_plane(results):
    """ControlPlane_churn (ISSUE 9): the WHOLE "watch, reconcile, write
    status" loop — deployment rollout + node drain + eviction/replace driven
    through the controllers, hollow kubelets, and the batch scheduler, with
    the control-plane flight recorder measuring it all: per-controller
    reconcile-loop p99s (obs/reconcile.py), store watch-propagation
    commit->dequeue latency + delivered-RV lag, and submit->running spans
    with evict->replace causal links. Gated by CONTROL_PLANE_SLO
    (watch_propagation_p99_s / reconcile_p99_ms), asserted PASS by
    tests/test_bench_quick.py. Fixed-size like the gang rung: the rung IS
    the quick-tier control-plane smoke and runs in seconds."""
    from kubernetes_tpu.agent import HollowKubelet
    from kubernetes_tpu.api.types import Taint, TAINT_NO_EXECUTE
    from kubernetes_tpu.api.workloads import Deployment
    from kubernetes_tpu.controllers import (DeploymentController,
                                            ReplicaSetController,
                                            TaintEvictionController)
    from kubernetes_tpu.obs.reconcile import (controlstats_snapshot,
                                              reconcile_rollup)
    from kubernetes_tpu.scheduler import Framework
    from kubernetes_tpu.scheduler.batch import BatchScheduler
    from kubernetes_tpu.scheduler.plugins import default_plugins
    from kubernetes_tpu.scheduler.slo import CONTROL_PLANE_SLO, evaluate_slo
    from kubernetes_tpu.store import APIStore

    try:
        n_nodes, replicas = 24, 192
        store = APIStore()
        kubelets = [HollowKubelet(store, f"hollow-{i}",
                                  capacity={"cpu": "16", "memory": "64Gi",
                                            "pods": "110"})
                    for i in range(n_nodes)]
        for k in kubelets:
            k.register()
        sched = BatchScheduler(store, Framework(default_plugins()),
                               batch_size=1024, solver="exact",
                               trace_sample_k=256)  # sample every pod: the
        # evict->replace chain assertions need both ends of every link
        sched.sync()
        dc = DeploymentController(store)
        rsc = ReplicaSetController(store)
        te = TaintEvictionController(store)
        for c in (dc, rsc, te):
            c.sync_all()
        controllers = (dc, rsc, te)

        def drive(rounds, done):
            for _ in range(rounds):
                for c in controllers:
                    c.reconcile_once()
                te.tick()  # fire due timed evictions
                sched.run_until_idle()
                for k in kubelets:
                    k.pump()
                if done():
                    return True
            return done()

        def pods_running():
            pods, _ = store.list("pods")
            return bool(pods) and all(
                p.spec.node_name and p.status.phase == "Running"
                for p in pods)

        store.create("deployments", Deployment.from_dict({
            "metadata": {"name": "cp-web"},
            "spec": {
                "replicas": replicas,
                # wide surge budget: the rung measures the control plane
                # under bulk churn, not the default one-pod-per-round crawl
                "strategy": {"type": "RollingUpdate",
                             "rollingUpdate": {"maxSurge": 64,
                                               "maxUnavailable": 64}},
                "selector": {"matchLabels": {"app": "cp-web"}},
                "template": {
                    "metadata": {"labels": {"app": "cp-web"}},
                    "spec": {"containers": [{"name": "c", "image": "v1",
                                             "resources": {"requests": {
                                                 "cpu": "100m"}}}]}},
            },
        }))
        # warm phase: initial rollout to Running (includes the solver's one
        # jit compile) — NOT measured; the churn window below is
        assert drive(30, pods_running), "initial rollout"
        # measured window starts here (the flightrec.clear() idiom)
        store.clear_watch_propagation()
        for c in controllers:
            c.recorder.clear()
        t0 = time.perf_counter()

        # (1) rolling update: new template -> new RS -> replace all pods
        def set_image(d):
            d.spec.template.spec.containers[0].image = "v2"
            return d

        store.guaranteed_update("deployments", "default/cp-web", set_image)

        def rolled():
            pods, _ = store.list("pods")
            new = [p for p in pods if any(
                c.image == "v2" for c in p.spec.containers)]
            return (len(new) >= replicas and all(
                p.spec.node_name and p.status.phase == "Running"
                for p in new))

        assert drive(60, rolled), "rolling update did not converge"

        # (2) node drain: NoExecute taint -> tainteviction evicts ->
        # ReplicaSet replaces -> scheduler re-places off the drained node
        drained = "hollow-0"
        node = store.get("nodes", drained)
        victims = sum(1 for p in store.list("pods")[0]
                      if p.spec.node_name == drained)
        node.spec.taints = list(node.spec.taints) + [
            Taint(key="bench/drain", effect=TAINT_NO_EXECUTE)]
        store.update("nodes", node, check_rv=False)

        def drained_done():
            pods, _ = store.list("pods")
            on_node = [p for p in pods if p.spec.node_name == drained]
            return (not on_node and len(pods) >= replicas
                    and pods_running())

        assert drive(60, drained_done), "drain/replace did not converge"
        dt = time.perf_counter() - t0

        # collect: controller reconcile rollup + watch propagation + spans
        snap = controlstats_snapshot()
        snap = {k: v for k, v in snap.items()
                if k in ("DeploymentController", "ReplicaSetController",
                         "TaintEvictionController")}
        roll = reconcile_rollup(snap)
        tel = store.watch_telemetry()
        prop = tel["propagation"]
        max_lag = max((s["rv_lag"] for s in tel["subscribers"]), default=0)
        tsnap = sched.podtrace.snapshot()
        chains = sum(1 for s in tsnap["spans"] if s.get("replaces"))
        chain_complete = sum(1 for s in tsnap["spans"]
                             if s.get("replaces") and s["complete"])
        running_spans = sum(1 for s in tsnap["spans"]
                            if s.get("submit_to_running_ms") is not None)
        slo = evaluate_slo({"watch": {"propagation": prop},
                            "reconcile": roll}, CONTROL_PLANE_SLO)
        ok = (slo["pass"] and not slo["skipped"] and victims > 0
              and chains >= 1 and chain_complete == chains
              and running_spans > 0)
        results["ControlPlane_churn"] = {
            "pods_per_sec": round(replicas / dt, 1), "wall_s": round(dt, 3),
            "pods": replicas, "nodes": n_nodes, "evicted_from_drain": victims,
            "watch": {"propagation_count": prop["count"],
                      "propagation_p50_s": prop["p50_s"],
                      "propagation_p99_s": prop["p99_s"],
                      "subscribers": len(tel["subscribers"]),
                      "max_rv_lag": max_lag,
                      "settle_s": prop["settle_seconds"]},
            "reconcile": roll,
            "controllers": {name: {"loops": st.get("loops"),
                                   "keys": st.get("keys"),
                                   "errors": st.get("errors"),
                                   "p99_ms": st.get("reconcile_p99_ms")}
                            for name, st in snap.items()},
            "trace": {"spans": len(tsnap["spans"]),
                      "evict_replace_chains": chains,
                      "chains_complete": chain_complete,
                      "running_spans": running_spans},
            "slo": slo, "controlplane_ok": ok,
            "solver": "exact+controllers+kubelets"}
        print(f"{'ControlPlane_churn':>28}: rollout+drain of {replicas} pods "
              f"in {dt:.2f}s  (propagation p99={prop['p99_s']}s over "
              f"{prop['count']} deliveries, worst reconcile p99="
              f"{roll['p99_ms']}ms [{roll['worst_controller']}], "
              f"{chains} evict->replace chains, SLO "
              f"{'PASS' if slo['pass'] else 'FAIL ' + str(slo['failed'])})",
              file=sys.stderr)
    except Exception as e:
        results["ControlPlane_churn"] = {"error": str(e)[:200]}
        print(f"ControlPlane_churn: ERROR {e}", file=sys.stderr)


def rung_transport(results):
    """Auction + Sinkhorn global solvers at 50k pods / 5k nodes (BASELINE.json
    ladder steps 3-4): throughput, placements, and mean assignment score vs
    the waterfill fast path on the identical problem."""
    import numpy as np

    from kubernetes_tpu.models.transport import transport_solve
    from kubernetes_tpu.models.waterfill import make_groups, waterfill_solve
    from kubernetes_tpu.ops.solver import make_inputs
    from kubernetes_tpu.snapshot.tensorizer import build_cluster_tensors, build_pod_batch
    from kubernetes_tpu.testing import MakePod

    try:
        snap = make_snapshot(_nodes(sz(5000), cpu="16", mem="64Gi"))
        pods = [MakePod(f"tr-{i}").req({"cpu": "500m", "memory": "1Gi"}).obj()
                for i in range(sz(50_000))]
        cluster = build_cluster_tensors(snap)
        batch = build_pod_batch(pods, snap, cluster)
        inputs, _ = make_inputs(cluster, batch)
        groups = make_groups(batch)

        def timed(fn):
            fn()  # warm-up/compile
            t0 = time.perf_counter()
            out = fn()
            return out, time.perf_counter() - t0

        base, dt_wf = timed(lambda: np.asarray(waterfill_solve(inputs, groups)))

        # BASELINE ladder #4: node-sharded Sinkhorn at 100k pods / 10k nodes
        # through the mesh path (all available devices; on the 1-chip bench
        # rig the sharding machinery still runs with a 1-wide mesh)
        try:
            from kubernetes_tpu.parallel.sharded import make_mesh

            big_snap = make_snapshot(_nodes(sz(10_000), cpu="16", mem="64Gi"))
            big_pods = [MakePod(f"ts-{i}").req(
                {"cpu": "500m" if i % 2 else "250m",
                 "memory": "1Gi"}).obj() for i in range(sz(100_000))]
            big_cluster = build_cluster_tensors(big_snap)
            big_batch = build_pod_batch(big_pods, big_snap, big_cluster)
            big_inputs, _ = make_inputs(big_cluster, big_batch)
            big_groups = make_groups(big_batch)
            mesh = make_mesh()
            wf_big, dt_wf_big = timed(
                lambda: np.asarray(waterfill_solve(big_inputs, big_groups)))
            solved, dt = timed(lambda: transport_solve(
                big_inputs, big_groups, method="sinkhorn",
                node_names=big_cluster.node_names, mesh=mesh))
            a = np.asarray(solved[0])
            placed = int((a >= 0).sum())
            pps = len(big_pods) / dt
            wf_pps = len(big_pods) / dt_wf_big
            results["Transport_sinkhorn_sharded_100k"] = {
                "pods_per_sec": round(pps, 1), "wall_s": round(dt, 3),
                "placed": placed, "pods": len(big_pods),
                "waterfill_placed": int((wf_big >= 0).sum()),
                "mesh_devices": int(np.prod(list(mesh.shape.values()))),
                "waterfill_pods_per_sec": round(wf_pps, 1),
                "vs_waterfill": round(pps / wf_pps, 2)}
            print(f"{'Transport_sinkhorn_sharded_100k':>28}: {pps:>9.0f} "
                  f"pods/s  ({placed}/{len(big_pods)} placed; "
                  f"{pps / wf_pps:.2f}x waterfill)", file=sys.stderr)
        except Exception as e:
            results["Transport_sinkhorn_sharded_100k"] = {"error": str(e)[:200]}
            print(f"Transport_sinkhorn_sharded_100k: ERROR {e}",
                  file=sys.stderr)

        for method in ("auction", "sinkhorn"):
            try:
                solved, dt = timed(lambda m=method: transport_solve(
                    inputs, groups, method=m, node_names=cluster.node_names))
                if solved is None:
                    results[f"Transport_{method}_50k"] = {"error": "solver declined problem"}
                    continue
                a = np.asarray(solved[0])
                placed = int((a >= 0).sum())
                pps = len(pods) / dt
                results[f"Transport_{method}_50k"] = {
                    "pods_per_sec": round(pps, 1), "wall_s": round(dt, 3),
                    "placed": placed, "pods": len(pods),
                    "waterfill_pods_per_sec": round(len(pods) / dt_wf, 1),
                    "waterfill_placed": int((base >= 0).sum())}
                print(f"{'Transport_' + method + '_50k':>28}: {pps:>9.0f} pods/s  "
                      f"({placed}/{len(pods)} placed; waterfill "
                      f"{len(pods) / dt_wf:.0f} pods/s)", file=sys.stderr)
            except Exception as e:
                results[f"Transport_{method}_50k"] = {"error": str(e)[:200]}
                print(f"Transport_{method}_50k: ERROR {e}", file=sys.stderr)
    except Exception as e:
        results["Transport_50k"] = {"error": str(e)[:200]}
        print(f"Transport_50k: ERROR {e}", file=sys.stderr)


def rung_node_affinity(results):
    # NodeAffinity (affinity/performance-config.yaml:323 shape, baseline 220):
    # half the nodes carry the wanted label; every pod requires it
    from kubernetes_tpu.testing import MakePod

    nodes = _nodes(sz(5000))
    for i, n in enumerate(nodes):
        n.metadata.labels["disk"] = "ssd" if i % 2 == 0 else "hdd"
    snap = make_snapshot(nodes)
    pods = [MakePod(f"na-{i}").node_affinity_in("disk", ["ssd"])
            .req({"cpu": "200m", "memory": "256Mi"}).obj()
            for i in range(sz(10000))]
    run_rung("NodeAffinity", snap, pods, "scan", 220, results=results)


def rung_preferred_topology_spread(results):
    # PreferredTopologySpreading (misc/performance-config.yaml:249 shape,
    # baseline 125): ScheduleAnyway constraints score instead of filter
    from kubernetes_tpu.testing import MakePod

    snap = make_snapshot(_nodes(sz(5000), zones=10))
    pods = [MakePod(f"pts-{i}").labels({"app": "soft"})
            .req({"cpu": "200m", "memory": "256Mi"})
            .topology_spread(1, ZONE, "ScheduleAnyway", {"app": "soft"})
            .obj() for i in range(sz(5000))]
    run_rung("PreferredTopologySpreading", snap, pods, "repair", 125,
             results=results)


def rung_affinity_quality(results):
    """AffinityQuality (ISSUE 17 satellite, ROADMAP carryover): the soft-term
    placement-QUALITY yardstick, not a throughput rung. Pods carry preferred
    pod-affinity terms toward per-zone seeds with deliberate capacity
    pressure (each zone can host ~80% of the pods that prefer it), so the
    scorer decides how much preference-weight each solver path realizes.
    The same workload solves twice — the propose-and-repair fast path (the
    penalty fold) vs the exact scan oracle — and the rung publishes the
    achieved soft score of each plus their ratio: the parity claim the
    defrag kernel's placement-quality numbers lean on."""
    import numpy as np

    from kubernetes_tpu.testing import MakePod

    try:
        n_z, nodes_per_zone, pref_z, n_pods, weight = 10, 3, 7, 140, 10
        n_nodes = n_z * nodes_per_zone
        # node-i sits in zone-(i % n_z): zone capacity = 3 nodes x 8 cpu
        nodes = _nodes(n_nodes, zones=n_z)
        # one seed per PREFERRED zone on node-z (zone-z for z < pref_z)
        seeds = [MakePod(f"seed-{z}").labels({"svc": f"s{z}"})
                 .node(f"node-{z}").req({"cpu": "100m"}).obj()
                 for z in range(pref_z)]
        snap = make_snapshot(nodes, bound_pods=seeds)
        # 20 pods prefer each seeded zone at 1.5 cpu = 30 cpu wanted vs
        # ~23.9 free — only ~15 of 20 can land preferred, the rest spill to
        # the 3 seedless zones (global headroom: every pod still places).
        # The score separates a real soft-term fold from a scorer that
        # ignores the preference
        pods = [MakePod(f"aq-{i}").labels({"peer": "1"})
                .preferred_pod_affinity(weight, ZONE,
                                        {"svc": f"s{i % pref_z}"})
                .req({"cpu": "1500m"}).obj() for i in range(n_pods)]

        from kubernetes_tpu.snapshot.tensorizer import build_cluster_tensors

        node_zone = [int(n.split("-")[1]) % n_z
                     for n in build_cluster_tensors(snap).node_names]

        def soft_score(a):
            # realized preference-weight: pod i's term is satisfied iff its
            # node's zone holds seed s{i % pref_z} (zone i % pref_z)
            return sum(weight for i in range(len(pods))
                       if a[i] >= 0 and node_zone[int(a[i])] == i % pref_z)

        def solve(solver):
            device_solve(snap, pods, solver)  # warm-up: compile
            a, dt, _info = device_solve(snap, pods, solver)
            return np.asarray(a), dt

        a_rep, dt_rep = solve("repair")
        a_scan, dt_scan = solve("scan")
        s_rep, s_scan = soft_score(a_rep), soft_score(a_scan)
        placed_rep = int((a_rep >= 0).sum())
        placed_scan = int((a_scan >= 0).sum())
        max_score = n_pods * weight
        parity = (s_rep / s_scan) if s_scan else (1.0 if not s_rep else 0.0)
        # the repair fold is approximate BY DESIGN (soft scores steer, hard
        # masks decide — a 0..200 preference row vs a 0..800 packing score):
        # measured parity on this shape is ~0.82, and the floor catches a
        # fold regression (sign flip, dropped term), not design headroom
        ok = (placed_rep == placed_scan == n_pods
              and s_scan > 0 and parity >= 0.7)
        results["AffinityQuality"] = {
            "pods": n_pods, "placed_repair": placed_rep,
            "placed_scan": placed_scan,
            "soft_score_repair": s_rep, "soft_score_scan": s_scan,
            "soft_score_max": max_score,
            "soft_score_parity": round(parity, 3),
            "pods_per_sec_repair": round(n_pods / dt_rep, 1) if dt_rep else 0,
            "pods_per_sec_scan": round(n_pods / dt_scan, 1) if dt_scan else 0,
            "ab_comparable": True,  # same box, same process, interleaved
            "quality_ok": ok,
            "solver": "repair-vs-scan"}
        print(f"{'AffinityQuality':>28}: soft score {s_rep}/{max_score} "
              f"(repair) vs {s_scan}/{max_score} (scan oracle), parity "
              f"{parity:.3f}, ok={ok}", file=sys.stderr)
    except Exception as e:
        results["AffinityQuality"] = {"error": str(e)[:200]}
        print(f"AffinityQuality: ERROR {e}", file=sys.stderr)


def _preemption_run(results, name, baseline, async_preparation):
    """Shared preemption harness; async_preparation picks the reference's
    PreemptionBasic (serial victim prep, baseline 18) vs PreemptionAsync
    (prepareCandidateAsync, baseline 160) modes."""
    from kubernetes_tpu.scheduler import Framework
    from kubernetes_tpu.scheduler.batch import BatchScheduler
    from kubernetes_tpu.scheduler.plugins import default_plugins
    from kubernetes_tpu.scheduler.plugins.default_preemption import (
        DefaultPreemption,
    )
    from kubernetes_tpu.store import APIStore
    from kubernetes_tpu.testing import MakePod

    def make_framework():
        plugins = default_plugins()
        for i, p in enumerate(plugins):
            if isinstance(p, DefaultPreemption):
                plugins[i] = DefaultPreemption(
                    async_preparation=async_preparation)
        return Framework(plugins)

    try:
        n_nodes = sz(500, floor=16)
        store = APIStore()
        for n in _nodes(n_nodes, cpu="4"):
            store.create("nodes", n)
        for i in range(n_nodes):
            low = MakePod(f"low-{i}").priority(1).req({"cpu": "3"}).obj()
            low.spec.node_name = f"node-{i}"
            store.create("pods", low)
        warm_store = APIStore()
        for n in _nodes(n_nodes, cpu="4"):
            warm_store.create("nodes", n)
        warm = BatchScheduler(warm_store, make_framework(), solver="auto")
        warm.sync()
        for i in range(n_nodes):
            warm_store.create("pods", MakePod(f"w-{i}").priority(100).req(
                {"cpu": "2"}).obj())
        warm.run_until_idle()

        sched = BatchScheduler(store, make_framework(), solver="auto")
        sched.sync()
        sched.run_until_idle()
        for i in range(n_nodes):
            store.create("pods", MakePod(f"high-{i}").priority(100).req(
                {"cpu": "2"}).obj())
        t0 = time.perf_counter()
        deadline = t0 + 120
        bound = 0
        while time.perf_counter() < deadline:
            sched.run_until_idle()
            bound = sum(1 for p in store.list("pods")[0]
                        if p.metadata.name.startswith("high") and p.spec.node_name)
            if bound >= n_nodes:
                break
            sched.queue.flush_backoff_completed()
            sched.queue.flush_unschedulable_left_over()
            time.sleep(0.05)
        dt = time.perf_counter() - t0
        pps = bound / dt
        results[name] = {
            "pods_per_sec": round(pps, 1),
            "vs_baseline": round(pps / baseline, 2),
            "placed": bound, "pods": n_nodes,
            "solver": ("async" if async_preparation else "serial")
            + "-preempt+batch"}
        print(f"{name:>28}: {pps:>9.0f} pods/s  "
              f"({bound}/{n_nodes} preempted+bound, "
              f"{pps / baseline:.1f}x baseline {baseline})", file=sys.stderr)
    except Exception as e:
        results[name] = {"error": str(e)[:200]}
        print(f"{name}: ERROR {e}", file=sys.stderr)


def rung_preemption_async(results):
    _preemption_run(results, "PreemptionAsync", 160, async_preparation=True)


def rung_watch_fanout(results):
    """Apiserver watch fan-out at kubemark scale: 5k streaming watchers
    through the select-based mux, measuring deliveries/s (VERDICT r4 #8;
    reference: cacher fan-out, storage/cacher/cacher.go:261)."""
    from kubernetes_tpu.perf.watch_scale import run as watch_run

    try:
        out = watch_run(n_watchers=sz(5000, floor=64),
                        n_events=sz(100, floor=8))
        results["ApiserverWatchFanout_5k"] = out
        if "error" in out:
            print(f"ApiserverWatchFanout_5k: ERROR {out['error']}",
                  file=sys.stderr)
        else:
            print(f"{'ApiserverWatchFanout_5k':>28}: "
                  f"{out['deliveries_per_s']:>9.0f} deliveries/s  "
                  f"({out['streams_established']} streams, "
                  f"{out['deliveries']} delivered in {out['fanout_s']}s)",
                  file=sys.stderr)
    except Exception as e:
        results["ApiserverWatchFanout_5k"] = {"error": str(e)[:200]}
        print(f"ApiserverWatchFanout_5k: ERROR {e}", file=sys.stderr)


def rung_trace_timeline(results):
    """TraceTimeline (ISSUE 18): the NorthStar smoke window captured with
    the trace buffer ARMED through TWO partitioned pipelines — the export
    must validate as Chrome trace-event JSON (B/E balanced, monotonic per
    tid, the partition pipelines on DISTINCT tracks so ≥2-core overlap is
    visible, ≥1 evict→replace flow arrow), the critical-path components
    must sum to the measured submit→bound latency, and the armed overhead
    is asserted from a MEASUREMENT (the buffer's accumulated tap self-time
    vs the timed wall, <1% with the 2ms absolute floor) published beside
    `disabled_check_ns` (tests/test_bench_quick.py)."""
    from kubernetes_tpu.obs import critpath, tracebuf
    from kubernetes_tpu.scheduler import Framework
    from kubernetes_tpu.scheduler.batch import BatchScheduler
    from kubernetes_tpu.scheduler.plugins import default_plugins
    from kubernetes_tpu.store import APIStore
    from kubernetes_tpu.testing import MakePod

    try:
        n_pods = sz(10_000, floor=1000)
        n_nodes = sz(500, floor=40)
        # warm-up on a throwaway cluster: shard-sized jit shapes must
        # compile before the timed window (the Partitioned rung discipline)
        _w = _partitioned_e2e(n_pods, n_nodes, 2, "ttw")[0]
        _w.stop()
        del _w
        # the disabled cost: ONE module-attribute check, measured
        dcn = tracebuf.disabled_check_cost_ns()
        buf = tracebuf.arm(capacity=200_000)
        try:
            sched, store, dt, bound = _partitioned_e2e(
                n_pods, n_nodes, 2, "tt")
            # the armed overhead measurement stops HERE: taps after the
            # timed window (the flow leg below) are not its cost
            instr_s = buf.self_seconds
            spans = []
            table = None
            for pipe in sched.pipelines:
                spans.extend(pipe.podtrace.snapshot().get("spans") or [])
                if table is None:
                    table = pipe.flightrec.stage_table()
            sched.stop()
            # evict→replace leg (separate small cluster, same armed
            # buffer): bound owner-ref'd pods deleted, then same-owner
            # replacements — the podtrace link path that export() renders
            # as Perfetto flow arrows
            fstore = APIStore()
            for n in _nodes(8, cpu="16", mem="64Gi"):
                fstore.create("nodes", n)
            fsched = BatchScheduler(fstore, Framework(default_plugins()),
                                    batch_size=1024, solver="fast")
            fsched.sync()
            owner = [{"kind": "ReplicaSet", "name": "rs-tt",
                      "uid": "u-rs-tt"}]
            firsts = []
            for i in range(8):
                p = MakePod(f"ttf-{i}").req({"cpu": "100m"}).obj()
                p.metadata.owner_references = [dict(r) for r in owner]
                firsts.append(p)
            fstore.create_many("pods", firsts, consume=True)
            fsched.run_until_idle()
            fsched.flush_binds()
            for p in firsts[:4]:
                fstore.delete("pods", p.key)
            fsched.run_until_idle()
            reps = []
            for i in range(4):
                p = MakePod(f"ttr-{i}").req({"cpu": "100m"}).obj()
                p.metadata.owner_references = [dict(r) for r in owner]
                reps.append(p)
            fstore.create_many("pods", reps, consume=True)
            fsched.run_until_idle()
            fsched.flush_binds()
            flow_spans = fsched.podtrace.snapshot().get("spans") or []
            spans.extend(flow_spans)
            fsched.stop()
            doc = buf.export(spans=spans)
            val = tracebuf.validate_export(doc)
            track_names = [ev.get("args", {}).get("name")
                           for ev in doc["traceEvents"]
                           if ev["ph"] == "M"
                           and ev["name"] == "thread_name"]
            partition_tracks = sum(
                1 for t in track_names
                if t and t.startswith("p") and t.endswith("-sched"))
            cp = critpath.analyze(spans, stage_table=table)
            overall = cp.get("overall") or {}
            st = buf.status()
        finally:
            tracebuf.disarm()
        results["TraceTimeline"] = {
            "wall_s": round(dt, 3),
            "pods": n_pods, "placed": bound,
            "pods_per_sec": round(bound / dt, 1) if dt > 0 else 0.0,
            "export_valid": val["valid"],
            "export_errors": val["errors"][:3],
            "events": st["trace_events_total"],
            "dropped": st["trace_events_dropped_total"],
            "tracks": val["tracks"],
            "partition_tracks": partition_tracks,
            "flow_arrows": val["flow_pairs"],
            "counters": val["counters"],
            # the armed budget, measured (never differenced): tap
            # self-time accumulated during the timed window
            "instrumentation_s": round(instr_s, 6),
            "overhead_frac": round(instr_s / dt, 6) if dt > 0 else 0.0,
            "disabled_check_ns": round(dcn, 2),
            "critpath": {
                "spans": cp.get("spans_analyzed", 0),
                "dominant": overall.get("dominant"),
                "dominant_share": overall.get("dominant_share"),
                "sum_p50_ms": overall.get("sum_p50_ms"),
                "total_p50_ms": overall.get("total_p50_ms"),
                "sum_p99_ms": overall.get("sum_p99_ms"),
                "total_p99_ms": overall.get("total_p99_ms"),
            },
        }
        print(f"{'TraceTimeline':>28}: {st['trace_events_total']} events "
              f"({st['trace_events_dropped_total']} dropped), "
              f"{partition_tracks} partition tracks, "
              f"{val['flow_pairs']} flow arrows, "
              f"overhead {instr_s / dt * 100 if dt > 0 else 0:.3f}% "
              f"of {dt:.2f}s, dominant={overall.get('dominant')}",
              file=sys.stderr)
    except Exception as e:
        results["TraceTimeline"] = {"error": str(e)[:200]}
        print(f"TraceTimeline: ERROR {e}", file=sys.stderr)



def rung_multiprocess(results):
    """MultiProcess_2w (ISSUE 19): the tentpole rung — the SAME
    constraint-free bind workload through an MPScheduler with TWO worker
    PROCESSES reading the store's pod columns from shared memory, solving
    locally, and submitting integer bind intents the owner arbitrates
    through bind_many + rv re-validation. Publishes conservation, the
    measured overlap (owner cpu + worker-reported cpu beyond wall — on a
    1-core rig that is ~0 and ab_comparable says so), 0 mid-run solver
    compiles (plain pods never touch the jit solvers), and the shm
    unlink-clean check (no named segment outlives stop())."""
    from kubernetes_tpu.scheduler.mpsched import MPScheduler
    from kubernetes_tpu.store import APIStore
    from kubernetes_tpu.store import shm
    from kubernetes_tpu.testing import MakePod, pod_conservation_report

    try:
        if not shm.available():
            results["MultiProcess_2w"] = {
                "skipped": "shared memory unavailable"}
            print("MultiProcess_2w: SKIPPED (no shared memory)",
                  file=sys.stderr)
            return
        n_pods = sz(20_000, floor=2000)
        n_nodes = sz(1000, floor=64)
        leaked_before = set(shm.leaked_segments())
        store = APIStore()
        for n in _nodes(n_nodes, cpu="16", mem="64Gi"):
            store.create("nodes", n)
        sched = MPScheduler(store, processes=2)
        CH = 10_000
        pending = [MakePod(f"mpb-{i}").req(
            {"cpu": "500m", "memory": "1Gi"}).obj() for i in range(n_pods)]
        keys = [pd.key for pd in pending]
        for lo in range(0, n_pods, CH):
            store.create_many("pods", pending[lo:lo + CH], consume=True)
        compiles0 = _solver_jit_cache()
        tms0 = os.times()
        t0 = time.perf_counter()
        sched.run_until_idle()
        dt = time.perf_counter() - t0
        tms1 = os.times()
        sched.flush_binds()
        compiles = sum(v - compiles0.get(k, 0)
                       for k, v in _solver_jit_cache().items() if v >= 0)
        st = sched.sched_stats()
        procs = st["processes"]
        rep = pod_conservation_report(store, sched, keys)
        c = rep["counts"]
        # overlap, measured: owner-process cpu (user+sys deltas) plus the
        # workers' self-reported process_time, minus wall — cpu beyond wall
        # can only come from processes genuinely running in parallel
        owner_cpu = ((tms1.user - tms0.user) + (tms1.system - tms0.system))
        worker_cpu = procs["worker_cpu_s"]
        overlap = round(max(0.0, owner_cpu + worker_cpu - dt), 6)
        sched.stop()
        leaked_after = [seg for seg in shm.leaked_segments()
                        if seg not in leaked_before]
        rig = _rig_info()
        cores = rig["cores"]
        ok = (c["lost"] == 0 and c["double_bound"] == 0
              and c["bound"] == n_pods)
        results["MultiProcess_2w"] = dict({
            "pods_per_sec": round(c["bound"] / dt, 1) if dt > 0 else 0.0,
            "wall_s": round(dt, 3),
            "pods": n_pods, "nodes": n_nodes, "placed": c["bound"],
            "processes": procs["configured"],
            "rounds": procs["rounds"],
            "stale_intents": procs["stale_intents"],
            "bind_conflicts": procs["bind_conflicts"],
            "worker_restarts": procs["worker_restarts"],
            "owner_cpu_s": round(owner_cpu, 4),
            "worker_cpu_s": round(worker_cpu, 4),
            "overlap_cpu_s": overlap,
            "concurrency_verdict": (_overlap_verdict(overlap, dt)
                                    if cores >= 2 else None),
            "ab_comparable": cores >= 2,
            "conservation": c,
            "conservation_ok": ok,
            "solver_compiles_during_run": compiles,
            "shm_leaked_segments": leaked_after,
            "shm_unlink_clean": not leaked_after,
            "per_worker": procs["workers"],
            "residual": procs["residual"],
            "solver": "ffd+mp2"}, **rig)
        print(f"{'MultiProcess_2w':>28}: {c['bound'] / dt:>9.0f} pods/s  "
              f"({c['bound']}/{n_pods} bound via 2 worker processes, "
              f"rounds={procs['rounds']} "
              f"stale={procs['stale_intents']} "
              f"conflicts={procs['bind_conflicts']}, "
              f"overlap {overlap:.2f}s cpu/{dt:.2f}s wall, "
              f"shm clean={not leaked_after})", file=sys.stderr)
    except Exception as e:
        results["MultiProcess_2w"] = {"error": str(e)[:200]}
        print(f"MultiProcess_2w: ERROR {e}", file=sys.stderr)


def rung_watch_fanout_store(results):
    """WatchFanout (ISSUE 19 satellite): the STORE's watch bus fanned out
    to a subscriber sweep — half lossy observability rings, half
    small-buffer cache watchers that the eviction path terminates when
    they fall behind — under create churn. Publishes the propagation-p99
    curve (commit->dequeue, settled per point) and the <=10s SLO verdict
    at EVERY point: fan-out scale must degrade the tail gracefully, never
    cliff it."""
    from kubernetes_tpu.scheduler.slo import CONTROL_PLANE_SLO
    from kubernetes_tpu.store import APIStore
    from kubernetes_tpu.testing import MakePod

    try:
        slo_s = CONTROL_PLANE_SLO["watch_propagation_p99_s"]
        n_events = sz(512, floor=128)
        sweep = (sz(32, floor=8), sz(256, floor=16), sz(1024, floor=32))
        curve = []
        ok_all = True
        for n_subs in sweep:
            store = APIStore()
            watches = []
            for i in range(n_subs):
                if i % 2 == 0:
                    # observability consumer: lossy ring survives overflow
                    w = store.watch(kind="pods", ring=True, maxsize=48)
                else:
                    # cache consumer: small buffer, falls behind -> evicted
                    w = store.watch(kind="pods", maxsize=48)
                watches.append(w)
            store.clear_watch_propagation()
            pods = [MakePod(f"wf{n_subs}-{i}").req({"cpu": "100m"}).obj()
                    for i in range(n_events)]
            t0 = time.perf_counter()
            CH = 64
            for lo in range(0, n_events, CH):
                store.create_many("pods", pods[lo:lo + CH], consume=True)
                # drain a rotating half each wave: mixed consumer speeds —
                # the undrained half's non-ring watchers fall behind and
                # evict, the rings drop oldest and survive
                off = (lo // CH) % 2
                for w in watches[off::2]:
                    if not w.terminated:
                        w.drain()
            for w in watches:
                if not w.terminated:
                    w.drain()
            dt = time.perf_counter() - t0
            wtel = store.watch_telemetry()
            prop = wtel["propagation"]
            evicted = sum(1 for w in watches if w.terminated)
            ring_dropped = sum(w.ring_dropped for w in watches)
            point_ok = (prop["count"] > 0
                        and (prop["p99_s"] or 0.0) <= slo_s)
            ok_all = ok_all and point_ok
            curve.append({
                "subscribers": n_subs,
                "events": n_events,
                "wall_s": round(dt, 3),
                "deliveries": prop["count"],
                "propagation_p50_s": prop["p50_s"],
                "propagation_p99_s": prop["p99_s"],
                "evicted": evicted,
                "ring_dropped": ring_dropped,
                "dropped": wtel["dropped"],
                "slo_ok": point_ok,
            })
            for w in watches:
                w.stop()
            del store, watches
        results["WatchFanout"] = dict({
            "points": curve,
            "slo_s": slo_s,
            "slo_ok": ok_all,
            "max_p99_s": max((pt["propagation_p99_s"] or 0.0)
                             for pt in curve),
            "subscribers_max": max(pt["subscribers"] for pt in curve),
        }, **_rig_info())
        print(f"{'WatchFanout':>28}: p99 curve "
              + " ".join(f"{pt['subscribers']}sub="
                         f"{(pt['propagation_p99_s'] or 0.0) * 1000:.1f}ms"
                         for pt in curve)
              + f"  (SLO<= {slo_s:.0f}s: {'PASS' if ok_all else 'FAIL'})",
              file=sys.stderr)
    except Exception as e:
        results["WatchFanout"] = {"error": str(e)[:200]}
        print(f"WatchFanout: ERROR {e}", file=sys.stderr)


RUNGS = [
    ("SchedulingBasic", rung_basic),
    ("TopologySpreading", rung_topology_spread),
    ("PodAntiAffinity", rung_pod_anti_affinity),
    ("PodAffinity", rung_pod_affinity),
    ("AntiAffinityNSSelector", rung_anti_affinity_ns_selector),
    ("MixedChurn", rung_mixed_churn),
    ("Preemption", rung_preemption),
    ("PreemptionAsync", rung_preemption_async),
    ("NodeAffinity", rung_node_affinity),
    ("PreferredTopologySpreading", rung_preferred_topology_spread),
    ("NorthStar", rung_north_star),
    ("NorthStarWarm", rung_north_star_warm),
    ("NorthStarEndToEnd", rung_north_star_endtoend),
    ("NorthStarSoak", rung_north_star_soak),
    ("BindCommit", rung_bind_commit),
    ("SchedStages", rung_sched_stages),
    ("GangScheduling", rung_gang),
    ("GangPreemption", rung_gang_preempt),
    ("Defrag", rung_defrag),
    ("AffinityQuality", rung_affinity_quality),
    ("Partitioned", rung_partitioned),
    ("ChaosChurn", rung_chaos_churn),
    ("MultiProcess", rung_multiprocess),
    ("WatchFanout", rung_watch_fanout_store),
    ("ControlPlane", rung_control_plane),
    ("SchedLint", rung_schedlint),
    ("TraceTimeline", rung_trace_timeline),
    ("Transport", rung_transport),
    ("ApiserverWatchFanout", rung_watch_fanout),
]

# --quick: the tier-1 smoke ladder — SMOKE-sized shapes, the rungs that
# exercise the host pipeline end-to-end, <=60s wall, same JSON line on
# stdout. Catches perf-path regressions (a broken coalesced ingest or bind
# path fails loudly here) without the full ladder's budget.
QUICK_RUNGS = ("SchedulingBasic", "MixedChurn", "NorthStarEndToEnd",
               "NorthStarSoak", "BindCommit", "SchedStages",
               "GangScheduling", "GangPreemption", "Defrag", "Partitioned",
               "ChaosChurn", "MultiProcess", "WatchFanout", "ControlPlane",
               "SchedLint", "TraceTimeline")
QUICK_BUDGET_S = 135.0


def main():
    global SMOKE, GLOBAL_BUDGET_S, MIN_RUNG_BUDGET_S, RUNGS
    results = {}
    quick = "--quick" in sys.argv
    if quick:
        SMOKE = True
        GLOBAL_BUDGET_S = min(GLOBAL_BUDGET_S, QUICK_BUDGET_S)
        MIN_RUNG_BUDGET_S = 5.0
        RUNGS = [(n, fn) for n, fn in RUNGS if n in QUICK_RUNGS]
    from kubernetes_tpu.device import require_tpu, use_compile_cache

    use_compile_cache()
    try:
        device = require_tpu()
    except RuntimeError as e:  # NoTPUError included: no chip, no numbers
        print(f"bench: {e}", file=sys.stderr)
        sys.exit(2)
    platform = device["platform"]
    print(f"device: {device}", file=sys.stderr)

    for name, rung in RUNGS:
        if budget_left() < MIN_RUNG_BUDGET_S:
            results[f"{name}_skipped"] = {
                "error": f"global budget exhausted ({GLOBAL_BUDGET_S:.0f}s)"}
            print(f"{name}: SKIPPED (budget)", file=sys.stderr)
            continue
        t0 = time.monotonic()
        rung(results)
        print(f"-- {name} took {time.monotonic() - t0:.1f}s "
              f"({budget_left():.0f}s budget left)", file=sys.stderr)
        checkpoint(results)

    # rig honesty columns (ISSUE 13 satellite): every successful rung's
    # JSON carries the core count + cgroup cpu quota it ran under, so a
    # core-starved run can never masquerade as a comparable number in the
    # BENCH_r* series (setdefault: the A/B rungs' own cores columns win)
    rig = _rig_info()
    for w in results.values():
        if isinstance(w, dict) and "error" not in w:
            w.setdefault("cores", rig["cores"])
            w.setdefault("cpu_quota", rig["cpu_quota"])

    ratios = [w["vs_baseline"] for w in results.values() if "vs_baseline" in w]
    headline = results.get("SchedulingBasic", {})
    out = {
        "metric": "scheduling_throughput_5000nodes_10000pods",
        "value": headline.get("pods_per_sec", 0.0),
        "unit": "pods/s",
        "vs_baseline": headline.get("vs_baseline", 0.0),
        "min_vs_baseline": min(ratios) if ratios else 0.0,
        "platform": platform,
        "device_kind": device["kind"],
        "device_count": device["count"],
        "rig": rig,
        "workloads": results,
    }
    if quick:
        out["quick"] = True
    print(json.dumps(out))


if __name__ == "__main__":
    main()
