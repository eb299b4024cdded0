#!/usr/bin/env python3
"""Chip smoke: the served batch-scheduling path, once, on one TPU.

Drives what a user runs: `kadm.init_control_plane` (the API server and the
leader-elected control plane, whose scheduler is `BatchScheduler(solver=
"auto")`) at the north-star cluster: 10,000 nodes, 100,000 pods. The nodes register through the store's bulk
API, with a renewed Lease each; 90% of the pods follow as plain pods, which
take the waterfill (`fast`) path, and once they are bound the other 10%, with
zone PodTopologySpread and hostname anti-affinity within groups of 8, which
take the `repair` path (one constrained pod sends its whole batch there). A
few pods are created and read back over HTTP. The scan and waterfill kernels
are then run on one fixed snapshot (2048 nodes, 1024 mixed pods) on the TPU
and on the CPU, and must agree bit for bit.

This is a smoke run, not a benchmark: its walls include compilation and the
first run of every shape.

The last line of stdout, on a pass on a TPU, is
  {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}
Exit codes: 0 passed on a TPU; 1 a check failed; 2 no TPU (the platform JAX
found is named); 3 a CPU rehearsal passed (JAX_PLATFORMS=cpu, every size cut
64x), which prints no result.

The top level touches no device: the chip belongs to one process at a time.
"""

from __future__ import annotations

import faulthandler
import json
import os
import sys
import threading
import time
import traceback

ZONE = "topology.kubernetes.io/zone"
HOST = "kubernetes.io/hostname"
N_NODES, N_PLAIN, N_CONSTRAINED, N_HTTP = 10_000, 90_000, 10_000, 8
N_ZONES, ANTI_GROUP = 16, 8
LEASE_RENEW_S, LEASE_SLICES = 10.0, 20  # kubelet's node-lease renew interval
PARITY_NODES, PARITY_PODS = 2048, 1024
REHEARSAL_CUT = 64  # a CPU rehearsal (JAX_PLATFORMS=cpu) cuts every size
WAVE_DEADLINE_S = 600.0


class SmokeFailure(Exception):
    pass


def say(msg: str) -> None:
    print(f"smoke: {msg}", flush=True)


class CompileCounter:
    """Counts XLA compiles (and persistent-cache hits among them) per jitted
    function, from JAX's own monitoring events."""

    def __init__(self):
        self.compiles: dict = {}
        self.seconds = 0.0
        self.cache_hits = 0

    def install(self) -> None:
        from jax import monitoring

        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, fun_name="?", **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles[fun_name] = self.compiles.get(fun_name, 0) + 1
            self.seconds += duration

    def _on_event(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def total(self) -> int:
        return sum(self.compiles.values())


def _nodes(n):
    from kubernetes_tpu.testing import MakeNode

    return [MakeNode(f"node-{i}").labels({HOST: f"node-{i}",
                                          ZONE: f"zone-{i % N_ZONES}"})
            .capacity({"cpu": "16", "memory": "64Gi", "pods": "110"}).obj()
            for i in range(n)]


class Kubelets:
    """What each node's kubelet does for the control plane here: hold a
    Lease in kube-node-lease and renew it every LEASE_RENEW_S. Without it the
    node lifecycle controller taints every node not-ready after its grace
    period and nothing schedules. Like real kubelets, the renewals spread over
    the interval (one of LEASE_SLICES slices per tick) instead of landing as
    one burst of 10,000 writes; ticks keep a fixed cadence: these "kubelets"
    share the interpreter with the control plane, and a tick slowed by it
    must not push the next one later (a lease older than the 40 s grace gets
    its node's pods evicted)."""

    def __init__(self, store, names):
        from kubernetes_tpu.api.types import ObjectMeta
        from kubernetes_tpu.api.workloads import Lease

        self.store = store
        self.leases = [Lease(
            metadata=ObjectMeta(name=n, namespace="kube-node-lease"),
            holder_identity=n) for n in names]
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._loop, daemon=True)

    def start(self) -> "Kubelets":
        from kubernetes_tpu.utils import Clock

        now = Clock().now()
        for lease in self.leases:
            lease.acquire_time = lease.renew_time = now
        self.store.create_many("leases", self.leases)
        self._thread.start()
        return self

    def _loop(self) -> None:
        from kubernetes_tpu.utils import Clock

        clock = Clock()
        tick = LEASE_RENEW_S / LEASE_SLICES
        due = clock.now() + tick
        k = 0
        while not self._stop.wait(max(0.0, due - clock.now())):
            due += tick
            for lease in self.leases[k::LEASE_SLICES]:  # update() stores a copy
                lease.renew_time = clock.now()
                self.store.update("leases", lease, check_rv=False)
            k = (k + 1) % LEASE_SLICES

    def stop(self) -> None:
        self._stop.set()
        self._thread.join(timeout=60)


def _plain(n):
    from kubernetes_tpu.testing import MakePod

    return [MakePod(f"plain-{i}").req({"cpu": "500m", "memory": "1Gi"}).obj()
            for i in range(n)]


def _constrained(n):
    from kubernetes_tpu.testing import MakePod

    return [MakePod(f"spread-{i}")
            .labels({"app": "spread", "grp": f"g{i // ANTI_GROUP}"})
            .req({"cpu": "500m", "memory": "1Gi"})
            .topology_spread(1, ZONE, "DoNotSchedule", {"app": "spread"})
            .pod_anti_affinity(HOST, {"grp": f"g{i // ANTI_GROUP}"})
            .obj() for i in range(n)]


def _create(store, pods) -> list:
    keys = [p.key for p in pods]
    for lo in range(0, len(pods), 10_000):
        store.create_many("pods", pods[lo:lo + 10_000], consume=True)
    return keys


def _check_healthy(sched) -> None:
    """The server requeues a failed solve and trips its breaker; a smoke run
    counts either as a failure, at once."""
    br = sched.breaker.describe()
    if br["state"] != "closed" or br["failures_total"]:
        raise SmokeFailure(f"solver breaker {br}")
    for rec in sched.flightrec.records():
        if rec.get("error") or "SolverError" in rec.get("reasons", {}):
            raise SmokeFailure(f"batch error: {rec.get('error')} "
                               f"reasons={rec.get('reasons')}")


def _wait_bound(sched, target: int, what: str) -> float:
    t0 = t_say = time.monotonic()
    while True:
        _check_healthy(sched)
        bound = sched.scheduled_count  # binds the store committed
        if bound >= target:
            return time.monotonic() - t0
        if time.monotonic() - t_say > 30:
            t_say = time.monotonic()
            say(f"{what}: {bound}/{target} bound after {t_say - t0:.0f}s, "
                f"{sched.batches_solved} batches")
        if time.monotonic() - t0 > WAVE_DEADLINE_S:
            raise SmokeFailure(f"{what}: {bound}/{target} pods bound after "
                               f"{WAVE_DEADLINE_S:.0f}s")
        time.sleep(0.05)


def schedule_phase(cut: int, counter: CompileCounter, report: dict) -> None:
    from kubernetes_tpu.api.resources import (compute_pod_resource_request,
                                              quantity_milli_value,
                                              quantity_value)
    from kubernetes_tpu.cli.kadm import init_control_plane
    from kubernetes_tpu.server.client import RESTClient
    from kubernetes_tpu.testing import assert_pod_conservation

    n_nodes, n_plain = N_NODES // cut, N_PLAIN // cut
    n_constrained = N_CONSTRAINED // cut
    t0 = time.monotonic()
    res = init_control_plane(port=0)
    kubelets = None
    try:
        if not res.wait_ready(timeout=60):
            raise SmokeFailure("control plane never took the lease")
        cp = res.control_plane
        # the leader starts the scheduler and then each controller; load
        # waits for all of them, as it would after `kadm init` returns
        while len(cp.controllers) < len(cp.controller_names):
            time.sleep(0.01)
        sched, store = cp.scheduler, res.store
        nodes = _nodes(n_nodes)
        kubelets = Kubelets(store, [n.metadata.name for n in nodes]).start()
        store.create_many("nodes", nodes, consume=True)
        report["setup_s"] = time.monotonic() - t0
        say(f"control plane up at {res.url}, {n_nodes} nodes registered in "
            f"{report['setup_s']:.3f}s")

        c0 = counter.total()
        t1 = time.monotonic()
        keys = _create(store, _plain(n_plain))
        say(f"{n_plain} plain pods created in {time.monotonic() - t1:.3f}s")
        client = RESTClient(res.url)
        http_names = [f"http-{i}" for i in range(N_HTTP)]
        for name in http_names:
            client.create("pods", {
                "metadata": {"name": name},
                "spec": {"containers": [{
                    "name": "c", "image": "pause",
                    "resources": {"requests": {"cpu": "100m",
                                               "memory": "128Mi"}}}]}})
        keys += [f"default/{n}" for n in http_names]
        wall = _wait_bound(sched, len(keys), "wave 1")
        report["wave1"] = {"pods": len(keys), "wall_s": wall,
                           "compiles": counter.total() - c0}
        say(f"wave 1 (smoke run, not a benchmark): {len(keys)} plain pods "
            f"bound in {wall:.3f}s incl. compile, "
            f"{report['wave1']['compiles']} compiles")
        for name in http_names:
            got = client.get("pods", name)["spec"].get("nodeName")
            if not got or got != store.get("pods", f"default/{name}").spec.node_name:
                raise SmokeFailure(f"HTTP pod {name} reads nodeName={got!r}")
        say(f"{N_HTTP} pods created over HTTP read back bound over HTTP")

        c1 = counter.total()
        keys += _create(store, _constrained(n_constrained))
        wall = _wait_bound(sched, len(keys), "wave 2")
        report["wave2"] = {"pods": n_constrained, "wall_s": wall,
                           "compiles": counter.total() - c1}
        say(f"wave 2 (smoke run, not a benchmark): {n_constrained} "
            f"constrained pods bound in {wall:.3f}s incl. compile, "
            f"{report['wave2']['compiles']} compiles")
        report["scheduling_compiles"] = counter.total() - c0
        # relists after each controller's first LIST: a burst that still
        # forced them shows here
        report["controller_relists"] = {
            type(c).__name__: c.relists for c in cp.controllers
            if getattr(c, "relists", 0)}
        _check_healthy(sched)
    except Exception:
        # what every thread of the control plane was doing when it failed
        faulthandler.dump_traceback(file=sys.stderr, all_threads=True)
        raise
    finally:
        if kubelets is not None:
            kubelets.stop()
        res.stop()

    # quiescent from here: the control plane is stopped, every bind committed
    rep = assert_pod_conservation(store, sched, keys)
    if rep["counts"]["bound"] != len(keys):
        raise SmokeFailure(f"not every pod bound: {rep['counts']}")
    nodes = {n.metadata.name: n for n in store.list("nodes")[0]}
    used: dict = {}
    per_zone: dict = {}
    grp_nodes = set()
    for p in store.list("pods")[0]:
        r = compute_pod_resource_request(p)
        u = used.setdefault(p.spec.node_name, [0, 0, 0])
        u[0] += r.milli_cpu
        u[1] += r.memory
        u[2] += 1
        grp = p.metadata.labels.get("grp")
        if grp is not None:  # wave 2: the constraints, checked from the store
            if (grp, p.spec.node_name) in grp_nodes:
                raise SmokeFailure(f"anti-affinity group {grp} twice on "
                                   f"{p.spec.node_name}")
            grp_nodes.add((grp, p.spec.node_name))
            zone = nodes[p.spec.node_name].metadata.labels[ZONE]
            per_zone[zone] = per_zone.get(zone, 0) + 1
    if per_zone and (len(per_zone) < N_ZONES
                     or max(per_zone.values()) - min(per_zone.values()) > 1):
        raise SmokeFailure(f"zone spread skew > 1: {per_zone}")
    for name, (cpu, mem, count) in used.items():
        alloc = nodes[name].status.allocatable
        if (cpu > quantity_milli_value(alloc["cpu"])
                or mem > quantity_value(alloc["memory"])
                or count > quantity_value(alloc["pods"])):
            raise SmokeFailure(f"node {name} over allocatable: cpu={cpu}m "
                               f"mem={mem} pods={count} alloc={alloc}")
    totals = sched.repair_totals
    if not totals["batches"] or totals["violations"]:
        raise SmokeFailure(f"repair totals {totals}")
    paths = dict(sched.solve_paths)
    if "fast" not in paths or "repair" not in paths or "native" in paths:
        raise SmokeFailure(f"solve paths {paths}")
    report.update(pods_bound=len(keys), nodes_used=len(used),
                  solve_paths=paths, repair=dict(totals),
                  batches=sched.batches_solved)
    say(f"checks passed: {len(keys)} pods bound once on {len(used)} nodes, "
        f"none over allocatable; zone skew <= 1 and no anti-affinity group "
        f"twice on a node; solve paths {paths}; repair {dict(totals)}; "
        f"breaker closed")


def _groups(inp):
    """make_groups over the pod rows the solver inputs carry."""
    from types import SimpleNamespace

    import numpy as np

    from kubernetes_tpu.models.waterfill import make_groups

    return make_groups(SimpleNamespace(
        pods=range(inp.req.shape[0]),
        class_of_pod=np.asarray(inp.class_of_pod), req=np.asarray(inp.req),
        req_nz=np.asarray(inp.req_nz),
        balanced_active=np.asarray(inp.balanced_active)))


def parity_phase(cut: int, report: dict) -> None:
    import jax
    import numpy as np

    from __graft_entry__ import _build_problem
    from kubernetes_tpu.models.waterfill import waterfill_solve
    from kubernetes_tpu.ops.solver import greedy_scan_solve

    n_nodes = max(PARITY_NODES // cut, N_ZONES)
    n_pods = PARITY_PODS // cut
    inp, d_max = _build_problem(n_nodes=n_nodes, n_pods=n_pods, mixed=True)
    groups = _groups(inp)
    got = {}
    for dev in (jax.devices()[0], jax.devices("cpu")[0]):
        x = jax.device_put(inp, dev)
        scan = np.asarray(greedy_scan_solve(x, d_max)[0])
        got[dev.platform] = (scan, waterfill_solve(x, groups))
    (scan_d, wf_d), (scan_c, wf_c) = got[jax.devices()[0].platform], got["cpu"]
    if not (scan_d >= 0).all():
        raise SmokeFailure(f"scan placed {(scan_d >= 0).sum()}/{n_pods}")
    for name, a, b in (("greedy_scan_solve", scan_d, scan_c),
                       ("waterfill_solve", wf_d, wf_c)):
        if a is None or b is None or not np.array_equal(a, b):
            diff = None if a is None or b is None else int((a != b).sum())
            raise SmokeFailure(f"{name} device/CPU assignments differ "
                               f"({diff} of {n_pods} pods)")
    report["parity"] = {"nodes": n_nodes, "pods": n_pods}
    say(f"parity: scan and waterfill assignments identical on "
        f"{jax.devices()[0].device_kind} and CPU ({n_nodes} nodes, "
        f"{n_pods} mixed pods)")


def main() -> int:
    from kubernetes_tpu.device import require_tpu, use_compile_cache

    t_start = time.monotonic()
    cache = use_compile_cache()
    try:
        device = require_tpu()
    except RuntimeError as e:  # NoTPUError included
        print(f"smoke: {e}", file=sys.stderr)
        return 2
    rehearsal = device["platform"] != "tpu"
    cut = REHEARSAL_CUT if rehearsal else 1
    say(f"platform={device['platform']} device_kind={device['kind']} "
        f"count={device['count']} compile_cache={cache} "
        f"JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r} "
        f"cluster=north star / {cut}"
        + (" (CPU rehearsal)" if rehearsal else ""))
    counter = CompileCounter()
    counter.install()
    report: dict = {"device": device, "cut": cut}
    failures = []
    for name, fn in (("schedule", lambda: schedule_phase(cut, counter, report)),
                     ("parity", lambda: parity_phase(cut, report))):
        t0 = time.monotonic()
        try:
            fn()
        except Exception as e:  # every phase runs; any failure fails the run
            traceback.print_exc()
            failures.append(f"{name}: {type(e).__name__}: {e}")
        report[f"{name}_phase_s"] = time.monotonic() - t0
        say(f"phase {name}: {report[f'{name}_phase_s']:.3f}s wall")

    import jax

    stats = jax.devices()[0].memory_stats() or {}
    report.update(compiles=counter.total(), compile_s=counter.seconds,
                  compile_cache_hits=counter.cache_hits,
                  compiles_by_fn=counter.compiles,
                  peak_bytes_in_use=stats.get("peak_bytes_in_use"),
                  wall_s=time.monotonic() - t_start)
    say(f"compiles={report['compiles']} compile_s={counter.seconds:.3f} "
        f"cache_hits={counter.cache_hits} "
        f"peak_bytes_in_use={report['peak_bytes_in_use']} "
        f"wall_s={report['wall_s']:.3f}")
    say("report " + json.dumps(report, default=str))
    if failures:
        for f in failures:
            print(f"smoke: FAILED {f}", file=sys.stderr)
        return 1
    if rehearsal:
        say("CPU rehearsal passed; no result without a TPU")
        return 3
    print(json.dumps({"ok": True, "device": {"platform": device["platform"],
                                             "kind": device["kind"],
                                             "count": device["count"]}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
