#!/usr/bin/env python3
"""The benchmark: one cell of BENCHMARK.json, one run.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A run brings up the served control plane (`kadm.init_control_plane`: the API
server and the leader-elected control plane, whose scheduler is
`BatchScheduler(solver="auto")`, with every controller it starts), registers
the configuration's nodes with a Lease each renewed as kubelets do, binds
the configuration's init pods, and starts two sources of load: the cell's
traffic mix, created through the in-process store, and an HTTP probe child
that creates pods over the REST API open-loop. After the mix's warm-up the
window opens for `--seconds`; when it closes the generator stops, the
backlog drains (bounded), the control plane stops, and the plain reference
(`reference.py`) checks every guarantee on the ordered log of what the
watch saw.

With --trace 0 the result carries the cell's end-to-end metrics, with
--trace 1 its per-layer metrics, read with a profiler trace of the whole
window. The last line of stdout is one JSON object; the numbers compared
with their limits close stderr, and close the JSON object too, under
"checks".

Exit codes: 0 a result was printed; 2 no TPU, or fewer chips than the cell
asks for (the platform JAX found is named); 3 a CPU rehearsal ran
(JAX_PLATFORMS=cpu: every size cut REHEARSAL_CUT times), which prints no
result; 1 the run failed.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402
from typing import Optional  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
REHEARSAL_CUT = 64
SETTLE_TIMEOUT_S = 600.0


def say(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def _place_compile_cache() -> None:
    """Programs that take a second or more to compile (JAX's default
    threshold) go to <checkout>/.jax_cache, a fixed path, so every run after
    the first in a checkout loads them from there. No size limit: a limit
    turns on JAX's LRU bookkeeping, a scan of the directory at every write,
    which slowed consecutive runs on the chip. Set before JAX is imported:
    it reads these at import."""
    os.makedirs(CACHE_DIR, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"


@dataclass
class Window:
    """What the metric readers read (`benchmark/metrics/*.py`)."""

    window_s: float
    setup_s: float
    binds_in_window: int
    bind_ms: list  # per pod created in the window: due -> binding observed
    api_ms: list  # per probe request due in the window: due -> response
    gen_late_ms: list  # per pod due in the window: due -> create sent
    stages_ms: dict  # flight-recorder stage totals over the window
    compiles_in_window: int
    relists: int
    batches: list = field(default_factory=list)  # flight records (traced runs)
    trace: Optional[dict] = None  # devtrace.reduce() of the window (traced runs)


class BatchPoller:
    """Keeps every flight-recorder batch record (the ring holds 64) by
    reading the ring every 50 ms; `missed` counts records that left the
    ring between two reads."""

    def __init__(self, flightrec):
        self.fr = flightrec
        self.records: dict = {}
        self.missed = 0
        self._last = None
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="bench-batch-poller")
        # flight records are stamped with time.time(); place them on the
        # monotonic clock
        self.offset = time.monotonic() - time.time()

    def start(self) -> "BatchPoller":
        self._thread.start()
        return self

    def _loop(self) -> None:
        while not self._stop.wait(0.05):
            self.poll()

    def poll(self) -> None:
        recs = self.fr.records()
        if recs and self._last is not None and recs[0]["seq"] > self._last + 1:
            self.missed += recs[0]["seq"] - self._last - 1
        for r in recs:
            self.records[r["seq"]] = r
        if recs:
            self._last = recs[-1]["seq"]

    def stop(self) -> None:
        self._stop.set()
        self._thread.join(timeout=10)
        self.poll()

    def in_window(self, lo: float, hi: float) -> list:
        out = []
        for r in sorted(self.records.values(), key=lambda r: r["seq"]):
            end = r["ts"] + self.offset
            if lo <= end < hi:
                out.append(dict(r, end=end))
        return out


class ProbeChild:
    """The HTTP probe (`probe.py`) as a child process. It never imports JAX."""

    def __init__(self, url: str, rate: float, salt: str, namespace: str,
                 template: dict):
        self.proc = subprocess.Popen(
            [sys.executable, os.path.join(ROOT, "benchmark", "probe.py"),
             "--url", url, "--rate", repr(rate), "--salt", salt,
             "--namespace", namespace, "--template", json.dumps(template)],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)
        self.t_stop = None

    def stop_sending(self) -> None:
        self.t_stop = time.monotonic()
        self.proc.stdin.write("stop\n")
        self.proc.stdin.flush()

    def results(self) -> dict:
        """The requests, once every one in flight has ended."""
        return json.loads(self.proc.stdout.readline())

    def readback(self, timeout_s: float = 120.0) -> dict:
        """The node each pod of the namespace reads over HTTP, now."""
        out, _ = self.proc.communicate("readback\n", timeout=timeout_s)
        return json.loads(out.strip().splitlines()[-1])

    def kill(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
        self.proc.wait(timeout=30)


def _percentiles(vals) -> str:
    from benchmark.stats import quantile

    if not vals:
        return "none"
    return (f"n={len(vals)} p50={quantile(vals, 0.5):.3f} "
            f"p99={quantile(vals, 0.99):.3f} max={max(vals):.3f}")


def _stage_totals(sched) -> dict:
    return {k: v["total_ms"] for k, v in sched.flightrec.stage_table().items()}


def _wait(pred, timeout_s: float, what: str, check=None) -> None:
    t0 = time.monotonic()
    while not pred():
        if check is not None:
            check()
        if time.monotonic() - t0 > timeout_s:
            raise RuntimeError(f"{what}: not done after {timeout_s:.0f}s")
        time.sleep(0.02)


def run_cell(cell, seed: int, seconds: float, trace: bool, cut: int,
             device: dict, fault: Optional[str] = None) -> dict:
    """Run one cell and return its result object (as printed), with the
    diagnostics that stderr carries under "diag"."""
    from benchmark import faults as faults_mod
    from benchmark.catalog import read_metrics
    from benchmark.compiles import CompileCounter
    from benchmark.deploy import PROBE_NAMESPACE, Deployment, PodFactory
    from benchmark.informer import Informer
    from benchmark.reference import PodShape, check, limits
    from benchmark.traffic import Generator

    config, traffic = cell.config, cell.traffic
    templates = config["templates"]
    salt = f"{seed % (1 << 32):08x}"
    counter = CompileCounter().install()
    dep = Deployment(config, cut)
    informer = gen = probe = poller = None
    diag: dict = {"cut": cut, "seed": seed}
    trace_dir = None
    try:
        dep.up()
        sched, store = dep.sched, dep.store
        informer = Informer(store).start()
        say(f"control plane up at {dep.url}; {len(dep.names)} nodes "
            f"({time.monotonic() - T_START:.3f}s)")

        init = config["init_pods"]
        n_init = max(1, init["count"] // cut)
        f_init = PodFactory(templates[init["template"]])
        init_pods = f_init.make([f"i-{salt}-{i}" for i in range(n_init)], salt)
        init_keys = {f"default/{p.metadata.name}" for p in init_pods}
        for lo in range(0, n_init, 5000):
            _n, errs = store.create_many("pods", init_pods[lo:lo + 5000],
                                         consume=True)
            if errs:
                raise RuntimeError(f"init pods refused: {errs[:3]}")
        _wait(lambda: informer.pending() == 0 and informer.seen_all(init_keys),
              SETTLE_TIMEOUT_S, "init pods bound")
        say(f"{n_init} init pods bound ({time.monotonic() - T_START:.3f}s)")

        probe_tmpl = templates[config["probe_pods"]["template"]]
        probe = ProbeChild(dep.url, max(traffic["probe_per_s"] / cut, 1.0), salt,
                           PROBE_NAMESPACE, probe_tmpl)
        measured = templates[config["measured_pods"]["template"]]
        gen = Generator(traffic, store, informer, PodFactory(measured), seed,
                        cut)
        # warm-up, first part: one backlog of each size the mix names, bound
        # and deleted in turn, so each batch-size bucket the window's
        # batches fall in has compiled
        for n in traffic.get("warmup_backlogs", []):
            n = max(1, n // cut)
            keys = gen.create_now(n)
            _wait(lambda: all(k in informer.bound_at for k in keys),
                  SETTLE_TIMEOUT_S, f"warm-up backlog of {n}")
            gen.delete_now(keys)
        say(f"warm-up backlogs bound ({time.monotonic() - T_START:.3f}s)")
        gen.start()
        if traffic["arrivals"] == "burst":
            # warm-up, second part: the mix itself, for the bursts it names
            # (or the seconds, for arrivals in an open loop)
            _wait(lambda: (gen.bursts_started() > traffic["warmup_bursts"]),
                  SETTLE_TIMEOUT_S, "warm-up bursts",
                  check=lambda: _generator_ok(gen))
        else:
            t_w = time.monotonic() + traffic["warmup_s"]
            _wait(lambda: time.monotonic() >= t_w, traffic["warmup_s"] + 5,
                  "warm-up", check=lambda: _generator_ok(gen))
        if trace:
            poller = BatchPoller(sched.flightrec).start()

        # -- the window ----------------------------------------------------
        if fault is not None:  # a control: the timed path broken underneath
            faults_mod.apply(fault, dep)
        if trace:
            # the whole window is traced; the trace starts before the
            # window opens and is written out after it closes
            import jax

            from benchmark.devtrace import WINDOW

            trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            annotation = jax.profiler.TraceAnnotation(WINDOW)
            annotation.__enter__()
        t_open = time.monotonic()
        c_open, st_open, rl_open = counter.total(), _stage_totals(sched), dep.relists()
        diag["pending_open"] = informer.pending()
        b_open = sched.batches_solved
        by_fn_open, cs_open = dict(counter.compiles), counter.seconds
        t_close = t_open + seconds
        time.sleep(max(0.0, t_close - time.monotonic()))
        t_close = time.monotonic()
        if trace:
            annotation.__exit__(None, None, None)
        c_close, st_close, rl_close = counter.total(), _stage_totals(sched), dep.relists()
        diag["pending_close"] = informer.pending()
        diag["batches_in_window"] = sched.batches_solved - b_open
        diag["compile_s_in_window"] = counter.seconds - cs_open
        diag["compiles_in_window_by_fn"] = {
            k: v - by_fn_open.get(k, 0) for k, v in counter.compiles.items()
            if v > by_fn_open.get(k, 0)}
        gen.stop()
        probe.stop_sending()
        if trace:
            jax.profiler.stop_trace()
        _generator_ok(gen)
        say(f"window closed: {t_close - t_open:.3f}s; "
            f"{informer.pending()} pods pending")

        # -- drain: every acknowledged create seen and bound, bounded -------
        t_drain = time.monotonic() + traffic["drain_s"]

        def drained(acked):
            return informer.pending() == 0 and informer.seen_all(acked)

        acked = init_keys | gen.acked
        while time.monotonic() < t_drain and not drained(acked):
            time.sleep(0.05)
        probe_out = probe.results()
        # probe pods acknowledged late are drained too, before the readback
        acked_probe = {f"{PROBE_NAMESPACE}/{r[4]}" for r in probe_out["results"]
                       if r[3] == 201}
        acked = acked | acked_probe
        while time.monotonic() < t_drain and not drained(acked):
            time.sleep(0.05)
        probe_out.update(probe.readback(timeout_s=traffic["drain_s"] + 90))
        t_drained = time.monotonic()
        diag["drain_s"] = t_drained - t_close
        _generator_ok(gen)
        import jax

        stats = jax.devices()[0].memory_stats() or {}
        mem_peak = stats.get("peak_bytes_in_use")
        if poller is not None:
            poller.stop()
        diag.update(breaker=sched.breaker.describe(),
                    solve_paths=dict(sched.solve_paths),
                    repair=dict(sched.repair_totals),
                    compiles_by_fn=dict(counter.compiles),
                    compile_s=counter.seconds, cache_hits=counter.cache_hits)
    except Exception:
        import faulthandler

        faulthandler.dump_traceback(file=sys.stderr, all_threads=True)
        raise
    finally:
        if gen is not None:
            gen.stop()
        if probe is not None:
            probe.kill()
        dep.down()
        if informer is not None:
            informer.stop()

    # -- the reference check (the control plane is stopped) ------------------
    shapes = {name: PodShape(t) for name, t in templates.items()}
    prefix = {"i": shapes[init["template"]],
              "m": shapes[config["measured_pods"]["template"]],
              "p": shapes[config["probe_pods"]["template"]]}

    def shape_of(key):
        return prefix[key.split("/", 1)[1].split("-", 1)[0]]

    readback = {f"{PROBE_NAMESPACE}/{n}": node
                for n, node in probe_out["nodes"].items()}
    for k in acked_probe:
        readback.setdefault(k, None)
    if probe_out.get("readback_error"):
        diag["readback_error"] = probe_out["readback_error"]
    t_ref = time.monotonic()
    counts = check(informer.log, config, dep.names, shape_of, acked, readback,
                   cell.root)
    diag["reference_s"] = time.monotonic() - t_ref
    lims = limits(config, cell.root)
    checks = {c: {"value": v, "limit": lims[c]} for c, v in counts.items()}

    # -- the window's readings --------------------------------------------------
    bound_at = informer.bound_at
    lo, hi = t_open, t_close
    binds_in_window = sum(1 for t in bound_at.values() if lo <= t < hi)
    bind_ms, gen_late_ms = [], []
    for key, due in gen.due.items():
        if lo <= due < hi:
            bind_ms.append((bound_at.get(key, t_drained) - due) * 1000)
    for due, late in gen.late:
        if lo <= due < hi:
            gen_late_ms.append(late * 1000)
    api_ms, api_failed, api_due = [], 0, 0
    sent = {int(r[4].rsplit("-", 1)[1]): r for r in probe_out["results"]}
    t0p, gap = probe_out["t0"], probe_out["gap"]
    i = max(0, int((lo - t0p) / gap) - 1)
    while t0p + i * gap < hi:
        due = t0p + i * gap
        if due >= lo:
            api_due += 1
            r = sent.get(i)
            if r is None:  # due in the window and never sent
                api_failed += 1
                api_ms.append((probe.t_stop - due) * 1000)
            else:
                api_ms.append((r[2] - due) * 1000)
                api_failed += r[3] != 201
        i += 1
    stages = {k: st_close.get(k, 0.0) - st_open.get(k, 0.0) for k in st_close}
    win = Window(window_s=hi - lo, setup_s=lo - T_START,
                 binds_in_window=binds_in_window, bind_ms=bind_ms,
                 api_ms=api_ms, gen_late_ms=gen_late_ms, stages_ms=stages,
                 compiles_in_window=c_close - c_open,
                 relists=rl_close - rl_open)
    breakdown = None
    dev = dict(device, memory_peak_bytes=mem_peak)
    if trace:
        from benchmark import devtrace

        if device["platform"] == "tpu":
            devtrace.peaks_for(device["kind"])  # a kind not in the table fails
        win.batches = poller.in_window(lo, hi)
        diag["batch_records_missed"] = poller.missed
        planes = devtrace.load_xplane(devtrace.find_xplane(trace_dir))
        diag["trace_structure"] = devtrace.structure(planes)
        try:
            red = devtrace.reduce(planes)
        except ValueError as e:  # no device plane: a CPU rehearsal
            diag["trace_error"] = str(e)
            red = None
        if red is not None:
            win.trace = red
            spans = []
            for r in win.batches:
                s = r["end"] - r["total_ms"] / 1000 - t_open
                for name, ms in r["stages"].items():
                    spans.append([s, s + ms / 1000, name])
                    s += ms / 1000
            progs = sorted(red["programs"].items(), key=lambda kv: -kv[1])
            breakdown = {"device_ops": [[k, v] for k, v in progs[:10]],
                         "idle_gaps": devtrace.name_gaps(red["gaps"], spans)}
            dev.update(busy_s=red["busy_s"], window_s=red["window_s"])
        shutil.rmtree(trace_dir, ignore_errors=True)
    metrics = read_metrics(cell.per_layer if trace else cell.end_to_end, win)

    unsched = len({k for op, k, _n, _g in informer.log if op == "U"})
    attempted = sum(1 for d in gen.due.values() if lo <= d < hi) + api_due
    failed = (gen.errors + api_failed + counts["unbound"] + counts["missing"]
              + unsched)
    correct = (all(v["value"] <= v["limit"] for v in checks.values())
               and not gen.failed_error)
    diag.update(window_s=win.window_s, setup_s=win.setup_s,
                binds_in_window=binds_in_window,
                bind_ms=_percentiles(bind_ms), api_ms=_percentiles(api_ms),
                gen_late_ms=_percentiles(gen_late_ms),
                compiles_in_window=win.compiles_in_window,
                compiles_total=counter.total(), relists=win.relists,
                unschedulable_pods=unsched, generator_error=gen.failed_error,
                probe_stuck_threads=probe_out.get("stuck_threads"),
                stages_ms=stages)
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": dev}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    result["diag"] = diag
    return result


def _generator_ok(gen) -> None:
    if gen.failed_error:
        raise RuntimeError(f"load generator failed: {gen.failed_error}")


def parse_args(argv=None, extra=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    if extra is not None:
        extra(ap)
    return ap.parse_args(argv)


def main(argv=None, fault: Optional[str] = None,
         traffic_overrides: Optional[dict] = None) -> int:
    """The benchmark's command. `fault` and `traffic_overrides` are for the
    tools beside it (`variant.py`); the benchmark's own runs set neither."""
    args = parse_args(argv) if not isinstance(argv, argparse.Namespace) else argv
    _place_compile_cache()
    sys.path.insert(0, ROOT)
    from benchmark.catalog import load_cell

    cell = load_cell(args.workload)
    cell.traffic.update(traffic_overrides or {})
    from kubernetes_tpu.device import require_tpu, use_compile_cache

    cache = use_compile_cache()
    try:
        device = require_tpu()
    except RuntimeError as e:  # NoTPUError included
        say(str(e))
        return 2
    rehearsal = device["platform"] != "tpu"
    if not rehearsal and device["count"] < cell.chips:
        say(f"cell {cell.name} needs {cell.chips} chips; JAX found "
            f"{device['count']} {device['kind']}")
        return 2
    cut = REHEARSAL_CUT if rehearsal else 1
    say(f"cell={cell.name} config={cell.config_name} traffic={cell.traffic_name} "
        f"seed={args.seed} seconds={args.seconds} trace={args.trace} "
        f"platform={device['platform']} kind={device['kind']} "
        f"count={device['count']} compile_cache={cache}"
        + (f" fault={fault}" if fault else "")
        + (f" overrides={traffic_overrides}" if traffic_overrides else "")
        + (f" (CPU rehearsal, sizes / {cut})" if rehearsal else ""))
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace), cut,
                      device, fault=fault)
    diag = result.pop("diag")
    say("diag " + json.dumps(diag, default=str))
    if rehearsal:
        say("rehearsal (no result without a TPU): "
            + json.dumps({k: v for k, v in result.items() if k != "device"}))
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']} limit {c['limit']}", file=sys.stderr)
    sys.stderr.flush()
    if rehearsal:
        return 3
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
