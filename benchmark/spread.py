#!/usr/bin/env python3
"""Run cells several times, one process after another (a chip holds one
process at a time), and report each metric's median and spread: the
distance between the first and third quartile (`statistics.quantiles(n=4)`)
as a share of the median, the number the bounds in BENCHMARK.json are set
from.

    python3 benchmark/spread.py --out DIR RUN [RUN ...]

Each RUN is `cell:seed[:seconds[:trace]]` (seconds default to BENCHMARK.json's
run_seconds, trace to 0). Every run's stdout and stderr go to DIR; one JSON
summary is printed last.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def spread(values: list):
    """(median, interquartile distance / median), or (median, None)."""
    med = statistics.median(values)
    if len(values) < 2 or not med:
        return med, None
    q1, _q2, q3 = statistics.quantiles(values, n=4)
    return med, (q3 - q1) / abs(med)


def run_one(cell, seed, seconds, trace, out_dir, extra=()):
    tag = f"{cell}.s{seed}.t{trace}"
    t0 = time.monotonic()
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", cell, "--seed", str(seed), "--seconds", str(seconds),
         "--trace", str(trace), *extra],
        cwd=ROOT, capture_output=True, text=True)
    wall = time.monotonic() - t0
    with open(os.path.join(out_dir, tag + ".out"), "w") as f:
        f.write(p.stdout)
    with open(os.path.join(out_dir, tag + ".err"), "w") as f:
        f.write(p.stderr)
    res = None
    lines = [ln for ln in p.stdout.splitlines() if ln.strip()]
    if p.returncode == 0 and lines:
        res = json.loads(lines[-1])
    return {"cell": cell, "seed": seed, "trace": trace, "rc": p.returncode,
            "wall_s": wall, "result": res,
            "stderr_tail": "" if res else p.stderr[-3000:]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", required=True)
    ap.add_argument("runs", nargs="+")
    args = ap.parse_args(argv)
    os.makedirs(args.out, exist_ok=True)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        default_s = json.load(f)["run_seconds"]
    runs = []
    for spec in args.runs:
        parts = spec.split(":")
        cell, seed = parts[0], int(parts[1])
        seconds = float(parts[2]) if len(parts) > 2 else default_s
        trace = int(parts[3]) if len(parts) > 3 else 0
        r = run_one(cell, seed, seconds, trace, args.out)
        runs.append(r)
        res = r["result"] or {}
        brief = {k: round(v["value"], 4) for k, v in (res.get("metrics") or {}).items()}
        print(f"run {cell} seed={seed} trace={trace} rc={r['rc']} "
              f"wall={r['wall_s']:.1f}s correct={res.get('correct')} "
              f"failed={res.get('failed')} {json.dumps(brief)}", flush=True)
        if r["stderr_tail"]:
            print(r["stderr_tail"], flush=True)
    summary: dict = {}
    for r in runs:
        res = r["result"]
        if not res or r["trace"]:
            continue
        for name, m in res["metrics"].items():
            summary.setdefault(r["cell"], {}).setdefault(name, []).append(m["value"])
    table = {cell: {name: dict(zip(("median", "spread"), spread(vals)), n=len(vals),
                               values=vals)
                    for name, vals in ms.items()}
             for cell, ms in summary.items()}
    print(json.dumps({"spread": table}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
