"""The device trace: the window recorded with JAX's profiler, reduced to
busy time, per-program device time and idle gaps.

The reduction works on plain tuples, so it is checked on a hand-made trace
(`tests/test_devtrace.py`) as well as on a recorded one:

  planes = [(plane_name, [(line_name, [(event_name, start_ns, dur_ns), ...]),
                          ...]), ...]

A device plane is one named "/device:TPU:<n>". On it, busy time is the
union of the events of the "XLA Ops" line (every line but "Steps" where a
backend writes no such line), and a program's time is the sum of its events
on the "XLA Modules" line. The host's annotation WINDOW, written around the
window by the benchmark's own thread, fixes it on the same clock.
"""

from __future__ import annotations

import glob
import json
import os
import re

WINDOW = "bench_trace_window"
_DEVICE = re.compile(r"^/device:TPU:\d+$")
PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peaks_for(kind: str) -> dict:
    """The published peaks of a device kind; a kind not in the table is an
    error, never a default."""
    with open(PEAKS_FILE) as f:
        table = json.load(f)["kinds"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in {PEAKS_FILE}")
    return table[kind]


def load_xplane(path: str) -> list:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    return [(plane.name, [(line.name, [(e.name, float(e.start_ns), float(e.duration_ns))
                                       for e in line.events])
                          for line in plane.lines])
            for plane in pd.planes]


def find_xplane(logdir: str) -> str:
    found = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return found[-1]


def union(intervals) -> list:
    """Sorted, merged [start, end) intervals."""
    out: list = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _clip(ivs, lo, hi) -> list:
    return [[max(s, lo), min(e, hi)] for s, e in ivs if e > lo and s < hi]


def program_name(event_name: str) -> str:
    """'jit_waterfill_group(1234)' -> 'waterfill_group'."""
    name = re.sub(r"\(\d+\)$", "", event_name)
    return name[4:] if name.startswith("jit_") else name


def reduce(planes: list) -> dict:
    """{window_s, busy_s, idle_share, programs {name: device seconds},
    gaps [[start_s, end_s] relative to the window], devices} averaged over
    the device planes. Raises when the trace has no window or no device."""
    win = None
    for _pname, lines in planes:
        for _lname, events in lines:
            for name, start, dur in events:
                if name == WINDOW:
                    win = (start, start + dur)
    if win is None:
        raise ValueError(f"trace has no {WINDOW!r} annotation")
    lo, hi = win
    devices = [(p, lines) for p, lines in planes if _DEVICE.match(p)]
    if not devices:
        raise ValueError("trace has no TPU device plane")
    busy_total = 0.0
    programs: dict = {}
    gaps_all = []
    for _p, lines in devices:
        by_line = {ln: evs for ln, evs in lines}
        op_lines = ([by_line["XLA Ops"]] if "XLA Ops" in by_line
                    else [evs for ln, evs in lines if ln != "Steps"])
        busy = _clip(union((s, s + d) for evs in op_lines for _n, s, d in evs),
                     lo, hi)
        busy_total += sum(e - s for s, e in busy)
        for name, s, d in by_line.get("XLA Modules", []):
            part = min(s + d, hi) - max(s, lo)
            if part > 0:
                k = program_name(name)
                programs[k] = programs.get(k, 0.0) + part
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        gaps_all.append([[edges[i], edges[i + 1]]
                         for i in range(0, len(edges), 2)
                         if edges[i + 1] > edges[i]])
    n = len(devices)
    window_s = (hi - lo) / 1e9
    busy_s = busy_total / n / 1e9
    gaps = sorted(gaps_all[0], key=lambda g: g[0] - g[1])
    return {
        "window_s": window_s,
        "busy_s": busy_s,
        "idle_share": 1.0 - busy_s / window_s if window_s > 0 else None,
        "programs": {k: v / n / 1e9 for k, v in programs.items()},
        "gaps": [[(s - lo) / 1e9, (e - lo) / 1e9] for s, e in gaps],
        "devices": n,
    }


def name_gaps(gaps: list, spans: list, limit: int = 10) -> list:
    """[[label, seconds]] for the longest gaps. spans are [start_s, end_s,
    label] relative to the window start (what the scheduler thread was
    doing); a gap takes the label that overlaps it most, or 'no batch'."""
    out = []
    for s, e in gaps[:limit]:
        best, lab = 0.0, "no batch"
        for a, b, label in spans:
            ov = min(b, e) - max(a, s)
            if ov > best:
                best, lab = ov, label
        out.append([lab, e - s])
    return out


def structure(planes: list) -> list:
    """A short description of a trace (planes, their lines and event counts)
    for reading one by hand."""
    return [[p, [[ln, len(evs)] for ln, evs in lines]] for p, lines in planes]
