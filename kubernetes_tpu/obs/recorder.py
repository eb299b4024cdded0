"""Generic ring/stage recorder machinery — the reusable half of the flight
recorder (factored out of scheduler/flightrec.py, ISSUE 9).

Design constraints inherited from PR 3/PR 7, and binding on every consumer:

  - taps are O(1) per BATCH/loop/chunk, never per pod/key/event in a
    pod-scale loop (schedlint HP001 enforces this in the hot files);
  - `time.perf_counter()` is the only usable tap clock in this container
    (`time.thread_time()` ticks at 10ms);
  - everything is bounded: the record ring evicts oldest, the per-stage
    histograms survive eviction at fixed memory;
  - measured self-time accrues to a sink (note_self_time) so the <2%
    instrumentation budget is bounded from a measurement, not by
    differencing two noisy runs.
"""

from __future__ import annotations

import math
import threading
import time
from collections import deque
from typing import Dict, List, Optional

from . import gcpause

# Windowed per-stage latency buckets (ISSUE 7): log-spaced 0.2ms..~42s so
# the p50/p99 estimates survive ring eviction at bounded memory. The ~1.55x
# bucket ratio bounds the interpolation error well inside the headroom any
# sane SLO ceiling carries; records still in the ring get EXACT nearest-rank
# percentiles instead (stage_table picks whichever source is lossless).
STAGE_P_BUCKETS = tuple(round(0.0002 * (1.55 ** i), 6) for i in range(28))


def nearest_rank(sorted_vals: List[float], q: float) -> float:
    """Exact nearest-rank percentile over a complete sample."""
    return sorted_vals[min(len(sorted_vals) - 1,
                           max(0, math.ceil(q * len(sorted_vals)) - 1))]


# -- spans on the profiler's clock ----------------------------------------------
#
# Every stage boundary is also a TraceMe span (jax.profiler.TraceAnnotation),
# so a jax.profiler trace shows the batch pipeline on the host line of the
# thread that ran it, on the same clock as the device's ops. With no trace
# active a span costs about half a microsecond; spans are per batch, per
# bind chunk or per solver group, never per pod.

SPAN_PREFIX = "sched."
BATCH_SPAN = "sched.batch"
# stages split into parts: the stage's time outside every named part is its
# `<stage>.host` part, so the parts sum to the stage
PARTED_STAGES = ("solve",)
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_TraceMe = None
_tls = threading.local()
_listener_lock = threading.Lock()
_listener_installed = False


def _traceme():
    global _TraceMe
    if _TraceMe is None:
        from jax.profiler import TraceAnnotation

        _TraceMe = TraceAnnotation
    return _TraceMe


def span(name: str):
    """A TraceMe span for `with span("sched.bind"): ...` — the stages that
    run outside a batch's StageClock (bind worker chunks, bind waits, queue
    admission)."""
    return _traceme()(name)


def _open_span(name: str):
    sp = _traceme()(name)
    sp.__enter__()
    return sp


class part:
    """`with part("solve.readback"): ...` attributes the enclosed time to a
    part of the stage open on this thread's StageClock, and restores the
    part that was open before on exit. A no-op where no StageClock is open
    on the thread or the open stage is not split (a solver called outside a
    batch's solve stage)."""

    __slots__ = ("name", "_clock", "_prev")

    def __init__(self, name: str):
        self.name = name
        self._clock = self._prev = None

    def __enter__(self):
        clock = getattr(_tls, "clock", None)
        if clock is not None:
            self._prev = clock._switch_part(self.name)
            if self._prev is not None:
                self._clock = clock
        return self

    def __exit__(self, *exc):
        if self._clock is not None:
            self._clock._switch_part(self._prev)
            self._clock = None
        return False


def _on_compile(event: str, duration: float, **_kw) -> None:
    """jax.monitoring listener: JAX calls it synchronously on the compiling
    thread, so the thread's open StageClock is the stage that paid."""
    if event != COMPILE_EVENT:
        return
    clock = getattr(_tls, "clock", None)
    if clock is not None:
        clock._note_compile(duration)


def install_compile_listener() -> None:
    """Register the one compile-duration listener of the process (idempotent;
    the flight recorder's enable switch decides whether it is called)."""
    global _listener_installed
    with _listener_lock:
        if _listener_installed:
            return
        from jax import monitoring

        monitoring.register_event_duration_secs_listener(_on_compile)
        _listener_installed = True


class StageClock:
    """Per-batch stage boundaries. enter(name) closes the open stage, whose
    time since the previous boundary is attributed to it, and opens `name`
    (None: an unattributed stretch); drop(name) closes the open stage
    without attributing it (work another accumulator claims, or nothing
    worth a row). Each stage is a TraceMe span `sched.<stage>` inside one
    `sched.batch` span, and `bounds` keeps each closed stage's (name,
    begin, end) on perf_counter.

    While a stage of PARTED_STAGES is open, part() splits it (`parts`,
    seconds, summing to the stage). XLA compiles that JAX reports on this
    thread accrue to the open stage and part (`compile_s`, `compiles`);
    garbage-collection pauses on any thread while the clock is open are
    `gc_s` and `gc_collections` (per generation) after finish()."""

    __slots__ = ("t0", "_last", "stages", "bounds", "_open", "_span",
                 "_batch_span", "_part", "_part_t", "_part_span", "parts",
                 "compile_s", "compiles", "gc_s", "gc_collections", "_gc0")

    def __init__(self, first: Optional[str] = None):
        self.t0 = self._last = time.perf_counter()
        self.stages: Dict[str, float] = {}
        self.bounds: List[tuple] = []
        self.parts: Dict[str, float] = {}
        self.compile_s: Dict[str, float] = {}
        self.compiles = 0
        self.gc_s = 0.0
        self.gc_collections = [0, 0, 0]
        self._gc0 = gcpause.COUNTER.snapshot()
        self._open: Optional[str] = None
        self._span = self._part = self._part_span = None
        self._part_t = 0.0
        self._batch_span = _open_span(BATCH_SPAN)
        _tls.clock = self
        if first is not None:
            self.enter(first)

    def _close(self, now: float, attribute: bool) -> None:
        name = self._open
        if name is None:
            return
        if self._part is not None:
            self._switch_part(None, now)
        if attribute:
            self.stages[name] = self.stages.get(name, 0.0) + now - self._last
            self.bounds.append((name, self._last, now))
        elif name in PARTED_STAGES:
            # the parts of a stage left out of the table go with it
            for k in [k for k in self.parts if k.startswith(name + ".")]:
                del self.parts[k]
        self._span.__exit__(None, None, None)
        self._span = self._open = None

    def _begin(self, name: Optional[str], now: float) -> None:
        self._last = now
        if name is None:
            return
        self._open = name
        self._span = _open_span(SPAN_PREFIX + name)
        if name in PARTED_STAGES:
            self._part = f"{name}.host"
            self._part_t = now
            self._part_span = _open_span(self._part)

    def enter(self, name: Optional[str]) -> None:
        now = time.perf_counter()
        self._close(now, attribute=True)
        self._begin(name, now)

    def drop(self, name: Optional[str] = None) -> None:
        now = time.perf_counter()
        self._close(now, attribute=False)
        self._begin(name, now)

    def _switch_part(self, name: Optional[str],
                     now: Optional[float] = None) -> Optional[str]:
        """Close the open part and open `name` (None: close only). Returns
        the part that was open, None when no split stage is open."""
        prev = self._part
        if prev is None:
            return None
        if now is None:
            now = time.perf_counter()
        self.parts[prev] = self.parts.get(prev, 0.0) + now - self._part_t
        self._part_span.__exit__(None, None, None)
        self._part_t = now
        self._part = name
        self._part_span = _open_span(name) if name is not None else None
        return prev

    def _note_compile(self, seconds: float) -> None:
        self.compiles += 1
        key = self._open or "batch"
        self.compile_s[key] = self.compile_s.get(key, 0.0) + seconds
        if self._part is not None:
            self.compile_s[self._part] = (self.compile_s.get(self._part, 0.0)
                                          + seconds)

    def finish(self) -> None:
        """Close the spans (an open stage is dropped: a stage that an
        exception cut short was never marked), read the GC counter, and
        stop receiving this thread's compiles. Idempotent."""
        if self._batch_span is None:
            return
        self._close(time.perf_counter(), attribute=False)
        self._batch_span.__exit__(None, None, None)
        self._batch_span = None
        if getattr(_tls, "clock", None) is self:
            _tls.clock = None
        self.gc_s, self.gc_collections = gcpause.COUNTER.since(self._gc0)

    def add(self, name: str, seconds: float) -> None:
        if seconds > 0:
            self.stages[name] = self.stages.get(name, 0.0) + seconds

    def sub(self, name: str, seconds: float) -> None:
        """Remove sub-stage time another bucket owns (floored at 0)."""
        if seconds > 0 and name in self.stages:
            self.stages[name] = max(0.0, self.stages[name] - seconds)

    def total(self) -> float:
        return time.perf_counter() - self.t0


class RingRecorder:
    """Bounded ring of per-loop/per-batch records plus per-stage aggregate
    state: totals and counts since clear() (survive ring eviction), windowed
    per-stage latency histograms feeding the p50/p99 columns, outside-bucket
    accumulators for work that runs between records, and measured self-time.

    Subclasses (FlightRecorder, ReconcileRecorder) own the record SCHEMA:
    they build their dict and hand it to _append_record with the stage map.
    """

    DEFAULT_CAPACITY = 64

    def __init__(self, capacity: int = DEFAULT_CAPACITY, enabled: bool = True):
        self.capacity = capacity
        self.enabled = enabled
        self._lock = threading.Lock()
        self._records: deque = deque(maxlen=capacity)
        self._seq = 0
        # aggregate per-stage seconds since clear(), across ALL records —
        # survives ring eviction so the stage table covers the full window
        self._stage_totals: Dict[str, float] = {}
        self._stage_batches: Dict[str, int] = {}
        # per-stage seconds accrued outside any record (add_outside)
        self._outside: Dict[str, float] = {}
        # per-stage latency histograms: one observation per record (or per
        # outside-bucket call), never evicted with the ring. Built lazily;
        # metrics.Histogram carries its own lock but every write here
        # happens under self._lock anyway.
        self._stage_hist: Dict[str, object] = {}
        # instrumentation self-time: seconds spent building records,
        # observing histograms, and in the timing taps. Divided by wall it
        # bounds the overhead budget from a measurement.
        self._self_s = 0.0
        # optional TimeSeriesRecorder (obs/timeseries.py, ISSUE 13): when
        # set, per-record stage maps and outside-bucket observations are
        # forwarded so the windowed view covers the overlapped stages
        # (bind, bind_wait, queue_add) the per-batch clock never sees
        self.timeseries = None

    # -- ingest ----------------------------------------------------------------

    def _hist_observe(self, stage: str, seconds: float) -> None:
        """One per-stage latency observation (caller holds self._lock)."""
        h = self._stage_hist.get(stage)
        if h is None:
            from ..server.metrics import Histogram

            h = self._stage_hist[stage] = Histogram(
                stage, buckets=STAGE_P_BUCKETS)
        h.observe(seconds)

    def add_outside(self, stage: str, seconds: float) -> None:
        if not self.enabled or seconds <= 0:
            return
        with self._lock:
            self._outside[stage] = self._outside.get(stage, 0.0) + seconds
            self._hist_observe(stage, seconds)
        ts = self.timeseries
        if ts is not None:
            ts.note_stage(stage, seconds)

    def outside_seconds(self, *stages: str) -> float:
        """Sum of the named outside buckets (the scheduler differences this
        around a pump to keep 'ingest' disjoint from its sub-stages)."""
        with self._lock:
            return sum(self._outside.get(s, 0.0) for s in stages)

    def note_self_time(self, seconds: float) -> None:
        with self._lock:
            self._self_s += seconds

    def _append_record(self, rec: Dict, stages: Dict[str, float]) -> Dict:
        """Ring append + per-stage aggregate updates for one record (caller
        holds self._lock; stage values in SECONDS). Stamps seq/ts AND the
        record's rendered `stages` map (milliseconds) — derived here so a
        subclass cannot desync the in-ring percentile source (read as ms by
        stage_table's exact path) from the histogram source (seconds)."""
        self._seq += 1
        rec["seq"] = self._seq
        rec["ts"] = time.time()
        rec["stages"] = {k: round(v * 1000, 3) for k, v in stages.items()}
        self._records.append(rec)
        for k, v in stages.items():
            self._stage_totals[k] = self._stage_totals.get(k, 0.0) + v
            self._stage_batches[k] = self._stage_batches.get(k, 0) + 1
            self._hist_observe(k, v)
        return rec

    # -- read side -------------------------------------------------------------

    def __len__(self) -> int:
        with self._lock:
            return len(self._records)

    def records(self) -> List[Dict]:
        with self._lock:
            return list(self._records)

    def last(self) -> Optional[Dict]:
        with self._lock:
            return self._records[-1] if self._records else None

    @property
    def self_seconds(self) -> float:
        with self._lock:
            return self._self_s

    def stage_table(self, order=(), overlapped=frozenset()) -> Dict[str, Dict]:
        """Aggregate per-stage view across every record since clear() plus
        the outside buckets: {stage: {total_ms, mean_ms, p50_ms, p99_ms,
        batches, overlapped}}.

        Percentile source (ISSUE 7): nearest-rank over the per-record ring
        while every observation is still in it (exact); once eviction or
        per-call outside observations outgrow the ring, the windowed stage
        histogram takes over (bucket-interpolated, error bounded by the
        STAGE_P_BUCKETS ratio)."""
        with self._lock:
            totals = dict(self._stage_totals)
            batches = dict(self._stage_batches)
            outside = dict(self._outside)
            hists = dict(self._stage_hist)
            ring_vals: Dict[str, List[float]] = {}
            for rec in self._records:
                for k, ms in rec["stages"].items():
                    ring_vals.setdefault(k, []).append(ms)

        def pcts(name):
            h = hists.get(name)
            n_obs = h._total if h is not None else 0
            vals = ring_vals.get(name)
            if vals and len(vals) == n_obs:
                vals = sorted(vals)
                return (round(nearest_rank(vals, 0.50), 3),
                        round(nearest_rank(vals, 0.99), 3))
            if h is None or n_obs == 0:
                return None, None
            return (round(h.quantile(0.50) * 1000, 3),
                    round(h.quantile(0.99) * 1000, 3))

        out: Dict[str, Dict] = {}
        for name in order:
            sec = totals.get(name, 0.0) + outside.get(name, 0.0)
            n = batches.get(name, 0)
            if sec == 0.0 and n == 0:
                continue
            p50, p99 = pcts(name)
            out[name] = {
                "total_ms": round(sec * 1000, 3),
                "mean_ms": round(sec * 1000 / n, 3) if n else None,
                "p50_ms": p50,
                "p99_ms": p99,
                "batches": n,
                "overlapped": name in overlapped,
            }
        # anything recorded under a name the caller's order doesn't know
        # keeps rendering (forward compatibility for new stages)
        for name in set(totals) | set(outside):
            if name not in out:
                sec = totals.get(name, 0.0) + outside.get(name, 0.0)
                p50, p99 = pcts(name)
                out[name] = {"total_ms": round(sec * 1000, 3),
                             "mean_ms": None,
                             "p50_ms": p50,
                             "p99_ms": p99,
                             "batches": batches.get(name, 0),
                             "overlapped": False}
        return out

    def clear(self) -> None:
        with self._lock:
            self._records.clear()
            self._stage_totals.clear()
            self._stage_batches.clear()
            self._outside.clear()
            self._stage_hist.clear()
            self._self_s = 0.0
            self._clear_extra()

    def _clear_extra(self) -> None:
        """Subclass hook: clear subclass state (caller holds self._lock)."""
