"""Process-wide garbage-collection pause counter: one `gc.callbacks` hook
that times every collection, whichever thread runs it.

Readers take a snapshot() and later ask since(snapshot) for the pause time
and the collections per generation in between: the flight recorder per
batch (a batch's `gc_ms`), the resource sampler per sample. A reader that
wants the longest single pause since its own baseline takes a max_cell().
A full collection (generation 2) is also a TraceMe span `gc.full`, so a
device-idle gap that a full collection caused shows as one in a
jax.profiler trace.

The hook costs two perf_counter reads and a few additions per collection;
install() is idempotent and the hook stays for the life of the process.
"""

from __future__ import annotations

import gc
import sys
import threading
import time
import weakref
from typing import List, Tuple

FULL_GC_SPAN = "gc.full"


class MaxCell:
    """The longest pause seen since the cell was made or reset."""

    __slots__ = ("value", "__weakref__")

    def __init__(self):
        self.value = 0.0


class GCPauseCounter:
    def __init__(self):
        self.pause_s = 0.0
        self.collections = [0, 0, 0]
        self._t0 = 0.0
        self._span = None
        # weak refs to the readers' max cells; replaced, never mutated in
        # place, so the callback iterates a list no thread changes under it
        self._cells: List[weakref.ref] = []
        self._lock = threading.Lock()
        self._installed = False

    def _callback(self, phase: str, info: dict) -> None:
        # collections never overlap (the interpreter runs one at a time),
        # so one start stamp serves every thread
        if phase == "start":
            self._t0 = time.perf_counter()
            # never imports JAX from inside a collection: the span needs
            # the profiler module loaded already (any span has loaded it)
            profiler = sys.modules.get("jax.profiler")
            if info.get("generation") == 2 and profiler is not None:
                self._span = profiler.TraceAnnotation(FULL_GC_SPAN)
                self._span.__enter__()
        elif phase == "stop" and self._t0:
            dt = time.perf_counter() - self._t0
            self._t0 = 0.0
            if self._span is not None:
                self._span.__exit__(None, None, None)
                self._span = None
            self.pause_s += dt
            gen = info.get("generation", 0)
            self.collections[gen if 0 <= gen <= 2 else 2] += 1
            for ref in self._cells:
                cell = ref()
                if cell is not None and dt > cell.value:
                    cell.value = dt

    def install(self) -> None:
        with self._lock:
            if not self._installed:
                gc.callbacks.append(self._callback)
                self._installed = True

    def snapshot(self) -> Tuple[float, int, int, int]:
        c = self.collections
        return (self.pause_s, c[0], c[1], c[2])

    def since(self, snap: Tuple[float, int, int, int]
              ) -> Tuple[float, List[int]]:
        """(pause seconds, [collections of generation 0, 1, 2]) since snap."""
        c = self.collections
        return (self.pause_s - snap[0],
                [c[0] - snap[1], c[1] - snap[2], c[2] - snap[3]])

    def max_cell(self) -> MaxCell:
        cell = MaxCell()
        with self._lock:
            self._cells = ([r for r in self._cells if r() is not None]
                           + [weakref.ref(cell)])
        return cell


COUNTER = GCPauseCounter()
