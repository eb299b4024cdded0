"""The one load generator. A traffic mix is a data file under
`benchmark/traffic/`; its `arrivals` picks how pods arrive:

  "burst"    closed-loop backlogs: a new burst of `burst_pods` is created
             (in `create_chunk` slices) whenever fewer than `refill_below`
             of the measured pods are pending. Once a burst is wholly bound,
             the burst `delete_lag` before it is deleted, as completed Jobs'
             pods are.
  "poisson"  open-loop arrivals at `rate_per_s`; each pod is deleted an
             exponential lifetime of mean `lifetime_mean_s` after its
             binding arrives, or never where `lifetime_mean_s` is null (as
             scheduler_perf's measured pods live on).

The seed fixes every name, arrival time and lifetime; the sizes are the
mix's own, so every seed does the same work in another order. Open-loop gaps
and lifetimes come in blocks of BLOCK drawn from one fixed stream, and the
seed only orders each block: every seed makes the same number of arrivals
in each block's span, and deletes as many pods after as long.
"""

from __future__ import annotations

import heapq
import threading
import time

import numpy as np

BLOCK = 1024
FIXED_STREAM = 0x5EED


class Shuffled:
    """Draws from blocks that every seed shares, each block in the order
    `rng` gives it; `draw(k, n)` is block k, n values long."""

    def __init__(self, rng, draw):
        self.rng = rng
        self.draw = draw
        self.block = 0
        self.left: list = []

    def take(self, n: int) -> list:
        out = []
        while len(out) < n:
            if not self.left:
                vals = self.draw(self.block, BLOCK)
                self.left = self.rng.permutation(vals).tolist()
                self.block += 1
            k = min(n - len(out), len(self.left))
            out.extend(self.left[-k:])
            del self.left[-k:]
        return out


class Generator:
    def __init__(self, traffic: dict, store, informer, factory, seed: int,
                 cut: int):
        self.t = traffic
        self.store = store
        self.informer = informer
        self.factory = factory
        self.cut = cut
        # arrivals are drawn on the generator's thread, lifetimes on the
        # informer's: one stream each keeps both fixed by the seed
        self.rng = np.random.default_rng([seed, 0])
        self.life_rng = np.random.default_rng([seed, 1])
        self.salt = f"{seed % (1 << 32):08x}"
        self.due: dict = {}  # key -> monotonic time the pod was due
        self.late: list = []  # (due, seconds late) per pod created
        self.acked: set = set()
        self.errors = 0
        self.failed_error: str = ""
        self._n = 0
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._thread = None
        kind = traffic["arrivals"]
        if kind == "burst":
            self._run = self._burst_loop
            self.burst_pods = max(1, traffic["burst_pods"] // cut)
            self.refill_below = max(1, traffic["refill_below"] // cut)
            self.bursts: list = []  # keys per burst
            self.burst_of: dict = {}
            self.burst_bound: list = []
            self.deleted_upto = 0
        elif kind == "poisson":
            self._run = self._poisson_loop
            self.rate = traffic["rate_per_s"] / cut
            life = traffic["lifetime_mean_s"]
            self._gaps = Shuffled(self.rng, lambda k, n: np.random.default_rng(
                [FIXED_STREAM, 0, k]).exponential(1.0 / self.rate, n))
            self._lives = None if life is None else Shuffled(
                self.life_rng, lambda k, n: np.random.default_rng(
                    [FIXED_STREAM, 1, k]).exponential(life, n))
            self._deletes: list = []  # heap of (due, key)
        else:
            raise ValueError(f"unknown arrivals {kind!r}")
        informer.on_bind(self._on_bind)

    # -- shared ---------------------------------------------------------------

    def _names(self, n: int) -> list:
        i0 = self._n
        self._n += n
        return [f"m-{self.salt}-{i}" for i in range(i0, i0 + n)]

    def _create(self, names: list, due: list) -> None:
        pods = self.factory.make(names, self.salt)
        t_send = time.monotonic()
        created, errors = self.store.create_many("pods", pods, consume=True)
        bad = {k for k, _msg in errors}
        with self._lock:
            for name, d in zip(names, due):
                key = f"default/{name}"
                if key in bad:
                    continue
                self.due[key] = d
                self.acked.add(key)
                self.late.append((d, t_send - d))
        self.errors += len(errors)

    def _delete(self, keys: list) -> None:
        for lo in range(0, len(keys), 5000):
            _n, errors = self.store.delete_pods(keys[lo:lo + 5000])
            self.errors += len(errors)

    def create_now(self, n: int) -> list:
        """Create n measured pods at once, outside the mix (warm-up); they
        take no part in the mix's bookkeeping. Returns their keys."""
        names = self._names(n)
        pods = self.factory.make(names, self.salt)
        _n, errors = self.store.create_many("pods", pods, consume=True)
        if errors:
            raise RuntimeError(f"warm-up pods refused: {errors[:3]}")
        return [f"default/{name}" for name in names]

    def delete_now(self, keys: list) -> None:
        self._delete(keys)

    def start(self) -> "Generator":
        self._thread = threading.Thread(target=self._guard, daemon=True,
                                        name="bench-generator")
        self._thread.start()
        return self

    def _guard(self) -> None:
        try:
            self._run()
        except Exception as e:  # reported in the run's result as a failure
            self.failed_error = f"{type(e).__name__}: {e}"
            raise

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=60)

    def _on_bind(self, keys: list, t: float) -> None:
        if self._run == self._burst_loop:
            with self._lock:
                for k in keys:
                    b = self.burst_of.get(k)
                    if b is not None:
                        self.burst_bound[b] += 1
        elif self._lives is not None:
            with self._lock:
                mine = [k for k in keys if k in self.due]
                if mine:
                    for k, s in zip(mine, self._lives.take(len(mine))):
                        heapq.heappush(self._deletes, (t + s, k))

    # -- burst ----------------------------------------------------------------

    def measured_pending(self) -> int:
        with self._lock:
            return (sum(len(b) for b in self.bursts) - sum(self.burst_bound))

    def bursts_started(self) -> int:
        return len(self.bursts)

    def _burst_loop(self) -> None:
        chunk = max(1, self.t["create_chunk"] // self.cut)
        lag = self.t["delete_lag"]
        while not self._stop.is_set():
            if self.measured_pending() < self.refill_below:
                names = self._names(self.burst_pods)
                b = len(self.bursts)
                with self._lock:
                    self.bursts.append([])
                    self.burst_bound.append(0)
                for lo in range(0, len(names), chunk):
                    if self._stop.is_set():
                        break
                    part = names[lo:lo + chunk]
                    now = time.monotonic()
                    with self._lock:
                        for name in part:
                            key = f"default/{name}"
                            self.burst_of[key] = b
                            self.bursts[b].append(key)
                    self._create(part, [now] * len(part))
            # delete every burst `lag` behind a wholly bound one
            with self._lock:
                done = [i for i, keys in enumerate(self.bursts)
                        if keys and self.burst_bound[i] >= len(keys)]
                upto = (max(done) - lag + 1) if done else 0
                todo = []
                while self.deleted_upto < upto:
                    todo.extend(self.bursts[self.deleted_upto])
                    self.deleted_upto += 1
            if todo:
                self._delete(todo)
            self._stop.wait(0.01)

    # -- poisson --------------------------------------------------------------

    def _poisson_loop(self) -> None:
        tick = self.t.get("tick_s", 0.005)
        nxt = time.monotonic()
        while not self._stop.is_set():
            now = time.monotonic()
            due = []
            while nxt <= now:
                due.append(nxt)
                nxt += self._gap()
            if due:
                self._create(self._names(len(due)), due)
            with self._lock:
                todo = []
                while self._deletes and self._deletes[0][0] <= now:
                    todo.append(heapq.heappop(self._deletes)[1])
            if todo:
                self._delete(todo)
            self._stop.wait(max(0.0, min(tick, nxt - time.monotonic())))

    def _gap(self) -> float:
        """Seconds to the next arrival."""
        return self._gaps.take(1)[0]
