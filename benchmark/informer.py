"""The benchmark's view of the pods, from the client's side: one watch on the
store, as an informer holds. It stamps each pod when its binding arrives,
and keeps the ordered log of what it saw for the reference check.

The log holds one tuple per observed change, in the store's order:
(op, key, node, group), where op is "A" (added), "B" (bound), "X" (moved from
one node to another), "D" (deleted) or "U" (marked unschedulable), and group
numbers the watch deliveries: the events of one batched write (a bind
batch, a bulk create or delete) share one group.
"""

from __future__ import annotations

import threading
import time


class Informer:
    def __init__(self, store):
        # unbounded buffer: the benchmark's own instrument must never be
        # evicted into a relist, which would leave holes in its log
        self._w = store.watch("pods", coalesce=True, maxsize=0)
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self.log: list = []
        self.node_of: dict = {}  # live pods: key -> node name or None
        self.bound_at: dict = {}  # key -> monotonic time its binding arrived
        self.unbound: set = set()  # live pods not yet bound
        self.seen: set = set()  # every key ever added
        self._bind_listeners: list = []
        self._group = 0
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="bench-informer")

    def start(self) -> "Informer":
        self._thread.start()
        return self

    def on_bind(self, fn) -> None:
        """fn(keys, t) is called on the informer thread for each delivery
        that bound pods."""
        self._bind_listeners.append(fn)

    def _loop(self) -> None:
        while not self._stop.is_set():
            first = self._w.get(timeout=0.05)
            if first is None:
                continue
            items = [first] + self._w.drain()
            t = time.monotonic()
            for item in items:
                self._handle(item, t)

    def _handle(self, item, t: float) -> None:
        evs = getattr(item, "events", None)
        if evs is None:
            evs = (item,)
        self._group += 1
        g = self._group
        bound_keys = []
        with self._lock:
            for ev in evs:
                obj = ev.obj
                key = f"{obj.metadata.namespace}/{obj.metadata.name}"
                node = obj.spec.node_name or None
                if ev.type == "ADDED":
                    self.seen.add(key)
                    self.node_of[key] = node
                    self.log.append(("A", key, None, g))
                    if node is None:
                        self.unbound.add(key)
                    else:
                        self._bound(key, node, g, t, bound_keys)
                elif ev.type == "DELETED":
                    self.node_of.pop(key, None)
                    self.unbound.discard(key)
                    self.log.append(("D", key, None, g))
                else:
                    had = self.node_of.get(key)
                    if node is None:
                        if had is None and _unschedulable(obj):
                            self.log.append(("U", key, None, g))
                    elif had is None:
                        self.node_of[key] = node
                        self._bound(key, node, g, t, bound_keys)
                    elif had != node:
                        self.node_of[key] = node
                        self.log.append(("X", key, node, g))
        if bound_keys:
            for fn in self._bind_listeners:
                fn(bound_keys, t)

    def _bound(self, key, node, g, t, bound_keys) -> None:
        self.unbound.discard(key)
        self.bound_at.setdefault(key, t)
        self.log.append(("B", key, node, g))
        bound_keys.append(key)

    def seen_all(self, keys: set) -> bool:
        with self._lock:
            return len(keys) <= len(self.seen) and keys <= self.seen

    def pending(self) -> int:
        with self._lock:
            return len(self.unbound)

    def stop(self) -> None:
        self._stop.set()
        self._thread.join(timeout=30)
        self._w.stop()


def _unschedulable(pod) -> bool:
    for c in pod.status.conditions:
        if c.type == "PodScheduled" and c.status == "False" \
                and c.reason == "Unschedulable":
            return True
    return False
