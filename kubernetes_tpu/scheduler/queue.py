"""3-tier scheduling queue: activeQ + backoffQ + unschedulablePods.

reference: pkg/scheduler/backend/queue/scheduling_queue.go — PriorityQueue :154,
AddUnschedulableIfNotPresent :741, flushBackoffQCompleted :790, Pop :829 (blocks),
MoveAllToActiveOrBackoffQueue :1028; backoff_queue.go:64 (initial 1s, max 10s);
flush cadence: backoff every 1s, unschedulable every 30s (:350).

QueueingHints are simplified to event-kind gating: on a cluster event, all
unschedulable pods move to backoff/active (the pre-hints behavior); per-plugin
hint functions can be layered on later without changing this interface.

Gang gating (scheduler/gang.py): with gang hooks installed, members of a
PodGroup are held in a STAGING area — a fourth tier next to active/backoff/
unschedulable — until the group reaches quorum (staged + already-placed >=
min_member), then the whole gang is admitted contiguously (one timestamp,
consecutive seqs) so a single solver batch sees it together. A failed gang
re-enters through add_gang_backoff as a unit: one shared expiry, so the
members re-stage and re-admit together.
"""

from __future__ import annotations

import heapq
import itertools
import threading
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

from ..api import Pod
from ..obs.recorder import span as _span
from ..utils import Clock

DEFAULT_POD_INITIAL_BACKOFF = 1.0  # seconds (scheduler.go:252)
DEFAULT_POD_MAX_BACKOFF = 10.0  # seconds (scheduler.go:253)
FLUSH_UNSCHEDULABLE_TIMEOUT = 30.0  # scheduling_queue.go:91


class _LessItem:
    """Adapts a QueueSort plugin's less(a, b) into a heap sort key."""

    __slots__ = ("qp", "less")

    def __init__(self, qp, less):
        self.qp = qp
        self.less = less

    def __lt__(self, other):
        return self.less(self.qp, other.qp)

    def __eq__(self, other):
        return not self.less(self.qp, other.qp) and not self.less(other.qp, self.qp)


@dataclass
class QueuedPodInfo:
    """reference: framework types.go:362 QueuedPodInfo."""

    pod: Pod
    timestamp: float = 0.0
    attempts: int = 0
    unschedulable_plugins: Tuple[str, ...] = ()
    # first-admission time, NEVER reset by requeues (timestamp is): the
    # submit->bound latency the pod tracer observes spans every retry. Set
    # from the admission batch's shared clock read — no per-pod clock calls.
    submit_ts: float = 0.0
    # the pod's live PodSpan when it is in the tracer's sample, linked at
    # batch-pop time (scheduler/podtrace.py): the bind worker's per-chunk
    # pass then pays ONE attribute read per pod instead of a key build +
    # set lookup. None for the unsampled ~100%.
    trace_span: object = field(default=None, repr=False, compare=False)

    def __post_init__(self):
        if not self.submit_ts:
            self.submit_ts = self.timestamp

    @property
    def key(self) -> str:
        return self.pod.key


class SchedulingQueue:
    def __init__(self, clock: Optional[Clock] = None,
                 initial_backoff: float = DEFAULT_POD_INITIAL_BACKOFF,
                 max_backoff: float = DEFAULT_POD_MAX_BACKOFF,
                 less=None, pre_enqueue=None):
        self._clock = clock or Clock()
        self._initial_backoff = initial_backoff
        self._max_backoff = max_backoff
        self._less = less  # (QueuedPodInfo, QueuedPodInfo) -> bool; default priority desc
        # pre_enqueue(pod) -> bool: re-checked on every promotion into activeQ
        # (the reference re-runs PreEnqueue in moveToActiveQ — a gated pod must
        # never reach the active queue via an unrelated cluster event)
        self._pre_enqueue = pre_enqueue
        self._lock = threading.Condition()
        self._seq = itertools.count()
        # activeQ: heap of (sort_key, seq, QueuedPodInfo)
        self._active: List[Tuple] = []
        self._backoff: List[Tuple[float, int, QueuedPodInfo]] = []
        # key -> entry count in _backoff (duplicates possible transiently):
        # keeps contains() O(1) — the partitioned dispatch layer (ISSUE 12)
        # probes membership once per foreign bound-pod event, which must not
        # cost an O(backoff) scan under chaos backlogs
        self._backoff_keys: Dict[str, int] = {}
        self._unschedulable: Dict[str, QueuedPodInfo] = {}
        self._in_active: Dict[str, QueuedPodInfo] = {}
        self._closed = False
        # gang staging (scheduler/gang.py): group key -> {pod key: qp}. Hooks
        # are installed by the batch scheduler via set_gang_hooks; without
        # them (or while gang_active() is False) every gang path is skipped.
        self._gang_of = None  # (pod) -> Optional[str]
        self._gang_ready = None  # (group, staged_count) -> bool
        self._gang_active = None  # () -> bool
        self._gang_staging: Dict[str, Dict[str, QueuedPodInfo]] = {}
        # parked-gang retry tier (ISSUE 14): gangs whose victim cover fired
        # wait HERE — off the active/backoff heaps — until the preemptor
        # releases them (victims observed deleted, or its deadline sweep).
        # A parked member is still pending for the conservation invariant
        # (tracked_keys / telemetry cover this tier).
        self._gang_parked: Dict[str, Dict[str, QueuedPodInfo]] = {}
        # stage-timing sink (a FlightRecorder, installed by BatchScheduler):
        # bulk-admission wall time accrues to its "queue_add" bucket so the
        # batch pipeline's stage table can attribute ingest sub-stages
        self.stat_sink = None
        # lifecycle-trace sink (a PodTracer, installed by BatchScheduler):
        # notified once per admission batch — AFTER the queue lock releases —
        # with the freshly-admitted QueuedPodInfos for reservoir sampling
        self.trace_sink = None

    def set_gang_hooks(self, gang_of, gang_ready, gang_active) -> None:
        """Install gang gating: gang_of(pod) names the pod's group (None for
        non-members), gang_ready(group, staged) decides quorum, gang_active()
        is the batch-level fast-out (False until any PodGroup exists, so
        gang-free clusters pay one call per admission batch)."""
        with self._lock:
            self._gang_of = gang_of
            self._gang_ready = gang_ready
            self._gang_active = gang_active

    # -- ordering --------------------------------------------------------------

    def _sort_key(self, qp: QueuedPodInfo):
        # default QueueSort: priority desc, then timestamp asc (priority_sort.go).
        # A custom QueueSort plugin's less() overrides via _LessItem comparison.
        if self._less is not None:
            return _LessItem(qp, self._less)
        return (-qp.pod.spec.priority, qp.timestamp)

    # -- add paths -------------------------------------------------------------

    def add(self, pod: Pod) -> None:
        with self._lock:
            qp = QueuedPodInfo(pod=pod, timestamp=self._clock.now())
            self._push_active(qp)
            self._lock.notify()
        ts = self.trace_sink
        if ts is not None:
            ts.admitted((qp,))

    def add_batch(self, pods: List[Pod], pre_gated: bool = False) -> None:
        """Bulk admission for a coalesced watch chunk: ONE lock acquisition
        and one O(n+m) heapify instead of n heappushes (the per-pod adds were
        a top stage of the 100k-backlog ingest). Pop order is identical to n
        add() calls — the heap key (sort_key, seq) is a total order, so heap
        layout doesn't matter. PreEnqueue gating still applies per pod via
        _pre_enqueue (gated pods park in unschedulable, as add() does);
        pre_gated=True skips that re-check when the caller just ran
        PreEnqueue on every pod itself (the coalesced ingest path — add()
        semantics double-run it, microseconds apart, with the same answer)."""
        if not pods:
            return
        sink = self.stat_sink
        if sink is not None and sink.enabled:
            import time as _time

            admitted = []
            t0 = _time.perf_counter()
            try:
                with _span("sched.queue_add"):
                    admitted = self._add_batch_locked(pods, pre_gated)
            finally:
                t1 = _time.perf_counter()
                sink.add_outside("queue_add", t1 - t0)
                from ..server import metrics as m

                m.batch_stage_duration.observe(t1 - t0, "queue_add")
                sink.note_self_time(_time.perf_counter() - t1)
        else:
            with _span("sched.queue_add"):
                admitted = self._add_batch_locked(pods, pre_gated)
        ts = self.trace_sink
        if ts is not None and admitted:
            # reservoir sampling at admission (scheduler/podtrace.py), with
            # the queue lock already released; the tracer accounts its own
            # self-time against the recorder budget
            ts.admitted(admitted)

    def _add_batch_locked(self, pods: List[Pod],
                          pre_gated: bool) -> List[QueuedPodInfo]:
        with self._lock:
            now = self._clock.now()
            gang_of = (self._gang_of if self._gang_active is not None
                       and self._gang_active() else None)
            entries = []
            for pod in pods:
                qp = QueuedPodInfo(pod=pod, timestamp=now)
                key = qp.key
                self._unschedulable.pop(key, None)
                if key in self._in_active:
                    continue
                if (not pre_gated and self._pre_enqueue is not None
                        and not self._pre_enqueue(pod)):
                    self._unschedulable[key] = qp  # still gated: stay parked
                    continue
                if gang_of is not None:
                    group = gang_of(pod)
                    if group is not None:
                        for m in self._gang_stage(group, qp):
                            self._in_active[m.key] = m
                            entries.append((self._sort_key(m),
                                            next(self._seq), m))
                        continue
                self._in_active[key] = qp
                entries.append((self._sort_key(qp), next(self._seq), qp))
            if not entries:
                return []
            if len(entries) >= len(self._active):
                self._active.extend(entries)
                heapq.heapify(self._active)
            else:
                for e in entries:
                    heapq.heappush(self._active, e)
            self._lock.notify_all()
            return [e[2] for e in entries]

    def _push_active(self, qp: QueuedPodInfo) -> None:
        self._unschedulable.pop(qp.key, None)
        if qp.key in self._in_active:
            return
        if self._pre_enqueue is not None and not self._pre_enqueue(qp.pod):
            self._unschedulable[qp.key] = qp  # still gated: stay parked
            return
        if self._gang_active is not None and self._gang_active():
            group = self._gang_of(qp.pod)
            if group is not None:
                for m in self._gang_stage(group, qp):
                    self._heap_push(m)
                return
        self._heap_push(qp)

    def _heap_push(self, qp: QueuedPodInfo) -> None:
        self._in_active[qp.key] = qp
        heapq.heappush(self._active, (self._sort_key(qp), next(self._seq), qp))

    def _backoff_push(self, ready: float, qp: QueuedPodInfo) -> None:
        heapq.heappush(self._backoff, (ready, next(self._seq), qp))
        self._backoff_keys[qp.key] = self._backoff_keys.get(qp.key, 0) + 1

    def _backoff_key_drop(self, key: str) -> None:
        n = self._backoff_keys.get(key, 0) - 1
        if n <= 0:
            self._backoff_keys.pop(key, None)
        else:
            self._backoff_keys[key] = n

    # -- gang staging (scheduler/gang.py) --------------------------------------

    def _gang_stage(self, group: str, qp: QueuedPodInfo) -> List[QueuedPodInfo]:
        """Park one gang member in staging; returns the members to admit NOW
        ([] while the group is below quorum). Admitted members share one
        timestamp, so with equal priorities the (sort_key, seq) total order
        pops them contiguously — one solver batch sees the whole gang."""
        self._gang_staging.setdefault(group, {})[qp.key] = qp
        return self._gang_collect(group, requester=qp)

    def _gang_collect(self, group: str,
                      requester: Optional[QueuedPodInfo] = None
                      ) -> List[QueuedPodInfo]:
        staged = self._gang_staging.get(group)
        if (not staged or self._gang_ready is None
                or not self._gang_ready(group, len(staged))):
            return []
        if self._pre_enqueue is not None:
            # gates may have closed on members staged earlier; a newly-gated
            # member breaks quorum and the gang keeps waiting (the reference
            # re-runs PreEnqueue on every promotion into activeQ)
            for key, m in list(staged.items()):
                if m is requester:
                    continue
                if not self._pre_enqueue(m.pod):
                    staged.pop(key)
                    self._unschedulable[key] = m
            if not staged or not self._gang_ready(group, len(staged)):
                if not staged:
                    self._gang_staging.pop(group, None)
                return []
        self._gang_staging.pop(group, None)
        now = self._clock.now()
        members = list(staged.values())
        for m in members:
            m.timestamp = now
        return members

    def reconsider_gangs(self) -> None:
        """Re-evaluate every staged group's quorum — called on PodGroup
        events (a created/raised-quorum PodGroup can unblock members that
        arrived before it)."""
        with self._lock:
            moved = False
            for group in list(self._gang_staging):
                for m in self._gang_collect(group):
                    self._heap_push(m)
                    moved = True
            if moved:
                self._lock.notify_all()

    def park_gang(self, group: str, members: List[QueuedPodInfo]) -> None:
        """Park a preempting gang (ISSUE 14): its victim cover was selected
        and the deletions are in flight — the members wait OUT of every
        retry loop until release_parked_gang moves them back (the preemptor
        calls it when the last victim's DELETED event lands, or from its
        deadline sweep when deletions stall). One gang, one parking slot:
        re-parking replaces (members are the same objects)."""
        if not members:
            return
        with self._lock:
            slot = self._gang_parked.setdefault(group, {})
            for m in members:
                slot[m.key] = m

    def release_parked_gang(self, group: str) -> int:
        """Move a parked gang back through the normal admission path: the
        members re-stage under their group (gang hooks installed), reach
        quorum together, and admit contiguously — the same all-at-once
        re-entry add_gang_backoff gives a vetoed gang, without the backoff
        wait. Returns the number of members released."""
        with self._lock:
            slot = self._gang_parked.pop(group, None)
            if not slot:
                return 0
            now = self._clock.now()
            for m in slot.values():
                m.timestamp = now
                self._push_active(m)
            self._lock.notify_all()
            return len(slot)

    def parked_gang_groups(self) -> List[str]:
        with self._lock:
            return list(self._gang_parked)

    def add_gang_backoff(self, members: List[QueuedPodInfo]) -> None:
        """Requeue a failed gang as a UNIT: every member enters the backoff
        queue under ONE shared expiry (the slowest member's backoff), so the
        gang re-stages and re-admits together when it fires — never member by
        member through the unschedulable map."""
        if not members:
            return
        with self._lock:
            now = self._clock.now()
            dur = max(self._backoff_duration(m.attempts) for m in members)
            ready = now + dur
            for m in members:
                m.timestamp = now
                self._backoff_push(ready, m)

    def add_backoff(self, qps: List[QueuedPodInfo]) -> None:
        """Transient-error requeue (ISSUE 6 failure domains): straight into
        the backoff tier with a per-pod expiry from its attempt count —
        unlike add_unschedulable, no cluster event is needed before the
        retry, which is right for infrastructure faults (a solver crash, a
        store hiccup) where the POD is fine and the retry just needs
        breathing room."""
        if not qps:
            return
        with self._lock:
            now = self._clock.now()
            for qp in qps:
                qp.timestamp = now
                self._backoff_push(
                    now + self._backoff_duration(qp.attempts), qp)

    def add_unschedulable(self, qp: QueuedPodInfo) -> None:
        """AddUnschedulableIfNotPresent (:741): failed pods wait for an event
        (unschedulable map) — backoff applies when they are moved back."""
        with self._lock:
            qp.timestamp = self._clock.now()
            self._unschedulable[qp.key] = qp

    def _backoff_duration(self, attempts: int) -> float:
        d = self._initial_backoff * (2 ** max(attempts - 1, 0))
        return min(d, self._max_backoff)

    def move_all_to_active_or_backoff(self) -> None:
        """MoveAllToActiveOrBackoffQueue (:1028) on a cluster event."""
        self.move_pods_for_event(lambda qp: True)

    def move_pods_for_event(self, should_move) -> None:
        """movePodsToActiveOrBackoffQueue (:1028) gated by QueueingHints:
        should_move(qp) -> bool decides, per unschedulable pod, whether this
        cluster event could make it schedulable (the scheduler derives it from
        the rejecting plugins' hint functions — scheduling_queue.go:263
        QueueingHintMap + podMatchesEvent). Pods that stay are still swept by
        flush_unschedulable_left_over (the reference's safety net)."""
        with self._lock:
            moved = False
            for key, qp in list(self._unschedulable.items()):
                if not should_move(qp):
                    continue
                self._unschedulable.pop(key)
                remaining = self._backoff_remaining(qp)
                if remaining > 0:
                    self._backoff_push(self._clock.now() + remaining, qp)
                else:
                    self._push_active(qp)
                moved = True
            if moved:
                self._lock.notify_all()

    def _backoff_remaining(self, qp: QueuedPodInfo) -> float:
        if qp.attempts == 0:
            return 0.0
        expiry = qp.timestamp + self._backoff_duration(qp.attempts)
        return max(0.0, expiry - self._clock.now())

    # -- flush loops (queue.Run :350) ------------------------------------------

    def flush_backoff_completed(self) -> None:
        with self._lock:
            now = self._clock.now()
            moved = False
            while self._backoff and self._backoff[0][0] <= now:
                _, _, qp = heapq.heappop(self._backoff)
                self._backoff_key_drop(qp.key)
                self._push_active(qp)
                moved = True
            if moved:
                self._lock.notify_all()

    def flush_unschedulable_left_over(self) -> None:
        """Pods stuck unschedulable longer than 30s get requeued (:350)."""
        with self._lock:
            now = self._clock.now()
            moved = False
            for key, qp in list(self._unschedulable.items()):
                if now - qp.timestamp > FLUSH_UNSCHEDULABLE_TIMEOUT:
                    self._unschedulable.pop(key)
                    self._push_active(qp)
                    moved = True
            # gang staging safety net: members of a group with NO PodGroup
            # (quorum hook returns None — deleted, or never created) must
            # not be stranded; after the same 30s window they release as
            # ORDINARY pods. Groups with a live PodGroup below quorum keep
            # waiting — releasing those would break all-or-nothing.
            released = 0
            for group in list(self._gang_staging):
                staged = self._gang_staging[group]
                if (self._gang_ready is None
                        or self._gang_ready(group, len(staged)) is not None):
                    continue
                for key, qp in list(staged.items()):
                    if now - qp.timestamp > FLUSH_UNSCHEDULABLE_TIMEOUT:
                        staged.pop(key)
                        self._heap_push(qp)
                        moved = True
                        released += 1
                if not staged:
                    self._gang_staging.pop(group, None)
            if moved:
                self._lock.notify_all()
        if released:
            from ..server import metrics as m

            m.gang_orphan_released_total.inc(released)

    # -- pop -------------------------------------------------------------------

    def pop(self, timeout: Optional[float] = None) -> Optional[QueuedPodInfo]:
        with self._lock:
            while not self._active and not self._closed:
                if not self._lock.wait(timeout=timeout):
                    return None
            if self._closed and not self._active:
                return None
            _, _, qp = heapq.heappop(self._active)
            self._in_active.pop(qp.key, None)
            qp.attempts += 1
            return qp

    def pop_batch(self, max_n: int, timeout: Optional[float] = None) -> List[QueuedPodInfo]:
        """Drain up to max_n pods for a batched TPU solve (the batching analog of
        the one-pod Pop the serial loop uses)."""
        out: List[QueuedPodInfo] = []
        first = self.pop(timeout=timeout)
        if first is None:
            return out
        out.append(first)
        with self._lock:
            if len(self._active) + 1 <= max_n:
                # draining everything: one Timsort beats n heappops and pops
                # in the identical (sort_key, seq) total order
                drained = sorted(self._active)
                self._active = []
                for _, _, qp in drained:
                    self._in_active.pop(qp.key, None)
                    qp.attempts += 1
                    out.append(qp)
                return out
            while self._active and len(out) < max_n:
                _, _, qp = heapq.heappop(self._active)
                self._in_active.pop(qp.key, None)
                qp.attempts += 1
                out.append(qp)
        return out

    # -- removal / updates -----------------------------------------------------

    def update(self, pod: Pod) -> bool:
        """Pod MODIFIED while queued. Only a spec change can affect schedulability
        (reference: eventhandlers.go updatePodInSchedulingQueue + util.PodChanged);
        status-only patches (e.g. our own PodScheduled condition write) must NOT
        requeue, or every failure would loop pod->patch->event->retry forever.
        Returns True if the pod was known to the queue."""
        with self._lock:
            key = pod.key
            tracked = None
            staged_in = None
            if key in self._in_active:
                tracked = self._in_active[key]
            else:
                for _, _, qp in self._backoff:
                    if qp.key == key:
                        tracked = qp
                        break
                if tracked is None:
                    tracked = self._unschedulable.get(key)
                if tracked is None:
                    for group, staged in self._gang_staging.items():
                        if key in staged:
                            tracked = staged[key]
                            staged_in = group
                            break
                if tracked is None:
                    # parked for a victim cover: keep the object fresh but
                    # stay parked — the preemptor's release/deadline owns
                    # when this gang re-enters the admission path
                    for parked in self._gang_parked.values():
                        if key in parked:
                            tracked = parked[key]
                            break
            if tracked is None:
                return False
            # status-only writes don't requeue (our own PodScheduled
            # condition would loop) — EXCEPT resourceClaimStatuses: the
            # resourceclaim controller's stamp resolves template claim
            # references, which gates schedulability exactly like spec
            spec_changed = (tracked.pod.spec != pod.spec
                            or tracked.pod.status.resource_claim_statuses
                            != pod.status.resource_claim_statuses)
            labels_changed = tracked.pod.metadata.labels != pod.metadata.labels
            tracked.pod = pod
            if (spec_changed or labels_changed) and staged_in is not None:
                # a spec or label change while staged (labels carry gang
                # membership): route the member back through _push_active so
                # it re-stages under its current group (or leaves staging if
                # no longer a member)
                staged = self._gang_staging.get(staged_in)
                if staged is not None:
                    staged.pop(key, None)
                    if not staged:
                        self._gang_staging.pop(staged_in, None)
                self._push_active(tracked)
                self._lock.notify()
                return True
            if spec_changed:
                if key in self._unschedulable:
                    self._unschedulable.pop(key)
                    remaining = self._backoff_remaining(tracked)
                    if remaining > 0:
                        self._backoff_push(self._clock.now() + remaining,
                                           tracked)
                    else:
                        self._push_active(tracked)
                        self._lock.notify()
                elif key in self._in_active:
                    # Re-sort: the heap key was computed at push time; a spec
                    # change (e.g. priority) must change pop order.
                    self._in_active.pop(key)
                    self._active = [(k, s, q) for k, s, q in self._active if q.key != key]
                    heapq.heapify(self._active)
                    self._push_active(tracked)
                    self._lock.notify()
            return True

    def delete(self, pod: Pod) -> None:
        self.delete_key(pod.key)

    def delete_key(self, key: str) -> None:
        with self._lock:
            self._unschedulable.pop(key, None)
            for group in list(self._gang_staging):
                staged = self._gang_staging[group]
                if staged.pop(key, None) is not None and not staged:
                    self._gang_staging.pop(group, None)
            for group in list(self._gang_parked):
                parked = self._gang_parked[group]
                if parked.pop(key, None) is not None and not parked:
                    self._gang_parked.pop(group, None)
            if key in self._in_active:
                self._in_active.pop(key)
                self._active = [(k, s, qp) for k, s, qp in self._active if qp.key != key]
                heapq.heapify(self._active)
            if key in self._backoff_keys:
                self._backoff = [(t, s, qp) for t, s, qp in self._backoff
                                 if qp.key != key]
                heapq.heapify(self._backoff)
                self._backoff_keys.pop(key, None)

    def clear(self) -> None:
        """Drop every queued pod across ALL tiers (crash-resync support:
        resync_from_store repopulates from a fresh LIST — a restarted
        scheduler has no memory of attempts or backoff)."""
        with self._lock:
            self._active.clear()
            self._backoff.clear()
            self._backoff_keys.clear()
            self._unschedulable.clear()
            self._in_active.clear()
            self._gang_staging.clear()
            self._gang_parked.clear()

    def contains(self, key: str) -> bool:
        """O(1) membership probe across every tier (active/backoff/
        unschedulable; gang staging is a small dict-of-dicts scan). The
        partitioned dispatch layer (ISSUE 12) calls this once per FOREIGN
        bound-pod event to clean up a stale local entry after losing a
        cross-partition race — it must never cost an O(queue) scan."""
        with self._lock:
            if (key in self._in_active or key in self._unschedulable
                    or key in self._backoff_keys):
                return True
            return (any(key in staged
                        for staged in self._gang_staging.values())
                    or any(key in parked
                           for parked in self._gang_parked.values()))

    def add_requeued(self, qps: List[QueuedPodInfo]) -> None:
        """Admit EXISTING QueuedPodInfos straight into the active tier,
        preserving their attempt counts and (crucially) submit_ts — the
        partitioned dispatch layer re-routes a pod that proved infeasible in
        one node shard to the next partition's queue through here. No
        backoff: the pod is not unschedulable, it was offered the wrong
        shard, and the hop count (PartitionRouter) bounds the re-routing so
        this cannot livelock."""
        if not qps:
            return
        with self._lock:
            for qp in qps:
                self._push_active(qp)
            self._lock.notify_all()

    def tracked_keys(self) -> List[str]:
        """Keys of every pod the queue knows, across all three tiers."""
        with self._lock:
            return (list(self._in_active)
                    + [qp.key for _, _, qp in self._backoff]
                    + list(self._unschedulable)
                    + [k for staged in self._gang_staging.values()
                       for k in staged]
                    + [k for parked in self._gang_parked.values()
                       for k in parked])

    def close(self) -> None:
        with self._lock:
            self._closed = True
            self._lock.notify_all()

    # -- introspection ---------------------------------------------------------

    def lengths(self) -> Tuple[int, int, int]:
        """(active, backoff, unschedulable); gang members waiting in staging
        or parked for a victim cover count as unschedulable — they are
        parked waiting, the same observable meaning."""
        with self._lock:
            staged = sum(len(s) for s in self._gang_staging.values())
            parked = sum(len(s) for s in self._gang_parked.values())
            return (len(self._active), len(self._backoff),
                    len(self._unschedulable) + staged + parked)

    def gang_staged_count(self) -> int:
        with self._lock:
            return sum(len(s) for s in self._gang_staging.values())

    def gang_parked_count(self) -> int:
        with self._lock:
            return sum(len(s) for s in self._gang_parked.values())

    def depths(self) -> Dict[str, int]:
        """Per-tier depth dict WITHOUT the O(queue) oldest-age scan
        telemetry() pays — the window-close probe (obs/timeseries.py) reads
        this every few seconds unthrottled, so it must stay O(tiers)."""
        with self._lock:
            return {"active": len(self._active),
                    "backoff": len(self._backoff),
                    "unschedulable": len(self._unschedulable),
                    "gang_staged": sum(len(s)
                                       for s in self._gang_staging.values()),
                    "gang_parked": sum(len(s)
                                       for s in self._gang_parked.values())}

    def telemetry(self) -> Dict[str, float]:
        """Queue depth by tier plus the age of the oldest pod still waiting
        anywhere (first-admission time, so a pod cycling through backoff
        keeps aging). One O(queue) scan per call — callers update gauges per
        PUMP, throttled (scheduler/batch.py), never per pod."""
        with self._lock:
            now = self._clock.now()
            staged = sum(len(m) for m in self._gang_staging.values())
            parked = sum(len(m) for m in self._gang_parked.values())
            waiting = itertools.chain(
                (qp for _, _, qp in self._active),
                (qp for _, _, qp in self._backoff),
                self._unschedulable.values(),
                (qp for m in self._gang_staging.values()
                 for qp in m.values()),
                (qp for m in self._gang_parked.values()
                 for qp in m.values()))
            oldest = min((qp.submit_ts or qp.timestamp for qp in waiting),
                         default=None)
            return {
                "active": len(self._active),
                "backoff": len(self._backoff),
                "unschedulable": len(self._unschedulable),
                "gang_staged": staged,
                "gang_parked": parked,
                "oldest_pending_age_s": (max(0.0, now - oldest)
                                         if oldest is not None else 0.0),
            }
