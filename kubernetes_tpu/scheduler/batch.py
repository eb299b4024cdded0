"""Batch TPU scheduler — drains whole pending-pod batches and solves them jointly.

The batching analog of ScheduleOne (SURVEY.md §2.4 'Pod-level serialization'):
pods are popped in queue (priority) order, tensorized against the current cache
snapshot, solved on device with the greedy scan kernel (ops/solver.py), and the
resulting assignments are assumed + bound through the same store surface the
serial path uses. Classes with features the device path doesn't cover yet
(inter-pod affinity, non-default PTS inclusion policies) fall back to the serial
oracle pod-by-pod — the framework-gating stance of the north star (solver
behind the same extension surface, serial path always available).
"""

from __future__ import annotations

import queue as _queue
import random as _random
import threading
import time
from collections import deque
from typing import Dict, List, Optional

import numpy as np

from ..chaos import faultinject as _chaos
from ..chaos.faultinject import FaultKill
from ..obs import gcpause as _gcpause
from ..obs import tracebuf as _tracebuf
from ..obs.recorder import install_compile_listener
from ..obs.recorder import part as _part
from ..obs.recorder import span as _span
from ..obs.timeseries import TimeSeriesRecorder
from ..snapshot.tensorizer import TensorCache, build_cluster_tensors, build_pod_batch
from ..store import (MODIFIED, APIStore, NotFoundError, is_bind_conflict,
                     pod_bind_clone, pod_structural_clone)
from .breaker import SolverCircuitBreaker
from .flightrec import FlightRecorder, StageClock, register_scheduler
from .framework import Status
from .podtrace import PodTracer
from .queue import QueuedPodInfo
from .runtime import Framework
from .serial import Scheduler


class _RequeuedChunk(list):
    """A bind chunk getting its ONE supervised retry after an escaped
    bind-worker exception (or a dead-worker recovery). A second escape fails
    its pods through the normal bind-error path instead of re-queueing again
    — no livelock on a deterministic fault."""


class BatchScheduler(Scheduler):
    """solver: 'exact' (scan, bit-parity with serial), 'fast' (water-filling),
    'auction' / 'sinkhorn' (global transportation solvers with warm-started
    duals — models/transport.py), 'native' (the C++ host engine — scan parity
    for constraint-free batches; native/hostsched.cpp), or 'auto' (fast when
    the batch has no topology-spread constraints, exact otherwise)."""

    BIND_FAILURE_LOG_CAP = 10_000  # take_bind_failures log bound

    def __init__(self, store: APIStore, framework: Framework, batch_size: int = 4096,
                 solver: str = "exact", pipeline_binds: bool = True,
                 columnar: bool = True, flight_recorder: bool = True,
                 flight_capacity: int = FlightRecorder.DEFAULT_CAPACITY,
                 breaker_threshold: int = 3, breaker_cooldown_s: float = 30.0,
                 bind_retries: int = 3, bind_retry_base_s: float = 0.05,
                 pod_trace: Optional[bool] = None,
                 trace_sample_k: int = PodTracer.DEFAULT_SAMPLE_K,
                 ts_window_s: float = 5.0, rank_align: bool = True,
                 gang_preemption: bool = True, **kw):
        super().__init__(store, framework, **kw)
        self.batch_size = batch_size
        self.solver = solver
        self.batches_solved = 0
        # flight recorder (scheduler/flightrec.py): per-batch stage timing +
        # bounded trace ring, surfaced via /debug/schedstats and `ktl sched
        # stats`. Stage marks are per BATCH (a handful of perf_counter reads),
        # so enabled-vs-disabled placement parity and the <2% overhead budget
        # both hold (tests/test_flightrec.py, tests/test_bench_quick.py).
        self.flightrec = FlightRecorder(capacity=flight_capacity,
                                        enabled=flight_recorder)
        if flight_recorder:
            # per-batch compile and GC-pause attribution (obs/recorder.py,
            # obs/gcpause.py): one listener and one hook per process
            install_compile_listener()
            _gcpause.COUNTER.install()
        self.queue.stat_sink = self.flightrec
        # sampled pod lifecycle tracer (scheduler/podtrace.py, ISSUE 7):
        # reservoir-samples K pods per window at queue admission, stamps
        # lifecycle edges with SHARED per-batch/per-chunk timestamps, and
        # feeds the all-pods submit->bound latency histogram. Follows the
        # recorder's enable switch unless pod_trace says otherwise; its
        # self-time accrues to the same <2% budget.
        self.podtrace = PodTracer(
            clock=self.clock, sample_k=trace_sample_k,
            enabled=flight_recorder if pod_trace is None else pod_trace,
            stat_sink=self.flightrec)
        self.queue.trace_sink = self.podtrace
        # windowed time-series (obs/timeseries.py, ISSUE 13): fixed-interval
        # windows over the batch pipeline — per-stage p50/p99, pods/s, and
        # window-close probe columns (queue depth, breaker state, watch lag,
        # partition counters, resource sampler). ONE note_batch tap per
        # schedule_batch; the flight recorder forwards its outside buckets
        # (bind / bind_wait / queue_add) so overlapped stages window too.
        # No stat_sink: its taps already run inside callers' measured
        # self-time windows — a sink would double-bill the budget.
        self.timeseries = TimeSeriesRecorder(
            window_s=ts_window_s, enabled=flight_recorder)
        self.flightrec.timeseries = self.timeseries
        # optional obs/resource.py ResourceSampler (attach_resource_sampler):
        # RSS / GC / live-object / per-thread CPU columns for the soak gates
        self.resource_sampler = None
        self._register_window_probes()
        # queue-depth/oldest-age gauge refresh throttle (satellite): the
        # telemetry scan is O(queue), so gauges update at most 1/s per pump
        self._q_telemetry_next = 0.0
        self._q_telemetry_last: Optional[Dict] = None
        self._q_telemetry_lock = threading.Lock()
        register_scheduler(self._bind_origin, self)
        # per-batch unschedulable-reason attribution (set during
        # schedule_batch; _handle_failure taps Status.plugin into it)
        self._batch_reasons: Optional[Dict[str, int]] = None
        self.preempt_victims_total = 0  # victims chosen by _batch_preempt
        self.trace_threshold = 1.0  # ScheduleBatch Trace log threshold (s)
        self.transport_state = None  # warm duals carried across batches
        # generation-diff incremental tensorization (cache.go:186 analog)
        self._tensor_cache = TensorCache()
        # columnar=True is the batched host pipeline: coalesced watch ingest,
        # structural+scatter-add assume accounting, self-bind short-circuit.
        # False restores the per-pod paths (the parity oracle for tests).
        self.columnar = columnar
        self.watch_coalesce = columnar
        # Cache-row mode (ISSUE 16): eligible device batches land as columnar
        # cache rows with zero per-pod objects. Resolved once at construction
        # like the store's columnar switch (STORE_COLUMNAR sweeps the whole
        # pipeline to its object-path oracle).
        from .cachecols import available as _cachecols_available

        self._cache_columnar = columnar and _cachecols_available()
        # Bind pipelining (schedule_one.go:120-132 bindingCycle-in-goroutine
        # analog): assume_pod runs synchronously so the next solve's snapshot
        # sees the capacity, while the store.bind writes flush on a worker
        # thread overlapped with solve(N+1).
        self.pipeline_binds = pipeline_binds
        # commit sub-batch size: each bind_many+confirm cycle covers this
        # many pods, so commit(N) overlaps the scheduling thread's work on
        # solve(N+1) at chunk granularity instead of whole-batch granularity
        # (and each store critical section stays short)
        self.bind_chunk = 4096
        self._bind_q: _queue.Queue = _queue.Queue()
        self._bind_worker: Optional[threading.Thread] = None
        self._bind_errors: List = []
        self._bind_successes = 0  # folded into scheduled_count on the
        self._bind_err_lock = threading.Lock()  # scheduling thread (no race)
        # assumed pods whose worker-side confirm missed (assume expired /
        # foreign interference): re-ingested on the scheduling thread at the
        # next drain, like any foreign MODIFIED
        self._bind_confirm_leftovers: List = []
        # async bind failures, surfaced to schedule_batch callers (the worker
        # requeues them internally, but "my bind_many failed" was invisible):
        # (pod key, message) pairs drained via take_bind_failures(). BOUNDED:
        # under sustained bind faults with no drainer the deque evicts oldest
        # entries and counts them instead of leaking (ISSUE 6 satellite)
        self.bind_failures: deque = deque(maxlen=self.BIND_FAILURE_LOG_CAP)
        self.bind_failures_dropped = 0
        # failure domains (ISSUE 6): solver circuit breaker (trips the fast
        # solver to the exact scan oracle after `breaker_threshold`
        # consecutive solver exceptions, half-open recovery after cooldown),
        # transient-bind retry policy, and bind-worker supervision state
        self.breaker = SolverCircuitBreaker(clock=self.clock,
                                            threshold=breaker_threshold,
                                            cooldown_s=breaker_cooldown_s)
        self.bind_retries = bind_retries
        self.bind_retry_base_s = bind_retry_base_s
        # the solver path the last _solve_device call executed (or was
        # executing when it raised) — what the breaker is fed, since the
        # MODE label alone would credit a constrained batch's scan run to
        # the fast path (scheduler/breaker.py path_matches_mode)
        self._solve_path = "exact"
        # successful device solves per executed path (fast/repair/exact/...):
        # which kernels a run really used, for sched_stats and the chip smoke
        self.solve_paths: Dict[str, int] = {}
        # constraint propose-and-repair observability (ISSUE 8): the last
        # batch's RepairStats (feeds the flight record) + running totals for
        # sched_stats/ktl — a pathological repair loop (rounds pinned at the
        # bound, heavy residual, full_scan re-solves) must be visible
        self._last_repair = None
        self.repair_totals = {
            "batches": 0, "rounds": 0, "proposed": 0, "repaired": 0,
            "residual": 0, "full_scan": 0, "violations": 0}
        # in-flight bind chunks (each owing one task_done): recorded by the
        # worker before commit, cleared after bookkeeping — non-empty with a
        # DEAD worker means a hard kill stranded them, and the liveness check
        # in _drain_bind_results re-queues them and settles the join() debt
        self._bind_inflight: List = []
        self.bind_worker_restarts = 0  # supervised escapes + dead-worker recoveries
        # gang scheduling (scheduler/gang.py): PodGroup quorums + placed
        # members, fed by the watch plumbing in serial.py; the queue holds
        # gang members in staging until quorum, and schedule_batch enforces
        # the all-or-nothing veto. Inactive (one attr read) until a PodGroup
        # exists.
        # partitioned scheduling (scheduler/partition.py, ISSUE 12):
        # installed by PartitionedScheduler on its pipelines; all inert on a
        # standalone scheduler. reroute_hook(qp, status) -> bool intercepts
        # a plain shard-capacity unschedulable verdict and moves the pod to
        # another partition's queue (True = ownership transferred, no local
        # requeue/narration); conflict_sink(qp, msg) consumes a LOST
        # cross-partition bind race (the pod IS bound — the store decided —
        # so the losing pipeline drops it instead of requeueing a pod that
        # no longer needs scheduling).
        self.partition_index: Optional[int] = None
        self.reroute_hook = None
        self.conflict_sink = None
        self.partition_conflicts = 0  # bind conflicts this pipeline LOST
        self.partition_reroutes = 0  # pods handed to another partition
        from .gang import GangDirectory
        from .gangpreempt import GangPreemptor

        self.gangs = GangDirectory()
        self.queue.set_gang_hooks(self.gangs.group_of,
                                  self.gangs.quorum_ready,
                                  lambda: self.gangs.active)
        self.gang_vetoes = 0  # gangs stripped post-solve (observability)
        # gang-aware preemption (scheduler/gangpreempt.py, ISSUE 14): a
        # solver-vetoed gang tries a min-cost victim cover on one ICI slice
        # before requeueing; rank_align gates the post-solve rank→ring
        # permutation (models/gangcover.py). Both inert on gang-free runs.
        self.rank_align = rank_align
        self.gangpreempt = GangPreemptor(self) if gang_preemption else None
        # background rebalancer (scheduler/rebalance.py, ISSUE 17):
        # installed by enable_rebalancer(); run_until_idle's quiesce path
        # paces it, sched_stats()["rebalance"] publishes its totals. Inert
        # (one attr read) until installed.
        self.rebalancer = None

    def schedule_batch(self, timeout: Optional[float] = 0.0) -> int:
        """Drain up to batch_size pods, solve jointly, bind. Returns #pods handled.

        Instrumented per BATCH (never per pod): a StageClock marks each
        pipeline stage boundary, the marks feed the scheduler_batch_stage
        histograms + a utiltrace-style Trace (logged past trace_threshold),
        and one flight-recorder record captures the batch's outcome, counts,
        and unschedulable-reason attribution. batch_solve_duration is
        observed in a try/finally with an outcome label
        (scheduled/unschedulable/error — mirroring scheduling_attempts) on
        EVERY path that pops a batch, including the no-nodes early return
        and errors. An empty pop schedules nothing and observes nothing by
        design (its pump cost folds into the aggregate outside buckets)."""
        from ..ops.solver import greedy_scan_solve, make_inputs
        from ..server import metrics as m
        from ..utils.tracing import Trace

        fr = self.flightrec
        clock = StageClock("ingest")
        # queue_add accrues into the recorder's outside bucket at its own
        # call site (inside this pump); difference it out so the "ingest"
        # residual stays disjoint from its sub-stage
        sub0 = fr.outside_seconds("queue_add")
        # pump until the watch drains — bounded: a 100k-pod backlog must
        # reach the queue as ONE batch (not batch_size/10k sub-solves), but
        # sustained event arrival must not starve scheduling forever
        for _ in range(8):
            if self.pump_events(max_events=self.batch_size) < self.batch_size:
                break
        clock.enter("pop")
        clock.sub("ingest", fr.outside_seconds("queue_add") - sub0)
        qps = self.queue.pop_batch(self.batch_size, timeout=timeout)
        clock.enter("tensorize" if qps else None)
        if not qps:
            clock.finish()
            # no batch to pin these marks to: fold idle pump/poll time into
            # the aggregate buckets (confirm-heavy idle cycles still show)
            for name, sec in clock.stages.items():
                fr.add_outside(name, sec)
            return 0
        m.batch_size_gauge.set(len(qps))
        # ONE full-batch pass finds the sampled pods (set-membership per pod,
        # nothing when the sample is empty); later stage stamps touch only
        # the <=K hits (scheduler/podtrace.py)
        self.podtrace.batch_popped(qps)
        trace = Trace("ScheduleBatch", pods=len(qps))
        failed0 = self.failed_count
        victims0 = self.preempt_victims_total
        self._batch_reasons = reasons = {}
        outcome = "error"  # overwritten unless the body raises
        out: Dict = {}
        # circuit breaker (scheduler/breaker.py): pick THIS batch's solver —
        # the configured one while CLOSED, the exact scan while OPEN, a
        # single probe of the configured one when HALF_OPEN
        out["solver"] = self.breaker.effective_solver(self.solver)
        self._last_repair = None  # set by _note_repair on the repair path
        self._tensor_cache.upload = None  # set by a device upload
        m.solver_breaker_state.set(self.breaker.code)
        try:
            self._schedule_batch_inner(qps, clock, trace, m,
                                       greedy_scan_solve, make_inputs, out)
            outcome = ("scheduled"
                       if out.get("dispatched", 0)
                       + out.get("serial_scheduled", 0) > 0
                       else "error" if "batch_error" in out
                       else "unschedulable")
            return len(qps)
        finally:
            self._batch_reasons = None
            self.batches_solved += 1
            t_fin = time.perf_counter()
            total = clock.total()
            clock.finish()
            for name, sec in clock.stages.items():
                m.batch_stage_duration.observe(sec, name)
            m.batch_solve_duration.observe(total, outcome)
            if self.gangs is not None and self.gangs.active:
                m.gang_staged.set(self.queue.gang_staged_count())
            fr.record(
                pods=len(qps), nodes=out.get("nodes", 0), outcome=outcome,
                solver=out.get("solver", self.solver), stages=clock.stages,
                total_s=total,
                scheduled=out.get("dispatched", 0)
                + out.get("serial_scheduled", 0),
                unschedulable=self.failed_count - failed0,
                fallback=out.get("fallback", 0),
                preempted=self.preempt_victims_total - victims0,
                reasons=reasons, gang=out.get("gang"),
                repair=(self._last_repair.as_dict()
                        if self._last_repair is not None else None),
                solver_iterations=getattr(self.transport_state,
                                          "iterations", None),
                breaker=(self.breaker.state
                         if self.breaker.state != "closed" else None),
                error=out.get("batch_error"), parts=clock.parts,
                compile_s=clock.compile_s, compiles=clock.compiles,
                gc_s=clock.gc_s, gc_collections=clock.gc_collections,
                upload=self._tensor_cache.upload)
            # windowed time-series (ISSUE 13): ONE tap per batch, inside the
            # t_fin self-time window so its cost bills to the <2% budget
            self.timeseries.note_batch(
                clock.stages, pods=len(qps),
                scheduled=out.get("dispatched", 0)
                + out.get("serial_scheduled", 0),
                failed=self.failed_count - failed0)
            # unified trace timeline (ISSUE 18): ONE tap per batch when a
            # buffer is armed — the batch envelope + stage slices land on
            # this pipeline's track (tid = p<i>-sched), inside the same
            # self-time window so the cost bills to the <2% budget
            if _tracebuf.ACTIVE is not None:
                tb = _tracebuf.ACTIVE
                tb.attach_clock(self.clock)
                tb.note_batch(
                    self._thread_label("sched"), t_end=t_fin,
                    stages=clock.stages, bounds=clock.bounds,
                    t_begin=clock.t0, pods=len(qps),
                    scheduled=out.get("dispatched", 0)
                    + out.get("serial_scheduled", 0),
                    outcome=outcome,
                    solver=out.get("solver", self.solver),
                    breaker=self.breaker.state)
            trace.log_if_long(self.trace_threshold)
            self._update_queue_telemetry()
            fr.note_self_time(time.perf_counter() - t_fin)

    def _schedule_batch_inner(self, qps, clock, trace, m,
                              greedy_scan_solve, make_inputs, out) -> None:
        """The batch pipeline body (schedule_batch owns the try/finally
        bookkeeping around it). Fills `out` with nodes/dispatched/fallback/
        gang counts for the flight record."""
        # Materialization barrier (ISSUE 16): a CONSTRAINED batch walks the
        # snapshot's pod lists (PTS selector counts, IPA existing-pod terms)
        # — collapse columnar cache rows into PodInfos before the snapshot is
        # taken so those walks see every pod. The predicate is a strict
        # superset of batch.has_constraints (ct/st/ipa all derive from these
        # two spec fields), checked pod-by-pod with early exit; the
        # steady-state constraint-free batch never materializes — that IS the
        # zero-alloc path.
        if self.cache.columnar_rows():
            for qp in qps:
                spec = qp.pod.spec
                if (spec.affinity is not None
                        or spec.topology_spread_constraints):
                    self.cache.materialize_columnar_rows()
                    break
        snapshot = self.cache.update_snapshot()
        out["nodes"] = len(snapshot)
        if len(snapshot) == 0:
            clock.enter(None)
            for qp in qps:
                self._handle_failure(qp, Status.unschedulable("no nodes available to schedule pods"))
            return

        cluster, changed_nodes = self._tensor_cache.cluster_tensors(snapshot)
        clock.enter("build_pod_batch")
        trace.step("Tensorized cluster", nodes=len(snapshot))
        pods = [qp.pod for qp in qps]
        store_cols = None
        if self.columnar:
            getcols = getattr(self.store, "pod_columns", None)
            if getcols is not None:
                store_cols = getcols()
        batch = build_pod_batch(
            pods, snapshot, cluster, ns_labels=self._ns_labels,
            hard_pod_affinity_weight=self._hard_pod_affinity_weight(),
            reuse=self._tensor_cache, changed_nodes=changed_nodes,
            gangs=self.gangs, store_cols=store_cols)
        if store_cols is not None:
            # bind/assume-edge sig capture (ISSUE 17 satellite): the batch
            # build just primed _class_sig/_req_sig on these pods — write
            # the refs back into the store's sig column so rows re-synced
            # by later status/relist writes keep a seedable signature. ONE
            # batched call per batch (HP001), not a per-pod ride-along.
            cap = getattr(self.store, "capture_sig_memos", None)
            if cap is not None:
                cap(pods)

        fallback_mask = batch.fallback_class[batch.class_of_pod]
        # Gang semantic hole CLOSED (ISSUE 8 satellite; ROADMAP direction 4
        # carryover): a gang member whose class needs the serial path
        # (volumes, DRA, non-default PTS policies) used to schedule
        # INDIVIDUALLY there — silently breaking all-or-nothing. The whole
        # gang is vetoed instead, with a narrated reason: every in-batch
        # member (device and fallback rows alike) fails unschedulable and
        # ONE Warning event names the gangs; a pod or PodGroup update
        # re-queues them through the normal unschedulable machinery.
        gang_strip = None
        if batch.gang_of_pod is not None:
            gof = np.asarray(batch.gang_of_pod)
            bad_gof = np.unique(gof[(gof >= 0) & fallback_mask])
            if bad_gof.size:
                gang_strip = np.isin(gof, bad_gof)
                names = ", ".join(batch.gang_keys[g] for g in bad_gof.tolist())
                self.gang_vetoes += int(bad_gof.size)
                m.gang_vetoed_total.inc(int(bad_gof.size),
                                        reason="serial_fallback")
                strip_rows = np.nonzero(gang_strip)[0].tolist()
                self.recorder.event(
                    qps[strip_rows[0]].pod, "Warning", "GangVetoed",
                    f"gang(s) {names} vetoed: a member class requires "
                    "serial-fallback scheduling (volumes/DRA), where "
                    "all-or-nothing placement cannot be enforced")
                for pi in strip_rows:
                    self._handle_failure(qps[pi], Status.unschedulable(
                        "gang member class requires serial-fallback "
                        "scheduling; all-or-nothing placement is only "
                        "enforced on the batched path (gang vetoed)"))
        if gang_strip is not None:
            device_idx = np.nonzero(~fallback_mask & ~gang_strip)[0]
            fallback_idx = np.nonzero(fallback_mask & ~gang_strip)[0]
        else:
            device_idx = np.nonzero(~fallback_mask)[0]
            fallback_idx = np.nonzero(fallback_mask)[0]
        out["fallback"] = int(fallback_idx.size)
        # each stage is named where it begins (its TraceMe span opens there)
        after_device = "fallback" if len(fallback_idx) else None
        clock.enter("solve" if device_idx.size else after_device)
        trace.step("Built pod batch", device=int(device_idx.size),
                   fallback=int(fallback_idx.size))

        assignment = None
        if device_idx.size:
            sub = _subset_batch(batch, device_idx)
            # gang members present in the device batch? (solver bias + the
            # all-or-nothing post-solve pass). The native and transport
            # backends don't model the slice-packing bonus, so gang batches
            # take the fast/exact paths (which do).
            has_gang = (sub.gang_of_pod is not None
                        and bool((sub.gang_of_pod >= 0).any()))
            solver = out.get("solver", self.solver)
            # Solver failure domain (ISSUE 6): a solver exception no longer
            # loses the batch — no assume has happened yet at solve time, so
            # the device pods requeue into the backoff tier as a unit and the
            # circuit breaker decides whether the NEXT batch degrades to the
            # exact scan oracle (scheduler/breaker.py).
            try:
                assignment = self._solve_device(solver, cluster, batch, sub,
                                                has_gang, greedy_scan_solve,
                                                make_inputs)
            except FaultKill:
                raise  # an injected hard death is not a handled fault
            except Exception as e:
                self._handle_solver_error(e, qps, device_idx, solver, out, m)
                clock.enter(after_device)
                trace.step("Solver failed; batch requeued",
                           error=type(e).__name__)
                assignment = None
            else:
                self.breaker.record_success(self._solve_path, self.solver)
                self.solve_paths[self._solve_path] = (
                    self.solve_paths.get(self._solve_path, 0) + 1)
        if device_idx.size and assignment is not None:
            # All-or-nothing gang veto (scheduler/gang.py), BEFORE any assume
            # or bind: a gang whose in-batch placements (plus members already
            # placed) miss min_member is stripped wholesale — its placed rows
            # become unplaced for every downstream consumer (bind loop,
            # capacity fold in _handle_device_rejects) — and requeued as a
            # unit. gang_requeue: gang id -> members collected for requeue.
            gang_requeue: Dict[int, List[QueuedPodInfo]] = {}
            hopeless: set = set()
            solver_vetoed: set = set()
            gang_need = None
            veto = None
            gang_info: Optional[Dict[str, int]] = None
            if has_gang:
                from .gang import gang_veto_mask

                gang_info = out["gang"] = {
                    "staged": self.queue.gang_staged_count(),
                    "vetoed": 0, "assume_vetoed": 0, "released": 0,
                    "hopeless": 0}
                gkeys = batch.gang_keys
                gang_need = need = np.array(
                    [max(0, (self.gangs.min_member(k) or 0)
                         - self.gangs.placed_count(k)) for k in gkeys],
                    dtype=np.int64)
                veto, _satisfied = gang_veto_mask(
                    assignment, np.asarray(sub.gang_of_pod), need)
                # a gang needing more members than one solve can ever see is
                # unsatisfiable by this configuration — park it with a
                # diagnostic instead of livelocking through backoff retries
                hopeless.update(np.nonzero(need > self.batch_size)[0].tolist())
                if veto.any():
                    vetoed_gids = np.unique(sub.gang_of_pod[veto])
                    n_vetoed = int(vetoed_gids.size)
                    # solver-vetoed gangs are the gang-preemption candidates
                    # (an assume-time veto means the gang FIT — a capacity
                    # race, not a room problem)
                    solver_vetoed = set(vetoed_gids.tolist())
                    self.gang_vetoes += n_vetoed
                    gang_info["vetoed"] = n_vetoed
                    m.gang_vetoed_total.inc(n_vetoed, reason="solver")
                    assignment = np.where(veto, -1, assignment)
                # rank-aware placement (ISSUE 14): permute which MEMBER gets
                # which node within each (gang, class, request) group so rank
                # order follows ICI ring position — a free permutation of an
                # identical-pod group, run ONLY when some member carries a
                # rank label (rank-less gang batches stay byte-identical)
                if (self.rank_align and sub.gang_rank is not None
                        and bool((np.asarray(sub.gang_rank) >= 0).any())):
                    assignment = self._rank_align_assignment(
                        cluster, sub, assignment, gang_info)
            # what follows is "assume" when any pod is placed (to_bind is
            # then non-empty), else the rejects' stage
            clock.enter("assume" if bool((np.asarray(assignment) >= 0).any())
                        else "reject")
            trace.step("Device solve done", solver=solver)
            self.podtrace.batch_stage("solve")  # shared per-batch stamp
            # Two phases: bind every device assignment FIRST, then handle the
            # rejected pods. Handling mid-loop would see capacity still
            # promised to not-yet-bound assignments and double-book nodes.
            rejected = []
            to_bind = []
            bind_rows: List[int] = []  # full-batch pod row per to_bind entry
            bind_nodes: List[int] = []  # cluster node index per to_bind entry
            bind_gang: List[int] = []  # gang id per entry (gang batches only)
            use_columnar = self.columnar and batch.raw_req is not None
            # Zero-object dispatch (ISSUE 16): a gang-free, constraint-free,
            # port-free device batch hands the bind worker the ORIGINAL pod
            # refs (the bind path only reads key + target node) and lands in
            # the cache as columnar ROWS — no pod_bind_clone, no PodInfo, no
            # per-pod allocation at all. Any gang/constraint/port in the
            # batch keeps the structural path byte-for-byte.
            cols_rows_ok = (use_columnar and self._cache_columnar
                            and not has_gang
                            and not batch.has_constraints
                            and batch.class_has_host_ports is not None
                            and not bool(batch.class_has_host_ports[
                                batch.class_of_pod[device_idx]].any()))
            clone = pod_bind_clone if use_columnar else pod_structural_clone
            node_names = cluster.node_names
            sub_gang = (np.asarray(sub.gang_of_pod).tolist()
                        if has_gang else None)
            veto_list = veto.tolist() if veto is not None else None
            # .tolist() once: per-element int() of numpy scalars is
            # measurable at 100k pods
            assign_list = np.asarray(assignment).tolist()
            for j, pi in enumerate(device_idx.tolist()):
                gid = sub_gang[j] if sub_gang is not None else -1
                if veto_list is not None and veto_list[j]:
                    gang_requeue.setdefault(gid, []).append(qps[pi])
                    continue
                nidx = assign_list[j]
                if nidx < 0:
                    if gid >= 0:
                        # unplaced extra of a SATISFIED gang: fail it alone —
                        # and never preempt to place part of a gang, so it
                        # skips the _batch_preempt path entirely
                        self._handle_failure(qps[pi], Status.unschedulable(
                            f"0/{len(node_names)} nodes are available "
                            "(gang member; preemption skipped)",
                            plugin="NodeResourcesFit"))
                    else:
                        rejected.append((j, qps[pi]))
                else:
                    qp = qps[pi]
                    to_bind.append((qp, node_names[nidx],
                                    qp.pod if cols_rows_ok else clone(qp.pod)))
                    bind_rows.append(pi)
                    bind_nodes.append(nidx)
                    if sub_gang is not None:
                        bind_gang.append(gid)
            if to_bind:
                # bulk assume under one cache lock, then hand the worker
                # CHUNKED batches: per-pod puts left bind_many at ~53-pod
                # batches under queue contention, while one 100k batch
                # would hold the store lock against every consumer
                pairs = [(assumed, node) for _qp, node, assumed in to_bind]
                batch_has_ports = True
                if cols_rows_ok:
                    batch_has_ports = False  # port-free by the dispatch gate
                elif use_columnar:
                    batch_has_ports = bool(
                        batch.class_has_host_ports is None
                        or batch.class_has_host_ports[
                            batch.class_of_pod[bind_rows]].any())
                # Assume/dispatch failure domain (ISSUE 6): an exception in
                # this window used to strand the whole batch's assumes. The
                # guard rolls back every entry whose chunk has NOT reached
                # the bind path and requeues it with backoff; dispatched
                # chunks are in flight, owned by the bind worker's own
                # retry/error machinery.
                accounted = False
                dispatched_hi = 0
                sync_bind_s = 0.0
                try:
                    if cols_rows_ok:
                        # row-mode phase 1: the placements land as columnar
                        # rows, zero per-pod objects; resource totals follow
                        # as one scatter-add in _columnar_account
                        bad = self.cache.assume_pods_columnar(pairs)
                    elif use_columnar:
                        # structural phase only; resource totals follow as
                        # one scatter-add in _columnar_account
                        bad = self.cache.assume_pods_structural(
                            pairs, check_ports=batch_has_ports)
                    else:
                        bad = self.cache.assume_pods(pairs)
                except FaultKill:
                    raise
                except Exception as e:
                    self._rollback_undispatched(
                        e, to_bind, bind_gang, 0, use_columnar, False,
                        batch_has_ports, m, out)
                    to_bind = []
                    bad = []
                bad_gangs = set()
                for i, msg in sorted(bad, reverse=True):
                    qp, node, _assumed = to_bind.pop(i)
                    bind_rows.pop(i)
                    bind_nodes.pop(i)
                    gid = bind_gang.pop(i) if bind_gang else -1
                    if gid >= 0:
                        bad_gangs.add(gid)
                        gang_requeue.setdefault(gid, []).append(qp)
                    else:
                        self._handle_failure(qp, Status.error(msg))
                if bad_gangs:
                    # all-or-nothing at assume time: a gang that lost a
                    # member releases every already-assumed sibling BEFORE
                    # any bind fires. On the columnar path phase 2 hasn't
                    # run yet, so the release must be the structural inverse
                    # (forget_pods_structural) — forget_pod would subtract
                    # resource totals that were never added.
                    if gang_info is not None:
                        gang_info["assume_vetoed"] = len(bad_gangs)
                        m.gang_vetoed_total.inc(len(bad_gangs),
                                                reason="assume")
                    released = []
                    for i in range(len(to_bind) - 1, -1, -1):
                        gid = bind_gang[i]
                        if gid in bad_gangs:
                            qp, _node, assumed = to_bind.pop(i)
                            bind_rows.pop(i)
                            bind_nodes.pop(i)
                            bind_gang.pop(i)
                            released.append(assumed)
                            gang_requeue.setdefault(gid, []).append(qp)
                    if gang_info is not None:
                        gang_info["released"] = len(released)
                    if use_columnar:
                        self.cache.forget_pods_structural(
                            released, check_ports=batch_has_ports)
                    else:
                        for assumed in released:
                            self.cache.forget_pod(assumed)
                if bind_gang:
                    # surviving members count toward quorum from assume on
                    # (our own bind confirmations bypass the event stream)
                    for i, (_qp, _node, assumed) in enumerate(to_bind):
                        if bind_gang[i] >= 0:
                            self.gangs.note_assumed(assumed)
                try:
                    if use_columnar and to_bind:
                        self._columnar_account(batch, cluster, snapshot,
                                               bind_rows, bind_nodes,
                                               batch_has_ports)
                        accounted = True
                    clock.enter("dispatch")
                    trace.step("Assumed placements", bound=len(to_bind))
                    self.podtrace.batch_stage("assume")
                    out["dispatched"] = len(to_bind)
                    # dispatch edge = handed to the bind path; stamped BEFORE
                    # the chunk loop so the synchronous-bind mode (which
                    # completes spans inside the loop) still records it
                    self.podtrace.batch_stage("dispatch")
                    for lo in range(0, len(to_bind), self.bind_chunk):
                        chunk = to_bind[lo:lo + self.bind_chunk]
                        if self.pipeline_binds:
                            self._ensure_bind_worker()
                            self._bind_q.put(chunk)
                        else:
                            t0 = time.perf_counter()
                            self._bind_batch(chunk)
                            sync_bind_s += time.perf_counter() - t0
                        dispatched_hi = lo + len(chunk)
                    if not self.pipeline_binds:
                        self._drain_bind_results()
                except FaultKill:
                    raise
                except Exception as e:
                    self._rollback_undispatched(
                        e, to_bind, bind_gang, dispatched_hi, use_columnar,
                        accounted, batch_has_ports, m, out)
                    out["dispatched"] = dispatched_hi
                clock.enter("reject")
                # synchronous binds ran inside the dispatch span AND are
                # observed as the "bind" stage by _bind_batch — keep the
                # stages disjoint (measured locally, so this holds with the
                # flight recorder disabled too)
                clock.sub("dispatch", sync_bind_s)
                trace.step("Dispatched binds")
            if rejected:
                self._handle_device_rejects(rejected, snapshot, cluster, sub,
                                            assignment)
            if gang_requeue:
                if gang_info is not None:
                    gang_info["hopeless"] = sum(
                        1 for g in gang_requeue if g in hopeless)
                # gang preemption (ISSUE 14): solver-vetoed gangs get ONE
                # victim-cover attempt before requeueing; context built
                # lazily only when an eligible gang exists
                preempt_ctx = None
                if (self.gangpreempt is not None and gang_need is not None
                        and any(g in solver_vetoed and g not in hopeless
                                for g in gang_requeue)):
                    preempt_ctx = self.gangpreempt.build_ctx(
                        snapshot, cluster, sub, assignment, gang_need)
                self._requeue_gangs(gang_requeue, batch.gang_keys or [],
                                    hopeless, preempt_gids=solver_vetoed,
                                    preempt_ctx=preempt_ctx,
                                    gang_info=gang_info)
            if rejected or gang_requeue:
                clock.enter(after_device)
                trace.step("Handled rejects", rejected=len(rejected))
            else:
                clock.drop(after_device)

        # Serial fallback, in original priority order among themselves.
        # Gang members never reach here: a gang touching a serial-fallback
        # class was vetoed above (all-or-nothing cannot be enforced on the
        # per-pod path).
        if len(fallback_idx):
            # (columnar cache rows are collapsed by schedule_pod itself
            # before it snapshots — the serial plugins walk pod lists)
            fb0 = self.scheduled_count
            for pi in fallback_idx:
                self._serial_one(qps[pi])
            out["serial_scheduled"] = self.scheduled_count - fb0
            clock.enter(None)
            trace.step("Serial fallback done", pods=len(fallback_idx))

    def _solve_device(self, solver, cluster, batch, sub, has_gang,
                      greedy_scan_solve, make_inputs) -> np.ndarray:
        """One device-batch solver dispatch, parameterized by the (possibly
        breaker-degraded) solver choice. 'fast' means fast-when-legal: the
        water-fill kernel has no topology-spread or inter-pod-affinity
        handling, so constrained batches always take the exact scan path
        regardless of mode. Any exception propagates to the failure-domain
        handler in _schedule_batch_inner (the batch requeues; it is never
        lost)."""
        from .breaker import REPRESENTATIVE

        # _solve_path tracks the path actually executing at every point so
        # both the success return and an exception anywhere in here
        # attribute to the right solver (the breaker must never credit a
        # scan outcome to the fast path, or vice versa). Routing is decided
        # BEFORE the injected fire so a chaos fault on a constrained
        # fast-mode batch attributes to the repair kernel it would have run
        # — tripping the breaker to the scan exactly like a waterfill fault.
        self._solve_path = REPRESENTATIVE.get(solver, solver)
        constraint_free = not batch.has_constraints
        use_fast = solver in ("fast", "auto") and constraint_free
        # constrained batches under the fast/auto modes ride the
        # propose-and-repair pipeline (models/repair.py, ISSUE 8); every
        # other mode's constrained batches stay on the scan oracle
        use_repair = solver in ("fast", "auto") and not constraint_free
        use_transport = (solver in ("auction", "sinkhorn")
                         and constraint_free and not has_gang)
        if use_repair:
            self._solve_path = "repair"
        elif not constraint_free:
            self._solve_path = "exact"  # the scan owns constrained batches
        if _chaos.ACTIVE is not None:
            _chaos.ACTIVE.fire("solver.solve")
        assignment = None
        # the solve stage's parts (obs/recorder.py): the uploads, the
        # solver's dispatch with its eager ops, and the host's waits on
        # device results; the rest of the stage is solve.host
        if solver == "native" and constraint_free and not has_gang:
            from ..native import native_available, native_greedy_solve

            if native_available():
                self._solve_path = "native"
                with _part("solve.kernel"):
                    assignment, _ = native_greedy_solve(cluster, sub)
                if assignment is None:
                    self._solve_path = "exact"
        # device upload happens only for paths that consume it; cluster
        # tensors ride the persistent HBM mirrors (diff streaming)
        inputs = d_max = None
        if assignment is None:
            with _part("solve.upload"):
                inputs, d_max = make_inputs(
                    cluster, sub,
                    device=self._tensor_cache.device_views(cluster))
        if use_transport:
            from ..models.transport import transport_solve
            from ..models.waterfill import make_groups

            self._solve_path = solver
            groups = make_groups(sub)
            with _part("solve.kernel"):
                solved = transport_solve(
                    inputs, groups, method=solver,
                    state=self.transport_state,
                    node_names=cluster.node_names)
            if solved is not None:
                assignment, self.transport_state = solved
            else:
                self._solve_path = "exact"  # declined: the scan takes it
        if use_fast:
            from ..models.waterfill import make_groups, waterfill_solve

            self._solve_path = "fast"
            groups = make_groups(sub)
            with _part("solve.kernel"):
                assignment = waterfill_solve(inputs, groups)
        if use_repair:
            from ..models.repair import repair_solve

            with _part("solve.kernel"):
                solved = repair_solve(
                    inputs, sub, d_max,
                    has_gang=bool(has_gang and sub.gang_bonus is not None))
            if solved is not None:
                assignment, rstats = solved
                self._note_repair(rstats)
            else:
                # problem shape exceeds the fast path's sort-key range:
                # decline to the oracle, exactly like waterfill_solve
                self._solve_path = "exact"
        if assignment is None:
            # static gates: constraint-free batches compile the scan
            # variant without IPA gathers / PTS segment sums
            self._solve_path = "exact"
            with _part("solve.kernel"):
                assignment, _, _ = greedy_scan_solve(
                    inputs, d_max, has_ipa=bool(batch.ipa.has_any),
                    has_ct=bool(batch.ct_class.size),
                    has_st=bool(batch.st_class.size),
                    has_gang=bool(has_gang and sub.gang_bonus is not None))
        with _part("solve.readback"):
            return np.asarray(assignment)

    def _note_repair(self, rstats) -> None:
        """Fold one constrained batch's RepairStats into the metrics and the
        running totals (ONE call per batch, never per pod)."""
        from ..server import metrics as m

        self._last_repair = rstats
        t = self.repair_totals
        t["batches"] += 1
        t["rounds"] += rstats.rounds
        t["proposed"] += rstats.proposed
        t["repaired"] += rstats.repaired
        t["residual"] += rstats.residual
        t["full_scan"] += int(rstats.full_scan)
        m.constraint_repair_rounds.observe(rstats.rounds)
        for kind, v in rstats.violations.items():
            if v:
                t["violations"] += v
                m.constraint_violations_total.inc(v, kind=kind)

    def _handle_solver_error(self, e, qps, device_idx, solver, out, m) -> None:
        """Solver failure domain: requeue the device pods with backoff (the
        pods are fine — the INFRASTRUCTURE hiccuped, so no cluster event is
        needed before retrying), feed the circuit breaker, and narrate ONCE
        per batch (a 100k-pod batch must not write 100k events)."""
        qps_dev = [qps[pi] for pi in device_idx.tolist()]
        tripped = self.breaker.record_failure(self._solve_path, self.solver)
        m.solver_breaker_state.set(self.breaker.code)
        m.batch_retries_total.inc(len(qps_dev), stage="solve",
                                  reason=type(e).__name__)
        self.queue.add_backoff(qps_dev)
        sink = self._batch_reasons
        if sink is not None:
            sink["SolverError"] = sink.get("SolverError", 0) + len(qps_dev)
        out["batch_error"] = f"{type(e).__name__}: {e}"[:200]
        msg = (f"solver {solver} failed ({type(e).__name__}: {str(e)[:120]});"
               f" {len(qps_dev)} pod(s) requeued with backoff")
        if tripped:
            msg += (f"; circuit breaker OPEN — degrading to "
                    f"{self.breaker.effective_solver(self.solver)} for "
                    f"{self.breaker.cooldown_s:g}s")
        self.recorder.event(qps_dev[0].pod, "Warning", "SchedulerError", msg)

    def _rollback_undispatched(self, e, to_bind, bind_gang, dispatched,
                               use_columnar, accounted, batch_has_ports,
                               m, out) -> int:
        """Assume/dispatch failure domain: roll back every to_bind entry at
        index >= `dispatched` (its chunk never reached the bind path) and
        requeue it with backoff. Before _columnar_account ran, the rollback
        is the STRUCTURAL inverse (phase-2 resource totals were never added
        — forget_pod would drive them negative); after it, forget_pod is the
        exact inverse. A failure INSIDE _columnar_account leaves the few
        already-poked nodes conservatively over-counted (capacity looks
        smaller than it is — the safe direction) until the diff path
        requantizes or resync_from_store rebuilds."""
        stranded = to_bind[dispatched:]
        if not stranded:
            return 0
        released = [assumed for _qp, _node, assumed in stranded]
        if use_columnar and not accounted:
            self.cache.forget_pods_structural(released,
                                              check_ports=batch_has_ports)
        else:
            for assumed in released:
                self.cache.forget_pod(assumed)
        if self.gangs is not None and bind_gang:
            for i in range(dispatched, len(to_bind)):
                if bind_gang[i] >= 0:
                    self.gangs.note_forgotten(to_bind[i][2])
        self.queue.add_backoff([qp for qp, _node, _assumed in stranded])
        m.batch_retries_total.inc(len(stranded), stage="dispatch",
                                  reason=type(e).__name__)
        sink = self._batch_reasons
        if sink is not None:
            sink["DispatchError"] = (sink.get("DispatchError", 0)
                                     + len(stranded))
        out["batch_error"] = f"{type(e).__name__}: {e}"[:200]
        self.recorder.event(
            stranded[0][0].pod, "Warning", "SchedulerError",
            f"assume/dispatch failed ({type(e).__name__}: {str(e)[:120]}); "
            f"{len(stranded)} assumed pod(s) rolled back and requeued")
        return len(stranded)

    def _requeue_gangs(self, groups: Dict[int, List[QueuedPodInfo]],
                       keys: List[str],
                       hopeless: frozenset = frozenset(),
                       preempt_gids: frozenset = frozenset(),
                       preempt_ctx: Optional[Dict] = None,
                       gang_info: Optional[Dict] = None) -> None:
        """Gang-aware rejection handling: a vetoed (or assume-rolled-back)
        gang re-enters the queue AS A UNIT — one shared backoff expiry via
        SchedulingQueue.add_gang_backoff, so the members re-stage and
        re-admit together instead of dribbling through the unschedulable map
        one cluster event at a time. One FailedScheduling narration per gang
        (not per member: a 250-rank gang must not write 250 events per
        veto). `hopeless` gangs (min_member beyond what one solve can see)
        park unschedulable with a diagnostic instead — retrying on a timer
        would livelock.

        Gang preemption (ISSUE 14): a SOLVER-vetoed gang (in preempt_gids,
        with a built preempt_ctx) first tries a victim cover
        (scheduler/gangpreempt.py). A fired cover PARKS the gang — its
        members are neither failures nor requeued here, they wait in the
        parked tier for victim termination; a partial-room veto (or an
        inapplicable attempt) falls through to the normal unit requeue."""
        for gid, members in groups.items():
            key = keys[gid] if 0 <= gid < len(keys) else "<unknown>"
            if gid in hopeless:
                status = Status.unschedulable(
                    f"pod group {key} needs more members than the solver "
                    f"batch size ({self.batch_size}) can place together; "
                    "raise batch_size or lower minMember",
                    plugin="GangScheduling")
                for m in members:
                    self._handle_failure(m, status)
                continue
            if preempt_ctx is not None and gid in preempt_gids:
                got = self.gangpreempt.try_preempt(key, gid, members,
                                                   preempt_ctx)
                # trace timeline (ISSUE 18): one instant per preemption
                # ATTEMPT (per gang, never per member)
                if _tracebuf.ACTIVE is not None:
                    fired = got is not None and not got.get("vetoed")
                    _tracebuf.ACTIVE.instant(
                        self._thread_label("sched"),
                        "gang_preempt:%s" % ("fired" if fired else "vetoed"),
                        cat="gang",
                        args={"gang": key,
                              "victims": (got or {}).get("victims", 0)})
                if got is not None and not got.get("vetoed"):
                    # cover fired: the gang is PARKED awaiting victim
                    # termination — not a scheduling failure
                    if gang_info is not None:
                        gang_info["preempted"] = (
                            gang_info.get("preempted", 0) + 1)
                        gang_info["preempt_victims"] = (
                            gang_info.get("preempt_victims", 0)
                            + got["victims"])
                        gang_info["cover_cost"] = (
                            gang_info.get("cover_cost", 0) + got["cost"])
                    if self._batch_reasons is not None:
                        self._batch_reasons["GangPreemption"] = (
                            self._batch_reasons.get("GangPreemption", 0)
                            + len(members))
                    continue
                if got is not None and gang_info is not None:
                    gang_info["preempt_vetoed_partial"] = (
                        gang_info.get("preempt_vetoed_partial", 0) + 1)
            self.failed_count += len(members)
            if self._batch_reasons is not None:
                self._batch_reasons["GangScheduling"] = (
                    self._batch_reasons.get("GangScheduling", 0)
                    + len(members))
            for m in members:
                m.unschedulable_plugins = ("GangScheduling",)
            self.recorder.event(
                members[0].pod, "Warning", "FailedScheduling",
                f"pod group {key}: {len(members)} member(s) cannot be placed "
                "together (all-or-nothing); gang requeued")
            self.queue.add_gang_backoff(members)

    def _rank_align_assignment(self, cluster, sub, assignment,
                               gang_info: Optional[Dict]) -> np.ndarray:
        """Rank-aware placement pass (ISSUE 14): within each (gang, class,
        request) group — where members are interchangeable by construction —
        permute WHICH member gets WHICH node so rank order follows ICI ring
        position (models/gangcover.py rank_align; sorted-to-sorted matching
        minimizes consecutive-rank hop distance). The node SET is untouched:
        feasibility, capacity accounting, and the gang veto all see the same
        multiset. Publishes the before/after mean neighbor distance into the
        batch's gang flight-record dict."""
        from ..models.gangcover import (alignment_groups,
                                        mean_neighbor_distance, rank_align)
        from .gang import node_slice_positions

        slice_ids, pos = node_slice_positions(cluster)
        if slice_ids is None:
            return assignment  # no ICI topology: adjacency is moot
        a = np.asarray(assignment, dtype=np.int64)
        gop = np.asarray(sub.gang_of_pod)
        ranks = np.asarray(sub.gang_rank, dtype=np.int64)
        groups = alignment_groups(gop, np.asarray(sub.class_of_pod),
                                  np.asarray(sub.req),
                                  np.asarray(sub.req_nz))
        # rank-less members order AFTER ranked siblings, by row
        # (deterministic); keys stay far under the int32 sentinels
        eff_rank = np.where(ranks >= 0, ranks,
                            1_000_000 + np.arange(len(ranks)))
        # per-member position key: slice-major ring position of the assigned
        # node; unlabeled nodes sort after every labeled one, unplaced last
        stride = cluster.n + 1
        node_key = np.where(
            slice_ids >= 0, slice_ids * stride + np.maximum(pos, 0),
            2**28 + np.arange(cluster.n))
        placed = a >= 0
        pos_key = np.where(placed, node_key[np.maximum(a, 0)], 2**30)
        aligned = rank_align(a, groups, eff_rank, pos_key)
        # adjacency pre/post telemetry is observability, not placement —
        # pure-Python per-member passes, so it rides the flight recorder's
        # enable switch like every other non-essential measurement
        if gang_info is not None and self.flightrec.enabled:
            from .gang import ring_lengths

            ranked = ranks >= 0
            ring_len = ring_lengths(slice_ids, pos)

            def dist(assign):
                aa = np.asarray(assign)
                ok = ranked & (aa >= 0)
                sl = np.where(ok, slice_ids[np.maximum(aa, 0)], -1)
                pp = np.where(ok, pos[np.maximum(aa, 0)], -1)
                return mean_neighbor_distance(
                    np.where(ranked, gop, -1).tolist(), ranks.tolist(),
                    sl.tolist(), pp.tolist(), ring_len)

            pre, post = dist(a), dist(aligned)
            if pre is not None:
                gang_info["adjacency_pre"] = round(pre, 3)
            if post is not None:
                gang_info["adjacency_post"] = round(post, 3)
            gang_info["rank_aligned"] = int((aligned != a).sum())
        return aligned.astype(np.int32)

    def _columnar_account(self, batch, cluster, snapshot, bind_rows,
                          bind_nodes, has_ports: bool = True) -> None:
        """Phase 2 of the columnar assume: per-node requested-resource deltas
        for the whole solved batch as numpy scatter-adds keyed by the
        tensorizer's node index — one Resource poke per touched node in the
        cache, and (when nothing foreign intervened and no host ports are in
        play) a direct feed of TensorCache's generation diff so solve(N+1)
        skips the per-node requantize walk entirely."""
        rows = np.asarray(bind_rows, dtype=np.int64)
        nodes = np.asarray(bind_nodes, dtype=np.int64)
        n, r = cluster.n, len(cluster.resource_dims)
        from ..native import hostcommit, native_available, native_commit_deltas

        if native_available() and hostcommit.available():
            # ONE GIL-free C pass (ctypes CDLL releases the GIL for the
            # call) replacing two np.add.at dispatches + bincount + unique.
            # NO lock is held here — the CDLL kernels are blocking calls
            # under schedlint LK002 (store/store.py NATIVE LOCK RULE).
            # Gated on hostcommit.available() too so the documented kill
            # switch (HOSTSCHED_NATIVE_COMMIT=0) forces the pure-numpy
            # fallback on EVERY native-commit path, this one included.
            d_used, d_used_nz, d_count, touched = native_commit_deltas(
                rows, nodes, batch.raw_req, batch.raw_req_nz, n)
        else:
            d_used = np.zeros((n, r), dtype=np.int64)
            d_used_nz = np.zeros((n, r), dtype=np.int64)
            np.add.at(d_used, nodes, batch.raw_req[rows])
            np.add.at(d_used_nz, nodes, batch.raw_req_nz[rows])
            d_count = np.bincount(nodes, minlength=n)
            touched = np.unique(nodes)
        final_gen = self.cache.apply_node_resource_deltas(
            cluster.resource_dims,
            [(cluster.node_names[i], d_used[i], d_used_nz[i])
             for i in touched],
            expected_gen=snapshot.generation)
        if final_gen is not None and not has_ports:
            self._tensor_cache.apply_assume_deltas(
                touched, d_used[touched], d_used_nz[touched],
                d_count[touched], tensorized_gen=snapshot.generation,
                assume_gen=final_gen)

    def _handle_device_rejects(self, rejected, snapshot, cluster, sub,
                               assignment) -> None:
        """Failure handling for pods the device solver could not place.

        When the batch is constraint-free (no PTS DoNotSchedule rows, no
        inter-pod affinity), preemption candidates are computed as dense
        priority-tier tensors (_batch_preempt) — the vector analog of the
        reference's parallel DryRunPreemption (preemption.go:680) — and only
        the single chosen node per pod is verified with the real serial
        filters. Constrained batches keep the serial PostFilter path, because
        evicting victims can change PTS/IPA feasibility in ways the tier math
        does not model."""
        import itertools

        import numpy as np

        from .framework import CycleState

        if self.cache.columnar_rows():
            # Pre-batch placements held as columnar rows have no PodInfo, so
            # the victim walk below cannot see them. Collapse them and patch
            # the local (pre-batch) snapshot clones in place; rows assumed by
            # THIS batch stay out of the patch — the dry run already sees
            # those via placed_by_node, and the next update_snapshot re-clones
            # every touched node from the cache anyway.
            batch_keys = {p.key for p in sub.pods}
            mat: list = []
            self.cache.materialize_columnar_rows(mat)
            for node_name, pi in mat:
                if pi.pod.key in batch_keys:
                    continue
                ni = snapshot.node_info_map.get(node_name)
                if ni is not None:
                    # raw append: phase 2 already folded the resources into
                    # this clone; keep len(pods)+col_count exact
                    ni.pods.append(pi)
                    ni.col_count -= 1

        # post-batch capacity: fold every in-batch assignment into used state
        used = cluster.used.astype(np.int64).copy()
        pod_count = cluster.pod_count.astype(np.int64).copy()
        a = np.asarray(assignment)
        placed = a >= 0
        if placed.any():
            np.add.at(used, a[placed], sub.req[placed])
            np.add.at(pod_count, a[placed], 1)
        alloc = cluster.alloc.astype(np.int64)
        max_pods = cluster.max_pods.astype(np.int64)

        filter_ok = sub.tables.filter_ok
        node_names = cluster.node_names
        n = len(node_names)

        constraint_free = sub.ct_class.size == 0 and not sub.ipa.has_any
        if constraint_free:
            # in-batch placements per node: the verify step must see them
            placed_by_node = {}
            for jj in np.nonzero(placed)[0]:
                placed_by_node.setdefault(int(a[jj]), []).append(sub.pods[jj])
            remaining = self._batch_preempt(
                rejected, snapshot, cluster, sub, alloc, used, pod_count,
                max_pods, placed_by_node)
            # the tier math is strictly more permissive than the serial dry
            # run for constraint-free pods (it ignores port conflicts), so a
            # pod with no tier candidate has no serial candidate either —
            # fail it without a second sweep.
            for j, qp in remaining:
                # attributed to Fit so hint-gated requeue fires on node
                # capacity / assigned-pod-freed events
                self._handle_failure(qp, Status.unschedulable(
                    f"0/{n} nodes are available", plugin="NodeResourcesFit"))
            return

        # Constrained batch: synthesize the per-node failure map (vectorized;
        # shared Status instances per category) and run the serial PostFilter.
        unres = Status.unresolvable("node(s) didn't match the pod's static predicates")
        nofit = Status.unschedulable("Insufficient resources on the node")
        inbatch = Status.unschedulable("node rejected by in-batch constraints")
        names_arr = np.array(node_names)
        for j, qp in rejected:
            pod = qp.pod
            cls = int(sub.class_of_pod[j])
            req = sub.req[j].astype(np.int64)
            fits = np.all((req[None, :] == 0) | (req[None, :] <= alloc - used),
                          axis=1) & (pod_count + 1 <= max_pods)
            static_ok = filter_ok[cls]
            failed = {}
            failed.update(zip(names_arr[~static_ok].tolist(), itertools.repeat(unres)))
            failed.update(zip(names_arr[static_ok & ~fits].tolist(), itertools.repeat(nofit)))
            failed.update(zip(names_arr[static_ok & fits].tolist(), itertools.repeat(inbatch)))
            fw = self._fw(pod) or self.framework
            state = CycleState()
            fw.run_pre_filter(state, pod, snapshot)
            from .serial import ScheduleResult

            result = ScheduleResult(
                status=Status.unschedulable(f"0/{n} nodes are available"),
                failed_nodes=failed, state=state,
                evaluated_nodes=n)
            self._maybe_preempt(qp, result)
            self._handle_failure(qp, result.status, result.failed_nodes)

    def _preemption_plugin(self, fw):
        from .plugins.default_preemption import DefaultPreemption

        for p in fw.post_filter_plugins:
            if isinstance(p, DefaultPreemption):
                return p
        return None

    def _batch_preempt(self, rejected, snapshot, cluster, sub, alloc, used,
                       pod_count, max_pods, placed_by_node):
        """Tiered batch preemption (reference: preemption.go DryRunPreemption
        :680 + SelectCandidate :396, reframed as tensor math).

        For each rejected pod at priority p, candidate nodes are those where
        the pod fits after evicting every pod with priority < p — computed
        once per distinct tier as dense [N,R] freed-capacity tensors. Node
        selection follows pick_one_node_for_preemption's order (fewest PDB
        violations, lowest max victim priority, smallest priority sum, fewest
        victims, index). Only the chosen node runs the serial dry run
        (_dry_run_node), which produces the MINIMAL victim set via the
        reprieve pass and exact PDB accounting; its victims update the tier
        tensors so later pods in the batch see the new capacity.

        Returns the (j, qp) pairs that could not be preempted."""
        import numpy as np

        from .framework import CycleState, PodInfo
        from .gangpreempt import flatten_snapshot_victims

        n = cluster.n
        dims = cluster.resource_dims
        r = len(dims)

        # flatten bound pods into victim arrays (one snapshot pass) — the
        # helper shared with the gang victim cover (ISSUE 14 satellite)
        v_node, v_prio, v_req, v_pods, node_victims = \
            flatten_snapshot_victims(snapshot, dims)
        if not v_pods:
            return list(rejected)
        v_alive = np.ones(len(v_pods), dtype=bool)

        plugin_by_fw: dict = {}

        def plugin_for(pod):
            fw = self._fw(pod) or self.framework
            got = plugin_by_fw.get(id(fw))
            if got is None:
                got = (fw, self._preemption_plugin(fw))
                plugin_by_fw[id(fw)] = got
            return got

        # PDB exhaustion per victim (approximate violation count for node
        # selection; the serial dry run on the chosen node is exact). Listed
        # from the store directly — profiles without DefaultPreemption must
        # not blind the batch to budgets.
        try:
            pdbs, _ = self.store.list("poddisruptionbudgets")
        except Exception:
            pdbs = []
        v_pdb_blocked = np.zeros(len(v_pods), dtype=bool)
        if pdbs:
            for vi, p in enumerate(v_pods):
                v_pdb_blocked[vi] = any(
                    pd.metadata.namespace == p.metadata.namespace
                    and pd.selector is not None
                    and pd.selector.matches(p.metadata.labels)
                    and pd.disruptions_allowed <= 0
                    for pd in pdbs)

        tier_cache: dict = {}

        def tier(p):
            got = tier_cache.get(p)
            if got is None:
                mask = v_alive & (v_prio < p)
                freed = np.zeros((n, r), np.int64)
                np.add.at(freed, v_node[mask], v_req[mask])
                cnt = np.zeros(n, np.int64)
                np.add.at(cnt, v_node[mask], 1)
                psum = np.zeros(n, np.int64)
                np.add.at(psum, v_node[mask], v_prio[mask])
                viol = np.zeros(n, np.int64)
                if pdbs:
                    np.add.at(viol, v_node[mask & v_pdb_blocked], 1)
                pmax = np.full(n, -(2**31), np.int64)
                np.maximum.at(pmax, v_node[mask], v_prio[mask])
                got = [freed, cnt, psum, viol, pmax]
                tier_cache[p] = got
            return got

        filter_ok = sub.tables.filter_ok
        node_names = cluster.node_names
        remaining = []
        nominated_by_node: Dict[int, List] = {}
        for j, qp in rejected:
            pod = qp.pod
            fw, plugin = plugin_for(pod)
            if plugin is None or pod.spec.preemption_policy == "Never":
                remaining.append((j, qp))
                continue
            p = pod.spec.priority
            cls = int(sub.class_of_pod[j])
            req = sub.req[j].astype(np.int64)
            freed, cnt, psum, viol, pmax = tier(p)
            fits = np.all((req[None, :] == 0)
                          | (req[None, :] <= alloc - used + freed), axis=1)
            fits &= pod_count + 1 - cnt <= max_pods
            cand_mask = fits & filter_ok[cls] & (cnt > 0)
            if not cand_mask.any():
                remaining.append((j, qp))
                continue
            idxs = np.nonzero(cand_mask)[0]
            order = np.lexsort((idxs, cnt[idxs], psum[idxs], pmax[idxs], viol[idxs]))
            # candidate cap mirrors GetOffsetAndNumCandidates (preemption.go:595)
            num_candidates = max(plugin.MIN_CANDIDATE_NODES_ABSOLUTE,
                                 n * plugin.MIN_CANDIDATE_NODES_PERCENTAGE // 100)
            state = CycleState()
            _, st = fw.run_pre_filter(state, pod, snapshot)
            chosen = None
            if st.is_success():
                for oi in order[:num_candidates]:  # best-ranked first
                    nn = int(idxs[oi])
                    ni = snapshot.node_info_list[nn]
                    # the snapshot NodeInfo is pre-batch: drop victims an
                    # earlier pod in this batch already claimed (v_alive
                    # False) and add in-batch placements/nominations, or the
                    # dry run re-selects dead victims and frees nothing
                    dead = [v_pods[vi] for vi in node_victims[nn]
                            if not v_alive[vi]]
                    extra = list(placed_by_node.get(nn, ()))
                    extra += nominated_by_node.get(nn, [])
                    if dead or extra:
                        ni = ni.clone()
                        for dp_ in dead:
                            ni.remove_pod(dp_)
                        for xp in extra:
                            ni.add_pod(PodInfo(xp))
                    got = plugin._dry_run_node(state, pod, ni, pdbs)
                    if got is not None:
                        chosen = (nn, got)
                        break
            if chosen is None:
                remaining.append((j, qp))
                continue
            nn, cand = chosen
            victims = cand.victims
            self.preempt_victims_total += len(victims)
            vkeys = {v.key for v in victims}
            freed_now = np.zeros(r, np.int64)
            for vi in node_victims[nn]:
                if v_alive[vi] and v_pods[vi].key in vkeys:
                    v_alive[vi] = False
                    freed_now += v_req[vi]
                    for tp, (tfreed, tcnt, tpsum, tviol, _tp) in tier_cache.items():
                        if v_prio[vi] < tp:
                            tfreed[nn] -= v_req[vi]
                            tcnt[nn] -= 1
                            tpsum[nn] -= v_prio[vi]
                            if v_pdb_blocked[vi]:
                                tviol[nn] -= 1
            # max victim priority can only be recomputed, not decremented
            for tp, arrs in tier_cache.items():
                alive = [int(v_prio[vi]) for vi in node_victims[nn]
                         if v_alive[vi] and v_prio[vi] < tp]
                arrs[4][nn] = max(alive) if alive else -(2**31)
            used[nn] += req - freed_now
            pod_count[nn] += 1 - len(victims)
            nominated_by_node.setdefault(nn, []).append(pod)
            plugin._prepare_candidate(cand, pod)
            qp.pod.status.nominated_node_name = node_names[nn]
            self.preemption_count += 1
            self._handle_failure(qp, Status.unschedulable(
                f"preempted {len(victims)} pod(s) on {node_names[nn]}; "
                "waiting for victims to terminate", plugin="NodeResourcesFit"))
        return remaining

    def _handle_failure(self, qp: QueuedPodInfo, status: Status,
                        failed_nodes: Optional[Dict[str, Status]] = None) -> None:
        """Taps the failure's attribution (plugin, else the reason text) into
        the current batch's flight record before the shared requeue path.

        Partitioned re-route (ISSUE 12): an UNSCHEDULABLE verdict from a
        pipeline that only sees one node shard is not a cluster verdict —
        the reroute hook offers the pod to the next partition (or the global
        residual pass) instead of parking it, UNLESS preemption nominated a
        node here (victims are terminating on OUR shard; the pod must wait
        locally). A re-routed pod is not a failure: no event, no status
        patch, no failed_count — the terminal verdict belongs to whichever
        pipeline exhausts the routing."""
        hook = self.reroute_hook
        if hook is not None:
            from .framework import Code

            if (status.code == Code.UNSCHEDULABLE
                    and not qp.pod.status.nominated_node_name
                    and hook(qp, status)):
                self.partition_reroutes += 1
                return
        sink = self._batch_reasons
        if sink is not None:
            key = status.plugin or (status.reasons[0][:80] if status.reasons
                                    else status.code.name.lower())
            sink[key] = sink.get(key, 0) + 1
        super()._handle_failure(qp, status, failed_nodes)

    def _update_queue_telemetry(self, want_dict: bool = False) -> Optional[Dict]:
        """Refresh the scheduler_queue_depth{tier} gauges and the
        oldest-pending-age gauge (ISSUE 7 satellite). Called once per pump
        (schedule_batch's finally), throttled to 1/s because the underlying
        scan is O(queue) under the queue lock — gauges are a dashboard read,
        not a control input. The throttle holds for EVERY caller: a read
        surface (want_dict=True) inside the window gets the cached <=1s-old
        dict instead of forcing a rescan, so an aggressive external poller
        (`ktl sched stats -w --interval 0.1` against a 100k backlog) can't
        turn /debug/schedstats into a queue-lock DoS."""
        # claim the refresh slot under a private lock (check-then-act:
        # sched_stats runs on HTTP handler threads concurrently with the
        # pump) so N simultaneous pollers produce ONE scan, not N; the scan
        # itself runs outside the claim lock
        with self._q_telemetry_lock:
            now = self.clock.now()
            if now < self._q_telemetry_next and \
                    self._q_telemetry_last is not None:
                return self._q_telemetry_last if want_dict else None
            self._q_telemetry_next = now + 1.0
        t0 = time.perf_counter()
        tel = self.queue.telemetry()
        from ..server import metrics as m

        for tier in ("active", "backoff", "unschedulable", "gang_staged",
                     "gang_parked"):
            m.queue_depth.set(tel[tier], tier=tier)
        m.queue_oldest_age.set(tel["oldest_pending_age_s"])
        self.flightrec.note_self_time(time.perf_counter() - t0)
        self._q_telemetry_last = tel
        return tel

    def sched_stats(self) -> Dict:
        """The /debug/schedstats payload: live counters + the flight
        recorder's aggregate stage table (now with p50/p99 columns), the
        submit->bound latency distribution, tracer health, and the last-batch
        record (the machine-generated successor of ROADMAP's hand-maintained
        table)."""
        tel = self._update_queue_telemetry(want_dict=True)
        # read the windows FIRST: the read settles an expired open window,
        # and the meta counters below must describe the settled state
        windows = self.timeseries.windows(last=12)
        gang = None
        if self.gangs is not None and self.gangs.active:
            from ..server import metrics as m

            expired = self.gangs.quorum_expired_count(self.cache.contains)
            m.gang_quorum_expired_assumes.set(expired)
            gang = {"staged": self.queue.gang_staged_count(),
                    "parked": self.queue.gang_parked_count(),
                    "vetoes": self.gang_vetoes,
                    "quorum_expired_assumes": expired,
                    # victim-cover stats (ISSUE 14): attempts/preempted/
                    # victims/cover_cost/slices_ripped/vetoed_partial +
                    # release accounting, the `ktl sched stats` gang-
                    # preemption line's source
                    "preemption": (self.gangpreempt.stats()
                                   if self.gangpreempt is not None
                                   else None)}
        fr = self.flightrec
        return {
            "solver": self.solver,
            "batch_size": self.batch_size,
            "batches_solved": self.batches_solved,
            "scheduled": self.scheduled_count,
            "failed": self.failed_count,
            "preemptions": self.preemption_count,
            "preempt_victims": self.preempt_victims_total,
            "queue": {"active": tel["active"], "backoff": tel["backoff"],
                      "unschedulable": tel["unschedulable"],
                      "gang_staged": tel["gang_staged"],
                      "gang_parked": tel.get("gang_parked", 0),
                      "oldest_pending_age_s": round(
                          tel["oldest_pending_age_s"], 3)},
            "latency": self.podtrace.latency_stats(),
            "trace": {"enabled": self.podtrace.enabled,
                      "sample_k": self.podtrace.sample_k,
                      "completed": self.podtrace.completed_total,
                      "live_incomplete": self.podtrace.live_incomplete,
                      "windows_rotated": self.podtrace.windows_rotated},
            "watch": self._watch_summary(),
            "gang": gang,
            "repair": (dict(self.repair_totals,
                            last=self._last_repair.as_dict())
                       if self._last_repair is not None
                       else dict(self.repair_totals)
                       if self.repair_totals["batches"] else None),
            "breaker": self.breaker.describe(),
            "solve_paths": dict(self.solve_paths),
            # partitioned mode (ISSUE 12): this pipeline's shard identity +
            # the absorbed cross-partition races; None standalone
            "partition": ({
                "index": self.partition_index,
                "nodes": self.cache.node_count(),
                "conflicts": self.partition_conflicts,
                "reroutes": self.partition_reroutes,
            } if self.partition_index is not None else None),
            # background rebalancer (ISSUE 17): fragmentation score +
            # migration/wave/abort totals; None until enable_rebalancer()
            "rebalance": (self.rebalancer.stats()
                          if self.rebalancer is not None else None),
            "bind_worker": {
                "restarts": self.bind_worker_restarts,
                "failures_logged": len(self.bind_failures),
                "failures_dropped": self.bind_failures_dropped,
            },
            # columnar pod-row store (ISSUE 15): rows/diverged/lazy-
            # materialization telemetry from the store this pipeline binds
            # into (None on the dict path) — the observable proof that the
            # steady state stays lazy (diverged grows with binds, while
            # materialized_total only moves when something actually reads
            # the rows)
            "store_columnar": (self.store.columnar_stats()
                               if hasattr(self.store, "columnar_stats")
                               else None),
            # cache rows (ISSUE 16): the scheduler-side half of the columnar
            # pipeline — rows live per steady-state placement, and
            # materialized_total only moves when a constrained batch / serial
            # fallback / conservation check forces object rows
            "cache_columnar": self.cache.columnar_stats(),
            "recorder": {"enabled": fr.enabled, "capacity": fr.capacity,
                         "records": len(fr),
                         "self_seconds": round(fr.self_seconds, 6)},
            # trace timeline (ISSUE 18): arm/drop counters so a full ring
            # is observable from /debug/schedstats and `ktl sched stats`
            "tracebuf": _tracebuf.status(),
            "stages": fr.stage_table(),
            # steady-state telemetry (ISSUE 13): the recent closed windows
            # (the live feed of `ktl sched top` and the windowed SLO keys)
            # plus the resource sampler's summary when one is attached
            "timeseries": {
                "enabled": self.timeseries.enabled,
                "window_s": self.timeseries.window_s,
                "capacity": self.timeseries.capacity,
                "windows_closed": self.timeseries.windows_closed,
                "self_seconds": round(self.timeseries.self_seconds, 6),
            },
            "windows": windows,
            "resource": (self.resource_sampler.summary()
                         if self.resource_sampler is not None else None),
            "last_batch": fr.last(),
        }

    def _register_window_probes(self) -> None:
        """Window-close probes (obs/timeseries.py): each runs ONCE per
        closed window — queue depth (O(tiers), no age scan), breaker state,
        watch-bus lag (pure read, no settlement), the partition's
        conflict/reroute counters, and the resource sampler's latest
        columns. Everything here is lazy: attributes constructed later in
        __init__ (breaker) or installed later (partition_index, sampler)
        resolve at fire time."""
        ts = self.timeseries
        ts.add_probe("queue", lambda: self.queue.depths())
        ts.add_probe("breaker", lambda: {"state": self.breaker.state})
        ts.add_probe("watch", lambda: self.store.watch_lag())
        ts.add_probe("partition", self._partition_window_probe)
        ts.add_probe("resource", self._resource_window_probe)
        # live zero-alloc gauge (ISSUE 16): per-window pod-object
        # materializations across the columnar pipeline (store rows + cache
        # rows). Steady state reads 0 — the end-to-end zero-object claim as
        # a live gauge, not only a bench assertion. One tap per window close
        # (HP001).
        self._alloc_probe_total: Optional[int] = None
        ts.add_probe("alloc", self._alloc_window_probe)

    def _alloc_window_probe(self) -> Optional[Dict]:
        total = 0
        seen = False
        getstats = getattr(self.store, "columnar_stats", None)
        if getstats is not None:
            st = getstats()
            if st is not None:
                total += int(st.get("materialized_total", 0))
                seen = True
        cm = getattr(self.cache, "columnar_materialized", None)
        if cm is not None:
            total += int(cm())
            seen = True
        if not seen:
            return None  # object-path pipeline: the gauge has no meaning
        prev = self._alloc_probe_total
        self._alloc_probe_total = total
        return {"pod_obj_allocs": total - prev if prev is not None else total,
                "materialized_total": total}

    def _partition_window_probe(self) -> Optional[Dict]:
        if self.partition_index is None:
            return None
        return {"index": self.partition_index,
                "conflicts": self.partition_conflicts,
                "reroutes": self.partition_reroutes}

    def _resource_window_probe(self) -> Optional[Dict]:
        s = self.resource_sampler
        if s is None:
            return None
        last = s.latest()
        if last is None:
            return None
        return {"rss_mb": last["rss_mb"],
                "alloc_blocks": last["alloc_blocks"],
                "gc_collections": last["gc"]["collections"],
                "gc_pause_s": last["gc"]["pause_s"],
                # cumulative sampler self-time at window close (difference
                # consecutive windows for the per-window overhead)
                "sampler_self_s": round(s.self_seconds, 6),
                "threads": {k: v["cpu_s"]
                            for k, v in last["threads"].items()}}

    def _thread_label(self, role: str) -> str:
        return (f"p{self.partition_index}-{role}"
                if self.partition_index is not None else role)

    def attach_resource_sampler(self, sampler) -> None:
        """Wire an obs/resource.py ResourceSampler: the sampler's latest
        columns join every closed window (the rss/alloc slope gates' feed),
        and this scheduler's threads register for per-thread CPU
        attribution — the loop thread on start(), the bind worker as it
        spawns, both immediately when already running."""
        self.resource_sampler = sampler
        if sampler is not None:
            if self._thread is not None:
                sampler.register_thread(self._thread_label("sched"),
                                        self._thread)
            if self._bind_worker is not None:
                sampler.register_thread(self._thread_label("bind"),
                                        self._bind_worker)

    def _watch_summary(self) -> Dict:
        """The store watch bus seen from this scheduler (ISSUE 9): settled
        commit->dequeue propagation plus subscriber counts and the worst
        delivered-RV lag — the "watch" section of sched_stats that `ktl
        sched stats` renders and watch_propagation_p99_s gates. One
        watch_telemetry() call (settles pending taps; O(subscribers))."""
        try:
            tel = self.store.watch_telemetry()
        except Exception as e:  # a wedged store must not 500 the endpoint
            return {"error": str(e)}
        subs = tel.get("subscribers") or []
        return {
            "subscribers": len(subs),
            "max_rv_lag": max((s.get("rv_lag", 0) for s in subs), default=0),
            "dropped": tel.get("dropped") or {},
            "propagation": tel.get("propagation") or {},
        }

    def _hard_pod_affinity_weight(self) -> int:
        for fw in self.profiles.values():
            for p in fw.plugins:
                if p.name == "InterPodAffinity":
                    return getattr(p, "hard_pod_affinity_weight", 1)
        return 1

    def _bind_one(self, qp: QueuedPodInfo, node_name: str, assumed,
                  async_mode: bool) -> None:
        try:
            self.store.bind(qp.pod.metadata.namespace, qp.pod.metadata.name, node_name)
            self.cache.finish_binding(assumed)
            if async_mode:
                with self._bind_err_lock:
                    self._bind_successes += 1
            else:
                self.scheduled_count += 1
        except Exception as e:
            self.cache.forget_pod(assumed)
            if self.gangs is not None:
                self.gangs.note_forgotten(assumed)
            if async_mode:
                # surfaced on the scheduling thread at the next drain; handling
                # failures re-enters the queue, which isn't bind-thread-safe
                with self._bind_err_lock:
                    self._bind_errors.append((qp, Status.error(str(e))))
            else:
                self._handle_failure(qp, Status.error(str(e)))

    def _ensure_bind_worker(self) -> None:
        if self._bind_worker is not None and not self._bind_worker.is_alive():
            # a hard-dead worker's in-flight chunks and task_done debt MUST
            # be recovered before a replacement starts: the new worker's
            # first cycle overwrites the shared _bind_inflight record,
            # destroying the evidence — the debt then leaks and flush_binds
            # wedges forever (found by the full-size ChaosChurn_20k rung:
            # the enqueue path won the race against the liveness drain)
            self._recover_dead_worker()
        if self._bind_worker is None:
            # the queue is BOUND at thread start: a crash resync swaps
            # self._bind_q for a fresh queue, and the old worker must keep
            # draining (and exiting on) the queue it was born with
            self._bind_worker = threading.Thread(
                target=self._bind_loop, args=(self._bind_q,), daemon=True)
            self._bind_worker.start()
            if self.resource_sampler is not None:
                # re-registering the label points the CPU column at the
                # replacement worker (a restart keeps one column)
                self.resource_sampler.register_thread(
                    self._thread_label("bind"), self._bind_worker)

    def _bind_loop(self, q: _queue.Queue) -> None:
        """SUPERVISED bind worker (ISSUE 6): _bind_cycle drains one pipelined
        sub-batch; an exception that escapes it (past _bind_batch's own
        error handling) no longer kills the worker silently — the supervisor
        counts the escape and continues, after _bind_cycle re-queued the
        in-flight chunk for ONE retry (a second escape fails its pods). An
        injected FaultKill is the deliberate exception: it is a hard thread
        death, recovered by the liveness check in _drain_bind_results."""
        while True:
            try:
                if self._bind_cycle(q):
                    return
            except FaultKill:
                # hard death by design: exit WITHOUT the cycle bookkeeping
                # (the in-flight chunk stays recorded, its task_done debt
                # unsettled) — exactly what a real thread-killing failure
                # leaves behind; the liveness check recovers both
                return
            except Exception:
                with self._bind_err_lock:
                    self.bind_worker_restarts += 1

    def _bind_cycle(self, q: _queue.Queue) -> bool:
        """One drain cycle: items queued at wake-up are merged only up to
        bind_chunk pods per store.bind_many + confirm cycle, so commit(N)
        runs while the scheduling thread works on solve(N+1) — chunk-granular
        overlap instead of one monolithic commit (the bind_wait stall the
        PR 3 stage table surfaced). Returns True on the shutdown sentinel.

        Bookkeeping contract: the merged batches are recorded in
        _bind_inflight BEFORE commit and cleared — with their task_done debt
        settled — on every handled path. Only a hard kill leaves them
        recorded, which is exactly what the dead-worker liveness check needs
        to re-queue them and unwedge flush_binds."""
        item = q.get()
        if item is None:
            q.task_done()
            return True
        batches = [item]  # each queue item is a LIST of bind triples
        merged = len(item)
        while merged < self.bind_chunk:
            try:
                nxt = q.get_nowait()
            except _queue.Empty:
                break
            if nxt is None:
                # shutdown requested mid-merge: put the sentinel back for
                # the NEXT cycle (settling our get) so this cycle's chunk
                # commits under the normal bookkeeping
                q.put(None)
                q.task_done()
                break
            batches.append(nxt)
            merged += len(nxt)
        with self._bind_err_lock:
            self._bind_inflight = batches
        handled = False
        try:
            if _chaos.ACTIVE is not None:
                _chaos.ACTIVE.fire("bind.worker")
            self._bind_batch([t for b in batches for t in b])
            handled = True
        except Exception:
            self._requeue_inflight(batches, q)
            handled = True
            raise  # the supervisor counts the escape
        finally:
            if handled:
                with self._bind_err_lock:
                    self._bind_inflight = []
                for _ in batches:
                    q.task_done()
            # BaseException (FaultKill): leave _bind_inflight recorded with
            # its task_done debt — _drain_bind_results settles both
        return False

    def _requeue_inflight(self, batches, q: _queue.Queue) -> None:
        """Give each escaped in-flight chunk ONE more trip through the bind
        queue; a chunk that already retried fails its pods through the
        normal bind-error path instead (requeue via _drain_bind_results) —
        a deterministic escape must not livelock the worker."""
        for b in batches:
            if isinstance(b, _RequeuedChunk):
                with self._bind_err_lock:
                    for qp, _node, assumed in b:
                        self.cache.forget_pod(assumed)
                        if self.gangs is not None:
                            self.gangs.note_forgotten(assumed)
                        self._bind_errors.append((qp, Status.error(
                            "bind worker failed twice on this chunk")))
            else:
                q.put(_RequeuedChunk(b))
        from ..server import metrics as m

        # pods, not chunks — the metric's unit across every requeue stage
        m.batch_retries_total.inc(sum(len(b) for b in batches),
                                  stage="worker", reason="escaped")

    def _check_bind_worker_alive(self) -> None:
        """Dead-worker liveness check (ISSUE 6 satellite), run every drain:
        a worker that died hard (FaultKill, MemoryError) with an empty bind
        queue used to stay dead — and its in-flight chunk's unmatched
        task_done debt hung flush_binds forever. Here: recover the stranded
        chunks + debt, and restart the worker if work remains."""
        w = self._bind_worker
        if w is None or w.is_alive():
            return
        self._recover_dead_worker()
        if self._bind_q.unfinished_tasks:
            self._ensure_bind_worker()

    def _recover_dead_worker(self) -> None:
        """Settle a hard-dead worker's estate — shared by the liveness drain
        and the enqueue path (whichever observes the death first): re-queue
        its in-flight chunks for the supervised retry, settle their
        unmatched task_done debt, count the restart, and clear the worker
        ref so _ensure_bind_worker starts a replacement. Runs only on the
        scheduling thread (both callers), so the estate is handed off
        exactly once."""
        with self._bind_err_lock:
            inflight, self._bind_inflight = self._bind_inflight, []
            self.bind_worker_restarts += 1
        self._bind_worker = None
        if inflight:
            self._requeue_inflight(inflight, self._bind_q)
            for _ in inflight:
                self._bind_q.task_done()  # the dead worker's unmatched gets

    def _bind_batch(self, items) -> None:
        t0 = time.perf_counter()
        try:
            with _span("sched.bind"):
                self._bind_batch_inner(items)
        finally:
            t1 = time.perf_counter()
            self.flightrec.add_outside("bind", t1 - t0)
            from ..server import metrics as m

            m.batch_stage_duration.observe(t1 - t0, "bind")
            # trace timeline (ISSUE 18): one slice per bind sub-batch on
            # the bind worker's track — overlap with the next solve is
            # visible as concurrent slices on p<i>-sched vs p<i>-bind
            if _tracebuf.ACTIVE is not None:
                _tracebuf.ACTIVE.note_span(
                    self._thread_label("bind"), "bind_chunk", t0, t1,
                    cat="bind", args={"pods": len(items)})
            self.flightrec.note_self_time(time.perf_counter() - t1)

    def _bind_batch_inner(self, items) -> None:
        triples = [(qp.pod.metadata.namespace, qp.pod.metadata.name, node)
                   for qp, node, _assumed in items]
        # chunked: each bind_many holds the store locks once; a single
        # 100k-bind hold would starve every other store consumer. A chunk
        # whose retries are exhausted fails ONLY its own pods — earlier
        # chunks already committed and must not be forgotten/requeued.
        errors = []
        for lo in range(0, len(triples), self.bind_chunk):
            chunk = triples[lo:lo + self.bind_chunk]
            exc = self._bind_chunk_with_retry(chunk, errors)
            if exc is not None:
                errors.extend((f"{ns}/{name}", str(exc))
                              for ns, name, _node in chunk)
        # pod tracer (scheduler/podtrace.py): ONE commit stamp for the whole
        # chunk (batch-boundary timestamps, no per-pod clocks); the confirm
        # stamp is read after the assume-confirm settles below
        pt = self.podtrace
        t_commit = self.clock.now() if pt is not None and pt.enabled else 0.0
        if not errors:
            # common case: whole sub-batch committed. On the coalesced
            # pipeline the assume-CONFIRM piggybacks right here (one cache
            # lock) instead of a later event re-ingest — the scheduler skips
            # its own origin-tagged MODIFIED batches entirely, removing the
            # old finish_binding ttl window AND the confirm stage from the
            # scheduling thread. Leftovers (assume expired, foreign rebind)
            # re-ingest on the scheduling thread at the next drain. The
            # per-pod pipeline (watch_coalesce=False, the parity oracle)
            # keeps the finish_binding + event-confirm flow byte-for-byte.
            if self.watch_coalesce:
                pairs = [(qp.pod.key, node) for qp, node, _a in items]
                leftover = self.cache.confirm_assumed_bulk(pairs)
                with self._bind_err_lock:
                    self._bind_successes += len(items)
                    if leftover:
                        self._bind_confirm_leftovers.extend(
                            items[i][2] for i in leftover)
            else:
                self.cache.finish_binding_bulk([a for _qp, _node, a in items])
                with self._bind_err_lock:
                    self._bind_successes += len(items)
            if pt is not None and pt.enabled:
                pt.chunk_bound(items, t_commit, self.clock.now())
            return
        errmap = dict(errors)
        confirm = []
        with self._bind_err_lock:
            for qp, node, assumed in items:
                msg = errmap.get(qp.pod.key)
                if msg is None:
                    if self.watch_coalesce:
                        confirm.append((qp.pod.key, node, assumed))
                    else:
                        self.cache.finish_binding(assumed)
                    self._bind_successes += 1
                else:
                    self.cache.forget_pod(assumed)
                    if self.gangs is not None:
                        self.gangs.note_forgotten(assumed)
                    self._bind_errors.append((qp, Status.error(msg)))
            if confirm:
                leftover = self.cache.confirm_assumed_bulk(
                    [(k, n) for k, n, _a in confirm])
                self._bind_confirm_leftovers.extend(
                    confirm[i][2] for i in leftover)
        if pt is not None and pt.enabled:
            # partial-failure chunk: failed pods are excluded from both the
            # latency distribution and the sampled stamps (they re-enter the
            # queue and bind later — the tracer sees that attempt instead)
            pt.chunk_bound(items, t_commit, self.clock.now(),
                           errkeys=frozenset(errmap))

    def _bind_chunk_with_retry(self, chunk, errors) -> Optional[Exception]:
        """One chunk's bind_many with transient-failure retry (ISSUE 6):
        an EXCEPTION from bind_many is infrastructure (the per-pod conflict
        errors come back in the error list and are never retried — a
        conflict is a fact, not a fault), so the chunk retries up to
        bind_retries times under exponential backoff with jitter before its
        pods are declared failed. Returns the final exception, or None on
        success. Runs on the bind worker with NO lock held — the sleeps
        stall only the overlapped commit, never the scheduling thread."""
        last: Optional[Exception] = None
        for attempt in range(self.bind_retries + 1):
            if attempt:
                from ..server import metrics as m

                m.batch_retries_total.inc(stage="bind", reason="transient")
                delay = (self.bind_retry_base_s * (2 ** (attempt - 1))
                         * (1.0 + _random.random()))
                time.sleep(delay)
            try:
                _bound, errs = self.store.bind_many(
                    chunk, origin=self._bind_origin)
                errors.extend(errs)
                return None
            except Exception as e:
                last = e
        return last

    def _drain_bind_results(self) -> None:
        """Fold completed async binds into counters and re-handle failures on
        the scheduling thread (handleBindingCycleError -> requeue). Does NOT
        wait for in-flight binds — callable every cycle under sustained load.
        Failures are requeued AND recorded in bind_failures so callers of
        schedule_batch can observe them (take_bind_failures). Also runs the
        dead-worker liveness check: called every schedule_batch cycle, so a
        hard-killed worker is detected within one cycle even when the bind
        queue is empty (ISSUE 6 satellite)."""
        if self.pipeline_binds:
            self._check_bind_worker_alive()
        with self._bind_err_lock:
            done, self._bind_successes = self._bind_successes, 0
            errs, self._bind_errors = self._bind_errors, []
            leftovers, self._bind_confirm_leftovers = (
                self._bind_confirm_leftovers, [])
        self.scheduled_count += done
        for pod in leftovers:
            # worker-side confirm missed (assume expired / foreign write got
            # in first): re-read the COMMITTED object — the assume-time clone
            # is stale (pre-bind rv, possibly older labels), and the pod may
            # have been deleted since (re-ingesting the clone would resurrect
            # it in the cache; the event-stream confirm of old couldn't,
            # because it ran in rv order) — then take the full ingest path,
            # exactly like a foreign MODIFIED, correcting the cache
            try:
                cur = self.store.get("pods", pod.key)
            except NotFoundError:
                continue  # deleted since the bind: nothing left to account
            self._handle_pod(MODIFIED, cur)
        if errs:
            self.flightrec.note_bind_failures(
                [(qp.pod.key, status.message()) for qp, status in errs])
        log = self.bind_failures
        csink = self.conflict_sink
        for qp, status in errs:
            msg = status.message()
            if csink is not None and is_bind_conflict(msg):
                # lost cross-partition bind race (ISSUE 12): the conflict is
                # a FACT — the pod is bound, the store decided the winner —
                # so this pipeline drops it (the assume was already
                # forgotten on the error path) and the coordinator counts
                # the absorbed race. Requeueing would schedule a bound pod.
                self.partition_conflicts += 1
                csink(qp, msg)
                continue
            if len(log) == log.maxlen:
                # bounded (ISSUE 6 satellite): a caller that never drains
                # must not leak under sustained bind faults — evict oldest,
                # count the drop so the loss is observable
                self.bind_failures_dropped += 1
            log.append((qp.pod.key, msg))
            self._handle_failure(qp, status)

    def take_bind_failures(self) -> List:
        """Drain the (pod key, error message) log of asynchronous bind
        failures observed since the last call. The pods themselves were
        already requeued via the normal failure path; this surfaces WHAT
        failed to callers of schedule_batch/flush_binds, which otherwise
        only ever see success counts. Bounded: under sustained faults with
        no drainer the log holds the most recent BIND_FAILURE_LOG_CAP
        entries (bind_failures_dropped counts the evictions)."""
        out = list(self.bind_failures)
        self.bind_failures.clear()
        return out

    def flush_binds(self) -> None:
        """Wait for queued store.bind writes, then drain results. The wait is
        recorded as the "bind_wait" stage — the scheduling thread's stall on
        in-flight binds, the residual the stage table needs to explain wall
        time when binds don't fully overlap the next solve.

        The wait is LIVENESS-AWARE (ISSUE 6): a plain Queue.join() hung
        forever when the worker died hard mid-chunk (the chunk's task_done
        debt was never settled). Here the wait wakes on task_done as before
        but re-checks the worker between naps, so a dead worker is replaced
        and its stranded chunk re-queued instead of wedging the flush."""
        t0 = time.perf_counter()
        if self._bind_worker is not None:
            q = self._bind_q
            with _span("sched.bind_wait"):
                while True:
                    with q.all_tasks_done:
                        if not q.unfinished_tasks:
                            break
                        q.all_tasks_done.wait(timeout=0.05)
                    self._check_bind_worker_alive()
        self.flightrec.add_outside("bind_wait", time.perf_counter() - t0)
        self._drain_bind_results()

    def sweep_expired_assumes(self) -> List[str]:
        """Base sweep plus the gang preemptor's parked-gang deadline: a
        cover whose victim deletions stalled releases its gang back to the
        normal retry ladder (scheduler/gangpreempt.py) — both run from the
        same idle loops."""
        expired = super().sweep_expired_assumes()
        if self.gangpreempt is not None:
            self.gangpreempt.sweep(self.clock.now())
        return expired

    def resync_from_store(self) -> Dict[str, int]:
        """Crash resync (ISSUE 6): rebuild ALL scheduler state from the
        store, as a restarted scheduler process would — proving the store is
        the single source of truth. Bound pods re-enter the cache from the
        LIST, pending pods re-enter the queue fresh (no attempt/backoff
        memory), stale assumes are simply gone (the fresh cache never knew
        them), and the bind pipeline restarts empty.

        In-flight binds are flushed first: a real crash would lose them
        in-process, but their pods are either committed (the LIST sees them
        bound) or still pending (the LIST re-queues them) — the store
        decides, which is the whole point. Flushing just makes the
        simulation deterministic. Returns {nodes, bound, pending,
        dropped_assumes}."""
        self.flush_binds()
        dropped = self.cache.assumed_count()
        # abandon the bind pipeline: sentinel the old worker to death on the
        # queue it was born with (it drains nothing — flush emptied it) and
        # start over with a fresh queue
        if self._bind_worker is not None:
            self._bind_q.put(None)
        self._bind_q = _queue.Queue()
        self._bind_worker = None
        with self._bind_err_lock:
            self._bind_inflight = []
            self._bind_errors = []
            self._bind_successes = 0
            self._bind_confirm_leftovers = []
        self._tensor_cache = TensorCache()
        if self.gangpreempt is not None:
            # parked-gang state is queue state; the fresh LIST re-admits
            # every pending pod, so in-flight cover tracking is stale
            self.gangpreempt.reset()
        counts = self._rebuild_from_store(preserve_queue=False)
        counts["dropped_assumes"] = dropped
        return counts

    def stop(self) -> None:
        """Stop the loop/watch like the base class, AND release the bind
        worker: parked in `q.get()` it would otherwise pin this scheduler's
        entire object graph (cache, store refs, 100k-pod heaps) for the
        process lifetime — the leak the partitioned A/B bench and
        `_absorb_dead`'s corpse.stop() both hit. Items queued before the
        sentinel still commit (FIFO); a later start() gets a fresh queue."""
        super().stop()
        if self._bind_worker is not None:
            self._bind_q.put(None)
            self._bind_q = _queue.Queue()
            self._bind_worker = None

    def _serial_one(self, qp: QueuedPodInfo) -> None:
        result = self.schedule_pod(qp.pod)
        if not result.suggested_host:
            self._maybe_preempt(qp, result)
            self._handle_failure(qp, result.status, result.failed_nodes)
            return
        # Full commit chain (Reserve/Permit/PreBind/PostBind) — fallback pods
        # (volumes, inter-pod affinity) depend on these extension points.
        self._commit_cycle(qp, result)

    def start(self) -> None:
        """Background loop: batch solve instead of one-pod cycles."""
        if self._thread is not None:
            return
        self._stop.clear()

        def loop():
            while not self._stop.is_set():
                handled = self.schedule_batch(timeout=0.0)
                # drain async-bind outcomes every cycle (bind failures must
                # requeue even under sustained load), full flush only on idle
                self._drain_bind_results()
                if handled == 0:
                    self.flush_binds()
                    self.pump_events()
                    self.queue.flush_backoff_completed()
                    self.queue.flush_unschedulable_left_over()
                    self.sweep_expired_assumes()
                    self._stop.wait(0.05)

        self._thread = threading.Thread(target=loop, daemon=True)
        self._thread.start()
        if self.resource_sampler is not None:
            self.resource_sampler.register_thread(
                self._thread_label("sched"), self._thread)

    def enable_rebalancer(self, **kwargs):
        """Attach a background Rebalancer (scheduler/rebalance.py, ISSUE 17)
        to this pipeline; kwargs pass through to its constructor. The
        run_until_idle quiesce path paces it via maybe_cycle(), and
        sched_stats()["rebalance"] publishes its totals. Returns it."""
        from .rebalance import Rebalancer

        self.rebalancer = Rebalancer(self, **kwargs)
        return self.rebalancer

    def run_until_idle(self, max_cycles: int = 10_000) -> int:
        n = 0
        while n < max_cycles:
            if self.schedule_batch(timeout=0.0) == 0:
                # quiesce: flush in-flight binds (may requeue failures), then
                # drain events + expired assumes before declaring idle
                self.flush_binds()
                self.pump_events()
                self.sweep_expired_assumes()
                if self.schedule_batch(timeout=0.0) == 0:
                    # idle: let the rebalancer take a paced defrag cycle —
                    # migrations emit create/delete events, so loop once
                    # more to ingest them before declaring idle for real
                    if self.rebalancer is not None:
                        r = self.rebalancer.maybe_cycle()
                        if r is not None and r.get("migrations"):
                            n += 1
                            continue
                    break
            n += 1
        self.flush_binds()
        return n


def _subset_batch(batch, idx):
    """View of a PodBatchTensors restricted to pod rows idx (class tables shared)."""
    import dataclasses

    return dataclasses.replace(
        batch,
        pods=[batch.pods[i] for i in idx],
        class_of_pod=batch.class_of_pod[idx],
        req=batch.req[idx],
        req_nz=batch.req_nz[idx],
        balanced_active=batch.balanced_active[idx],
        raw_req=None if batch.raw_req is None else batch.raw_req[idx],
        raw_req_nz=None if batch.raw_req_nz is None else batch.raw_req_nz[idx],
        gang_of_pod=(None if batch.gang_of_pod is None
                     else batch.gang_of_pod[idx]),
        gang_rank=(None if batch.gang_rank is None
                   else batch.gang_rank[idx]),
    )
