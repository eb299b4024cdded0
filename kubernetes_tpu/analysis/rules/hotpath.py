"""HP001 — per-pod instrumentation inside batch loops of the hot scheduler
files (scheduler/batch.py and scheduler/podtrace.py) and the controller
reconcile loops (controllers/base.py, ISSUE 9).

The flight recorder's contract (scheduler/flightrec.py, ROADMAP
instrumentation budget <2%) is "per BATCH, never per pod": stage marks,
histogram observations, recorder narration, and logging happen a handful of
times per schedule_batch call. A perf_counter read or metrics observe inside
a loop over the pod batch multiplies that by 100k and the budget is gone —
exactly the regression class tier-1's behavioral tests cannot see.

Batch loops are identified by the iterable's root name (the module's
pod-scale locals: qps, to_bind, items, rejected, ...), looking through
enumerate/zip/sorted/reversed wrappers, `.tolist()` and 1/2-arg `range(len(
...))`. Three-arg `range(0, len(x), chunk)` loops are CHUNK loops (pods /
bind_chunk iterations) and are exempt — per-chunk timing is the recorder's
own design.

Sampled-tracing exception (ISSUE 7): the pod tracer's lifecycle stamps ARE
per-pod work — legal ONLY behind a membership check against the sampled set
(`if key in self._sampled: span.stamp(...)`), which bounds the paying
population to K reservoir slots while unsampled pods pay one set lookup.
Instrumentation calls lexically inside an `if` whose test contains an
`x in <something named *sampled*>` comparison are therefore allowed; the
same call unguarded is a finding.

Reconcile loops (ISSUE 9): controllers/base.py drains its workqueue
(`for key in keys:`) and its watch buffer (`for ev in <watch>.drain(...):`)
at event scale — a 10k-object relist marks 10k keys per drain. The
ReconcileRecorder taps are per LOOP (two perf_counter reads around the
whole drain, one recorder.loop()/pump() call); per-key instrumentation
inside those loops is the same multiplier bug as per-pod stamping in
batch.py. `.drain(...)` iterables are recognized as event-scale regardless
of the receiver expression.

Steady-state telemetry (ISSUE 13): obs/timeseries.py and obs/resource.py
are hot files too — their contract is taps per WINDOW close / per SAMPLE
tick, never per pod. A note_batch/note_stage call is one tap per batch by
design; anything instrumenting inside a pod-scale loop of these files
(someone feeding the window per pod "for accuracy") is the same 100k
multiplier the flight recorder's budget forbids.

Trace timeline (ISSUE 18): obs/tracebuf.py and obs/critpath.py carry the
same contract — trace-buffer taps (note_batch/note_span/instant/counter)
are per batch / per chunk / per cycle / per window, NEVER per pod outside
a sampled-set membership check, and the analyzers iterate the ≤K-sampled
span set only. A `tracebuf.ACTIVE.instant(...)` inside a pod-scale loop
would turn the <1% armed budget into a per-pod ring append at 100k scale.

Profiler spans (obs/recorder.py): StageClock boundaries (enter/drop/finish),
solve parts (`with part(...)`) and the outside stages' spans (`with
span(...)`, jax.profiler.TraceAnnotation) are per batch, per bind chunk or
per solver group. One inside a pod-scale loop is the same multiplier.
"""

from __future__ import annotations

import ast
import re
from typing import List, Optional

from ..findings import Finding
from ..index import FuncInfo, ProjectIndex, render_chain

HOT_FILE_SUFFIXES = ("scheduler/batch.py", "scheduler/podtrace.py",
                     "controllers/base.py", "obs/timeseries.py",
                     "obs/resource.py", "obs/tracebuf.py",
                     "obs/critpath.py")

POD_SCALE = re.compile(
    r"^(qps|pods|pending|items|to_bind|bind_rows|bind_nodes|bind_gang|"
    r"triples|bindings|prepared|rejected|members|pairs|leftovers|errs|"
    r"errors|victims|device_idx|fallback_idx|assign_list|assignment|"
    r"events|batch|chunk|keys)$")

INSTRUMENTATION_CALLS = {"observe", "observe_many", "inc", "set", "mark",
                         "record", "step", "stamp", "add_outside",
                         "note_self_time", "event", "log", "info", "warning",
                         "debug", "error", "exception",
                         # trace-buffer taps (obs/tracebuf.py, ISSUE 18)
                         "instant", "counter", "note_span", "note_batch",
                         # StageClock boundaries and profiler spans
                         # (obs/recorder.py)
                         "enter", "drop", "finish", "span", "part",
                         "TraceAnnotation", "TraceMe"}
# the same taps called by bare name (`with _span("sched.bind"):`,
# `with part("solve.readback"):`, `TraceAnnotation(...)`)
_SPAN_NAMES = re.compile(r"^_?(span|part|TraceAnnotation|TraceMe)$")
_METRICY = re.compile(r"^(m|metrics|fr|flightrec|clock|trace|recorder|"
                      r"logger|logging|log|sp|span|spans|tracer|podtrace|"
                      r"pt|latency|tracebuf|_tracebuf|tb|buf|ACTIVE|"
                      r"profiler|_recorder)$")

# the membership guard that legalizes per-pod stamping: any name segment of
# the `in` comparator matching this (self._sampled, sampled, sampled_set)
_SAMPLED = re.compile(r"sampled")

# terminal-path helpers (failure/requeue/rollback/serial-fallback handlers)
# are exempt from the interprocedural form: every pod on those paths owes a
# terminal status by contract, so per-pod narration there is the design,
# not the multiplier bug
_TERMINAL_PATH = re.compile(
    r"fail|error|serial|fallback|reject|requeue|veto|evict|preempt|"
    r"rollback|cancel")

# how deep the via-call-chain form follows hot-file helpers
_VIA_DEPTH = 3


def _root_name(expr: ast.AST) -> Optional[str]:
    node = expr
    while True:
        if isinstance(node, ast.Attribute):
            node = node.value
        elif isinstance(node, ast.Subscript):
            node = node.value
        elif isinstance(node, ast.Call):
            f = node.func
            # look through .tolist()/.items()/.values() etc
            if isinstance(f, ast.Attribute):
                if f.attr == "drain":
                    # a watch-buffer drain is event-scale whatever the
                    # receiver is called (self._watch.drain(n), w.drain())
                    return "events"
                node = f.value
            elif isinstance(f, ast.Name) and f.id in (
                    "enumerate", "zip", "sorted", "reversed", "list",
                    "tuple"):
                if not node.args:
                    return None
                node = node.args[0]
            elif isinstance(f, ast.Name) and f.id == "range":
                if len(node.args) >= 3:
                    return None  # chunk loop: range(lo, len(x), step)
                node = node.args[-1] if node.args else None
                if node is None:
                    return None
            else:
                return None
        elif isinstance(node, ast.Name):
            return node.id
        else:
            return None


def _name_segments(node: ast.AST) -> List[str]:
    segs: List[str] = []
    while isinstance(node, ast.Attribute):
        segs.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        segs.append(node.id)
    return segs


def _is_pod_scale_loop(loop: ast.For) -> bool:
    root = _root_name(loop.iter)
    return root is not None and bool(POD_SCALE.match(root))


def _has_sampled_guard(test: ast.AST) -> bool:
    """True when the if-test contains `x in <...sampled...>` — the
    membership check that bounds per-pod stamping to the K-slot sample."""
    for node in ast.walk(test):
        if not isinstance(node, ast.Compare):
            continue
        for op, comp in zip(node.ops, node.comparators):
            if isinstance(op, ast.In) and any(
                    _SAMPLED.search(s) for s in _name_segments(comp)):
                return True
    return False


def _instrumentation_desc(call: ast.Call) -> Optional[str]:
    f = call.func
    if isinstance(f, ast.Attribute):
        if f.attr == "perf_counter":
            return "time.perf_counter()"
        if f.attr in INSTRUMENTATION_CALLS:
            # receiver chain must look metric/recorder/logger/tracer-ish;
            # plain container .add()/.update() etc. are data structure ops
            segs = _name_segments(f.value)
            if any(_METRICY.match(s) for s in segs):
                return f"instrumentation call .{f.attr}() on " \
                       f"'{segs[-1]}...'"
    elif isinstance(f, ast.Name):
        if f.id == "perf_counter":
            return "perf_counter()"
        if f.id == "Trace":
            return "Trace() construction"
        if _SPAN_NAMES.match(f.id):
            return f"profiler span {f.id}()"
        if f.id == "print":
            return "print()"
    return None


def _scan_loop_body(node: ast.AST, guarded: bool, hits: List) -> None:
    """Collect unguarded instrumentation calls, tracking sampled-set guards:
    descending into an `if <... in ...sampled...>` body flips guarded on;
    the orelse branch keeps the surrounding state."""
    if isinstance(node, ast.If) and _has_sampled_guard(node.test):
        for child in node.body:
            _scan_loop_body(child, True, hits)
        for child in node.orelse:
            _scan_loop_body(child, guarded, hits)
        return
    if isinstance(node, ast.Call) and not guarded:
        desc = _instrumentation_desc(node)
        if desc is not None:
            hits.append((node, desc))
    for child in ast.iter_child_nodes(node):
        _scan_loop_body(child, guarded, hits)


def _scan_loop_calls(node: ast.AST, guarded: bool, calls: List) -> None:
    """Collect the Call nodes in a loop body with the sampled-guard state
    at each site (guarded calls are legal whatever their callee does)."""
    if isinstance(node, ast.If) and _has_sampled_guard(node.test):
        for child in node.body:
            _scan_loop_calls(child, True, calls)
        for child in node.orelse:
            _scan_loop_calls(child, guarded, calls)
        return
    if isinstance(node, ast.Call) and not guarded:
        calls.append(node)
    for child in ast.iter_child_nodes(node):
        _scan_loop_calls(child, guarded, calls)


def _func_instrumentation(info: FuncInfo) -> List:
    """Unguarded instrumentation calls anywhere in a function body (the
    sampled-set guard exception applies exactly as in the loop scan)."""
    hits: List = []
    for stmt in info.node.body:
        _scan_loop_body(stmt, False, hits)
    return hits


def check(index: ProjectIndex) -> List[Finding]:
    findings: List[Finding] = []
    hot_files = []
    hot_infos = set()
    for fi in index.files:
        norm = fi.path.replace("\\", "/")
        if any(norm.endswith(sfx) for sfx in HOT_FILE_SUFFIXES):
            hot_files.append(fi)
            hot_infos.update(fi.functions)

    def _follow(_caller, _call, callee):
        return callee in hot_infos and not _TERMINAL_PATH.search(callee.name)

    for fi in hot_files:
        for info in fi.functions:
            for loop in ast.walk(info.node):
                if not isinstance(loop, ast.For) or \
                        not _is_pod_scale_loop(loop):
                    continue
                hits: List = []
                # the iterable expression runs per pod too (a clock.mark()
                # in a sort key multiplies just like one in the body)
                _scan_loop_body(loop.iter, False, hits)
                for stmt in loop.body + loop.orelse:
                    _scan_loop_body(stmt, False, hits)
                for node, desc in hits:
                    findings.append(Finding(
                        "HP001", fi.rel, node.lineno,
                        f"{info.qualname}: {desc} inside a pod-scale batch "
                        "loop",
                        hint="instrument per BATCH (StageClock marks / one "
                             "flight record), never per pod — or guard the "
                             "stamp behind the sampled-set membership check "
                             "(`if key in ...sampled...:`); see "
                             "scheduler/flightrec.py + scheduler/podtrace.py"))

                # interprocedural form (ISSUE 20): an unguarded call from
                # the pod-scale loop into a hot-file helper that instruments
                # unconditionally is the same multiplier, one hop removed
                calls: List = []
                _scan_loop_calls(loop.iter, False, calls)
                for stmt in loop.body + loop.orelse:
                    _scan_loop_calls(stmt, False, calls)
                reported = set()
                for call in calls:
                    callee = index.resolve_call(fi, info, call)
                    if callee is None or not _follow(info, call, callee):
                        continue
                    offender = chain = None
                    if _func_instrumentation(callee):
                        offender, chain = callee, [info, callee]
                    else:
                        reached = index.callgraph.reachable_from(
                            [callee], depth=_VIA_DEPTH, follow=_follow)
                        for f2, ch in sorted(
                                reached.items(),
                                key=lambda kv: len(kv[1])):
                            if _func_instrumentation(f2):
                                offender, chain = f2, [info] + ch
                                break
                    if offender is None or call.lineno in reported:
                        continue
                    reported.add(call.lineno)
                    ihits = _func_instrumentation(offender)
                    findings.append(Finding(
                        "HP001", fi.rel, call.lineno,
                        f"{info.qualname}: per-pod call reaches {ihits[0][1]}"
                        f" in {offender.qualname} via call chain "
                        f"{render_chain(chain)} — instrumentation one helper"
                        " deep still multiplies per pod",
                        hint="instrument per BATCH, or guard the call behind"
                             " the sampled-set membership check; terminal-"
                             "path helpers (fail/requeue/serial) are exempt"
                             " by name"))
    return findings
