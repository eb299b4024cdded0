"""Faults planted in the running program, to show that the reference check
fails a run that breaks a guarantee. The benchmark's own runs plant none;
`variant.py` and `tests/test_controls.py` do.

  pile     the solver's answer altered where it is produced: every placed
           pod goes to the cluster's first node (allocatable broken)
  firstfit the same, each placed pod to the first node, in the cluster's
           order, that still has room for it in the scheduler's view: every
           placement fits, and least-allocated scoring is dropped
  half     half of each batch left out of the answer (the pods are refused
           as unschedulable while nodes have room)
  nobind   the bind path reports success and commits nothing (the store's
           state is returned unchanged)
"""

from __future__ import annotations

import numpy as np

FAULTS = ("pile", "firstfit", "half", "nobind")


def _wrap_solver(sched, alter) -> None:
    """alter(assignment, cluster, batch) edits the solver's answer in place."""
    inner = sched._solve_device

    def solve(solver, cluster, batch, sub, *a, **kw):
        out = np.asarray(inner(solver, cluster, batch, sub, *a, **kw)).copy()
        alter(out, cluster, sub)
        return out

    sched._solve_device = solve


def _pile(a, _cluster, _batch):
    a[a >= 0] = 0


def _firstfit(a, cluster, batch):
    alloc = np.asarray(cluster.alloc, dtype=np.int64)
    used = np.asarray(cluster.used, dtype=np.int64).copy()
    count = np.asarray(cluster.pod_count, dtype=np.int64).copy()
    max_pods = np.asarray(cluster.max_pods, dtype=np.int64)
    req = np.asarray(batch.req, dtype=np.int64)
    j = 0
    for i in np.nonzero(a >= 0)[0]:
        while j < len(count) and not (count[j] < max_pods[j]
                                      and (used[j] + req[i] <= alloc[j]).all()):
            j += 1
        if j == len(count):
            a[i] = -1
            continue
        a[i] = j
        used[j] += req[i]
        count[j] += 1


def _half(a, _cluster, _batch):
    a[::2] = -1


def apply(fault: str, dep) -> None:
    sched = dep.sched
    if fault == "pile":
        _wrap_solver(sched, _pile)
    elif fault == "firstfit":
        _wrap_solver(sched, _firstfit)
    elif fault == "half":
        _wrap_solver(sched, _half)
    elif fault == "nobind":
        def bind_many(bindings, origin=None):
            return len(list(bindings)), []

        dep.store.bind_many = bind_many
    else:
        raise ValueError(f"unknown fault {fault!r} (have {FAULTS})")
