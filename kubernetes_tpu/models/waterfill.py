"""Water-filling batch solver — the fast path for constraint-light batches.

Greedy scheduling of identical pods is a water-filling process: each placement
takes the current-best node, whose score then decreases. For a group of
identical pods (same equivalence class AND same resource vector), the j-th
placement on node n has a computable marginal score s[n, j] — so the whole
greedy sequence collapses into ONE top-k over the [N, J] marginal-score matrix
instead of P sequential steps. This replaces the per-pod loop with a handful of
fully-parallel device ops: the MXU/VPU-friendly formulation of
prioritizeNodes() (reference: schedule_one.go:754).

Exactness: scores are evaluated against group-start normalization and made
monotone by a running cummin, so selections have the prefix property (if slot
(n, j) is chosen, all (n, i<j) are too). For score compositions that are
monotone per node (LeastAllocated + static scores — the SchedulingBasic /
NodeAffinity / Taint workloads), this equals the serial greedy assignment
*counts* per node; BalancedAllocation's non-monotone hump is handled by the
cummin (pessimistic, may diverge from serial by small score-epsilon choices).
Filter correctness is exact: a selected slot always fits.

Batches containing PodTopologySpread or InterPodAffinity constraints are routed
to the exact scan solver by the driver (solver='auto').
"""

from __future__ import annotations

import functools
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.solver import (
    SolverInputs,
    default_normalize,
    INT_MIN,
)
from ..obs.recorder import part
from ..scheduler.framework import MAX_NODE_SCORE


def bucket_j_max(max_pods, pod_count, n: int, max_slots: int,
                 cap_hint: Optional[int] = None) -> Optional[int]:
    """Pow2-bucketed per-node slot depth for the waterfill sort key.

    j_max must cover every node's remaining pod headroom, or schedulable pods
    would be silently clipped; the int32 sort key bounds total slots at
    `max_slots` (max_total_score * slots < 2^31 — each caller budgets its own
    score ceiling). Derived from STATIC capacity (max_pods) when it fits:
    headroom shrinks as the cluster fills and a headroom-derived bucket would
    recompile at every power-of-two boundary — each mid-run XLA compile costs
    tens of seconds on TPU. Only when the static bound blows the int32 key
    range does the tighter dynamic headroom (then a raw, unbucketed one) come
    in. cap_hint (the repair path's largest group size, itself pow2-bucketed
    by the shift below) tightens the depth when no group can ever fill a
    node. Returns None when the problem shape exceeds the key range entirely
    (callers fall back to the scan solver)."""
    cap = max(1, int(np.asarray(max_pods).max(initial=1)))
    if cap_hint is not None:
        cap = min(cap, max(1, int(cap_hint)))
    j_max = 1 << (cap - 1).bit_length()
    if n * j_max > max_slots:
        # documented last resort (docstring above): when the static pow2
        # bucket blows the int32 sort-key range, the raw dynamic headroom
        # keys the jit — recompiles are accepted there because the
        # alternative is no fast path at all
        headroom = max(1, int(np.asarray(max_pods - pod_count).max(initial=1)))
        if cap_hint is not None:
            headroom = min(headroom, max(1, int(cap_hint)))
        j_max = 1 << (headroom - 1).bit_length()
        if n * j_max > max_slots:
            if n * headroom > max_slots:
                return None
            j_max = headroom
    return j_max


@functools.partial(jax.jit, static_argnames=("j_max", "k_slots", "has_gang"))
def waterfill_group(
    alloc, used, used_nz, pod_count, max_pods,
    filter_ok_row, port_conflict_row, has_port,
    napref_row, has_napref, taint_row, img_row,
    req, req_nz, bal_active, group_size,
    j_max: int, k_slots: int,
    gang_row=None, has_gang: bool = False,
):
    """Place `group_size` (dynamic, <= k_slots) identical pods. k_slots is the
    static top-k width — bucketed to powers of two by the caller so batch-size
    changes don't recompile. Returns (k_per_node [N] int32, placement node ids
    [k_slots] int32 in greedy order, -1 beyond group_size)."""
    n = alloc.shape[0]
    # J_n: how many of this pod fit on node n right now
    free = alloc - used
    with_req = jnp.where(req[None, :] > 0, free // jnp.maximum(req[None, :], 1), j_max)
    j_cap = jnp.min(with_req, axis=1).astype(jnp.int32)
    j_cap = jnp.minimum(j_cap, max_pods - pod_count)
    j_cap = jnp.where(filter_ok_row, j_cap, 0)
    # a class with host ports can hold at most one pod per node, and zero where
    # the port is already taken
    j_cap = jnp.where(has_port, jnp.where(port_conflict_row, 0, jnp.minimum(j_cap, 1)), j_cap)
    j_cap = jnp.clip(j_cap, 0, j_max)

    # static (per-node) score components, normalized over the group-start
    # feasible set
    feas0 = j_cap > 0
    napref = jnp.where(has_napref, default_normalize(napref_row, feas0, reverse=False), 0)
    taint = default_normalize(taint_row, feas0, reverse=True)
    static = 2 * napref + 3 * taint + img_row  # int32 [N]
    if has_gang:
        # gang slice-packing bonus (scheduler/gang.py) — static per node like
        # img_row; the caller's slot guard budgets the extra score range
        static = static + gang_row

    # dynamic components as a function of j = pods already added (0..j_max-1),
    # via the SAME formula helpers the scan solver uses (one source of truth
    # for score parity), vmapped over the j axis
    from ..ops.solver import balanced_score, least_allocated_score

    js = jnp.arange(j_max, dtype=jnp.int32)  # [J]
    alloc2 = alloc[:, :2]  # cpu, memory — the configured scoring resources

    def at_j(j):
        least_j = least_allocated_score(alloc2, used_nz[:, :2] + j * req_nz[None, :2],
                                        req_nz[:2])
        bal_j = balanced_score(alloc2, used[:, :2] + j * req[None, :2], req[:2], bal_active)
        return least_j + bal_j

    score = jax.vmap(at_j)(js).T + static[:, None]  # [N, J]
    # prefix property: make marginal scores non-increasing in j
    score = jax.lax.associative_scan(jnp.minimum, score, axis=1)
    # mask slots beyond capacity
    score = jnp.where(js[None, :] < j_cap[:, None], score, INT_MIN)

    # greedy order = sort by (score desc, node asc, j asc). Encoded into one
    # int32 sort key: key = score * (n*j_max+1) - slot_rank. Valid while
    # max_score * slots < 2^31 — i.e. up to ~3M slots (scores are <= ~700);
    # callers cap j_max / shard nodes beyond that.
    slots = n * j_max
    flat_score = score.reshape(-1)
    # row-major flat index IS the (node asc, j asc) tie-break rank
    slot_rank = jnp.arange(slots, dtype=jnp.int32)
    sentinel = jnp.int32(-(2**31) + 1)
    key = flat_score * (slots + 1) - slot_rank
    key = jnp.where(flat_score <= INT_MIN, sentinel, key)
    top_keys, top_idx = jax.lax.top_k(key, k_slots)
    chosen = (top_keys > sentinel) & (jnp.arange(k_slots) < group_size)
    chosen_nodes = jnp.where(chosen, (top_idx // j_max).astype(jnp.int32), -1)

    k_per_node = jax.ops.segment_sum(
        chosen.astype(jnp.int32),
        jnp.where(chosen, top_idx // j_max, n).astype(jnp.int32),
        num_segments=n + 1,
    )[:n]
    return k_per_node, chosen_nodes


def waterfill_solve(inp: SolverInputs, groups: List[Tuple[np.ndarray, int]]):
    """Solve a batch as a sequence of identical-pod groups (few device calls).

    groups: list of (member_pod_indices (queue-ordered), class_id). Produces
    assignment[P] int32 like greedy_scan_solve, or None when the problem shape
    exceeds the fast path's int32 sort-key range (caller falls back to scan).
    """
    p = inp.req.shape[0]
    n = inp.alloc.shape[0]
    has_gang = inp.gang_bonus is not None
    # slot budget (bucket_j_max): max_total_score 800 * slots < 2^31 bounds
    # slots at ~2.6M; gang batches add GANG_SLICE_BONUS to the score range,
    # so their slot cap tightens to ~2.3M
    max_slots = 2_300_000 if has_gang else 2_600_000
    # host waits on device results are the solve stage's readback part
    # (obs/recorder.py; a no-op outside a batch's solve stage)
    with part("solve.readback"):
        j_max = bucket_j_max(inp.max_pods, inp.pod_count, n, max_slots)
    if j_max is None:
        return None
    assignment = np.full(p, -1, dtype=np.int32)
    used = inp.used
    used_nz = inp.used_nz
    pod_count = inp.pod_count
    port_taken = inp.node_ports

    for members, cls in groups:
        pi0 = int(members[0])
        with part("solve.readback"):
            has_port = bool(np.asarray(inp.class_ports[cls]).any())
        port_conflict = jnp.any(port_taken & inp.class_ports[cls][None, :], axis=1)
        # pow2 bucket keeps the jit key stable across batch sizes; never wider
        # than the slot count (top_k requires k <= size). Floored at 256 so
        # trickles of small batches (requeues, churn) share ONE compiled shape
        # instead of compiling per power of two.
        k_slots = min(1 << (len(members) - 1).bit_length(), n * j_max)
        k_slots = max(k_slots, min(256, n * j_max))
        k_per_node, chosen_nodes = waterfill_group(
            inp.alloc, used, used_nz, pod_count, inp.max_pods,
            inp.filter_ok[cls], port_conflict, has_port,
            inp.napref_raw[cls], inp.has_napref[cls], inp.taint_cnt[cls],
            inp.img_score[cls],
            inp.req[pi0], inp.req_nz[pi0], inp.balanced_active[pi0],
            jnp.int32(len(members)),
            j_max=j_max, k_slots=k_slots,
            gang_row=inp.gang_bonus[cls] if has_gang else None,
            has_gang=has_gang,
        )
        chosen = np.full(len(members), -1, dtype=np.int32)
        with part("solve.readback"):
            got = np.asarray(chosen_nodes)[: len(members)]
        chosen[: len(got)] = got  # k_slots may be < group size: overflow stays -1
        assignment[np.asarray(members)] = chosen
        # commit group effects
        placed = jnp.asarray(k_per_node)
        used = used + placed[:, None] * inp.req[pi0][None, :]
        used_nz = used_nz + placed[:, None] * inp.req_nz[pi0][None, :]
        pod_count = pod_count + placed
        if has_port:
            port_taken = port_taken | ((placed > 0)[:, None] & inp.class_ports[cls][None, :])

    return assignment


def make_groups(batch) -> List[Tuple[np.ndarray, int]]:
    """Group batch pods by (class, resource vector), preserving queue order of
    first appearance (the fast path's priority approximation)."""
    keys = {}
    order = []
    for i in range(len(batch.pods)):
        k = (int(batch.class_of_pod[i]), batch.req[i].tobytes(), batch.req_nz[i].tobytes(),
             bool(batch.balanced_active[i]))
        if k not in keys:
            keys[k] = []
            order.append(k)
        keys[k].append(i)
    return [(np.array(keys[k], dtype=np.int64), k[0]) for k in order]
