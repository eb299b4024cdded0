"""Global batch solvers: auction and Sinkhorn on the group-level transportation
problem — the north-star replacement for prioritizeNodes() when the objective
is *joint* placement quality rather than serial-greedy emulation (reference:
pkg/scheduler/schedule_one.go:754; BASELINE.json north_star "JAX auction/
Sinkhorn over a dense feasibility/cost tensor").

Formulation. Batch pods collapse into G equivalence groups (identical class +
resource vector — snapshot/class_compiler.py); the problem becomes a
transportation problem on a [G, N] utility matrix:

    max Σ x_gn · C_gn      s.t.  Σ_n x_gn ≤ supply_g   (place each pod ≤ once)
                                 Σ_g x_gn ≤ slots_n    (node pod-count headroom)
                                 0 ≤ x_gn ≤ jcap_gn    (per-cell multi-resource fit)

`jcap_gn` bounds how many g-pods fit on n alone; cross-group resource coupling
is NOT in the relaxation — `repair_plan` enforces it exactly afterwards, and
pods it cannot seat return -1 (the batch driver re-runs them serially, so the
end-to-end result never violates a Filter).

Both solvers carry their duals across calls (`TransportState`): under churn the
next batch warm-starts from the previous prices/potentials re-mapped by node
name — the incremental re-solve of the north star (mirrors the generation-diff
snapshot stream, reference cache.go:186).

Solvers:
  auction_solve  — Bertsekas-style parallel forward auction with eps-scaling.
                   Holders + new bids per node are merged and the top slots_n
                   unit-levels are retained per round (a [2G, N] sort — node
                   axis shardable over the mesh). Integer-optimal to within
                   G·eps_final on the relaxation.
  sinkhorn_solve — log-domain entropic OT with inequality column marginals
                   (iterative Bregman projections; col update g += min(0,
                   eps·log(cap/colsum))). Returns a fractional plan that
                   `round_plan` converts to integers (floor + largest
                   remainder under column capacity).
"""

from __future__ import annotations

import functools
from typing import List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.solver import SolverInputs, pod_row_feasibility_score

NEG_INF = np.float32(-1e30)


class GroupProblem(NamedTuple):
    """The [G, N] transportation problem (all device arrays except members)."""

    utility: jnp.ndarray  # [G, N] float32 (int scores cast)
    feasible: jnp.ndarray  # [G, N] bool
    jcap: jnp.ndarray  # [G, N] int32 — per-cell max placements (single group)
    supply: jnp.ndarray  # [G] int32
    slots: jnp.ndarray  # [N] int32 — pod-count headroom
    req: jnp.ndarray  # [G, R] int32
    alloc: jnp.ndarray  # [N, R] int32
    used: jnp.ndarray  # [N, R] int32
    members: Tuple[np.ndarray, ...]  # per-group pod indices (queue order), host


class TransportState(NamedTuple):
    """Warm-startable duals. price doubles as the Sinkhorn node potential -g."""

    price: np.ndarray  # [N] float32
    node_names: Tuple[str, ...]
    iterations: int  # iterations spent by the last solve (observability)


def _group_rows(inp: SolverInputs, groups) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """F[G,N], C[G,N] from each group's representative pod."""
    reps = np.array([int(m[0]) for m, _ in groups])
    reqs = inp.req[reps]
    req_nzs = inp.req_nz[reps]
    clss = inp.class_of_pod[reps]
    bals = inp.balanced_active[reps]

    def row(req, req_nz, cls, bal):
        return pod_row_feasibility_score(inp, req, req_nz, cls, bal)

    return jax.vmap(row)(reqs, req_nzs, clss, bals)


def build_group_problem(inp: SolverInputs, groups) -> Optional[GroupProblem]:
    """groups: make_groups(batch) output. Returns None when any group's class
    declares host ports (per-port exclusion isn't in the transport relaxation;
    callers fall back to waterfill/scan)."""
    if not groups:
        return None
    for _, cls in groups:
        if bool(np.asarray(inp.class_ports[cls]).any()):
            return None
    feas, util = _group_rows(inp, groups)
    reps = np.array([int(m[0]) for m, _ in groups])
    req = inp.req[reps]  # [G, R]
    free = inp.alloc[None, :, :] - inp.used[None, :, :]  # [1, N, R]
    per_res = jnp.where(
        req[:, None, :] > 0,
        free // jnp.maximum(req[:, None, :], 1),
        jnp.int32(2**30),
    )
    jcap = jnp.min(per_res, axis=2).astype(jnp.int32)  # [G, N]
    slots = (inp.max_pods - inp.pod_count).astype(jnp.int32)
    jcap = jnp.minimum(jcap, slots[None, :])
    jcap = jnp.where(feas, jnp.maximum(jcap, 0), 0)
    supply = jnp.asarray([len(m) for m, _ in groups], dtype=jnp.int32)
    return GroupProblem(
        utility=util.astype(jnp.float32),
        feasible=feas,
        jcap=jcap,
        supply=supply,
        slots=jnp.maximum(slots, 0),
        req=jnp.asarray(req),
        alloc=inp.alloc,
        used=inp.used,
        members=tuple(np.asarray(m) for m, _ in groups),
    )


# ---------------------------------------------------------------------------
# auction
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("max_rounds",))
def _auction_phase(utility, jcap, supply, slots, req, free, x0, price0, level0,
                   eps, max_rounds: int):
    """One eps-phase of the forward auction. Returns (x, price, level, rounds).

    State: x[G,N] units held, level[G,N] the bid level units in the cell were
    acquired at (cell granularity — mixed-level cells keep the min, which only
    makes holders easier to evict, never violates feasibility), price[N].

    Acceptance is **resource-exact**: per node, holder+bid units are taken in
    level order while the cumulative multi-resource usage still fits
    (free = alloc − used) and the pod-count slot bound holds — the knapsack
    step is a lax.scan over the 2G sorted candidate rows carrying the running
    [N,R] usage. So the auction never produces a cross-group over-commit; the
    relaxation gap the repair pass has to fix is only supply clamping.
    """
    g, n = utility.shape
    r = req.shape[1]
    req2 = jnp.concatenate([req, req], axis=0)  # [2G, R] rows for both halves
    big = jnp.int32(2**30)

    def cond(state):
        x, price, level, rounds, progress = state
        unassigned = supply - jnp.sum(x, axis=1)
        return (jnp.any(unassigned > 0) & progress) & (rounds < max_rounds)

    def body(state):
        x, price, level, rounds, _ = state
        unassigned = supply - jnp.sum(x, axis=1)
        # value of acquiring one more unit of node n for group g; a pod prefers
        # any feasible node over staying unassigned (utility floor -inf only
        # for truly infeasible cells)
        v = jnp.where(jcap > x, utility - price[None, :], NEG_INF)
        # MULTI-NODE bids: each group bids its top-K nodes per round,
        # spreading unassigned units across them in value order. With one
        # node per round a single huge group (G=1, supply 50k) could place
        # only jcap units per round — 400 rounds capped it at ~13k pods.
        k = min(16, n)
        vk, jk = jax.lax.top_k(v, k)  # [G, K]
        v1 = vk[:, 0]
        # the marginal competing value: the best node OUTSIDE the top-K
        # (or the K-th best when nothing else is feasible) — every bid in
        # the wave uses it, which only raises bids above the minimum
        # Bertsekas increment (aggressive bids stay eps-CS-valid)
        rows = jnp.arange(g)[:, None].repeat(k, axis=1)
        v_next = jnp.max(v.at[rows, jk].set(NEG_INF), axis=1)
        v_next = jnp.where(v_next <= NEG_INF / 2,
                           jnp.where(vk[:, k - 1] > NEG_INF / 2,
                                     vk[:, k - 1], v1),
                           v_next)
        bidding = (unassigned > 0) & (v1 > NEG_INF / 2)
        avail = jnp.clip(
            jnp.take_along_axis(jcap, jk, axis=1)
            - jnp.take_along_axis(x, jk, axis=1), 0, None)  # [G, K]
        avail = jnp.where(vk > NEG_INF / 2, avail, 0)
        prefix = jnp.cumsum(avail, axis=1) - avail  # exclusive prefix
        units_k = jnp.clip(unassigned[:, None] - prefix, 0, avail)
        units_k = jnp.where(bidding[:, None], units_k, 0)
        beta_k = jnp.take_along_axis(utility, jk, axis=1) - v_next[:, None] + eps
        bids = jnp.zeros_like(x).at[rows, jk].add(units_k)
        bid_level = jnp.full_like(level, NEG_INF).at[rows, jk].max(
            jnp.where(units_k > 0, beta_k, NEG_INF))

        # merge holders + bids per node; greedy knapsack acceptance by level
        units = jnp.concatenate([x, bids], axis=0)  # [2G, N]
        levels = jnp.concatenate([
            jnp.where(x > 0, level, NEG_INF),
            jnp.where(bids > 0, bid_level, NEG_INF),
        ], axis=0)
        order = jnp.argsort(-levels, axis=0)  # [2G, N] rows by level desc
        u_sorted = jnp.take_along_axis(units, order, axis=0)
        l_sorted = jnp.take_along_axis(levels, order, axis=0)
        req_sorted = req2[order]  # [2G, N, R]

        def accept(carry, row):
            used_acc, cnt_acc = carry  # [N, R], [N]
            u_row, l_row, rq = row  # [N], [N], [N, R]
            room = free - used_acc  # [N, R]
            fit = jnp.min(
                jnp.where(rq > 0, room // jnp.maximum(rq, 1), big), axis=1
            )  # [N]
            fit = jnp.minimum(fit, slots - cnt_acc)
            k = jnp.clip(fit, 0, u_row)
            k = jnp.where(l_row > NEG_INF / 2, k, 0)
            used_acc = used_acc + k[:, None] * rq
            cnt_acc = cnt_acc + k
            return (used_acc, cnt_acc), k

        (_, _), keep = jax.lax.scan(
            accept,
            (jnp.zeros((n, r), jnp.int32), jnp.zeros((n,), jnp.int32)),
            (u_sorted, l_sorted, req_sorted),
        )  # keep: [2G, N]

        # price rises to the highest rejected level (the (cap+1)-th bid)
        rejected = u_sorted - keep
        any_rej = jnp.any(rejected > 0, axis=0)
        top_rej_level = jnp.max(
            jnp.where(rejected > 0, l_sorted, NEG_INF), axis=0
        )
        new_price = jnp.where(
            any_rej, jnp.maximum(price, top_rej_level), price
        )
        # scatter kept units back to [2G, N] then fold the two halves
        kept = jnp.zeros_like(units).at[
            order, jnp.arange(n)[None, :].repeat(2 * g, axis=0)
        ].set(keep)
        kept_levels = jnp.where(kept > 0, levels, -NEG_INF)
        x_new = kept[:g] + kept[g:]
        level_new = jnp.minimum(kept_levels[:g], kept_levels[g:])
        level_new = jnp.where(x_new > 0, level_new, NEG_INF)
        progress = jnp.any(units_k > 0)
        return x_new, new_price, level_new, rounds + 1, progress

    x, price, level, rounds, _ = jax.lax.while_loop(
        cond, body, (x0, price0, level0, jnp.int32(0), jnp.bool_(True))
    )
    return x, price, level, rounds


def auction_solve(
    problem: GroupProblem,
    state: Optional[TransportState] = None,
    node_names: Optional[List[str]] = None,
    eps_start: Optional[float] = None,
    eps_final: float = 0.9,
    scale: float = 4.0,
    max_rounds: int = 400,
) -> Tuple[np.ndarray, TransportState]:
    """eps-scaling forward auction. Returns (x[G,N] int counts, state).

    Scores are integers, so eps_final < 1 yields a relaxation-optimal
    assignment up to per-node ties; warm prices from `state` skip most of the
    price discovery under churn."""
    g, n = problem.utility.shape
    price0 = np.zeros(n, np.float32)
    if state is not None and node_names is not None:
        remapped = _remap_price(state, node_names)
        price0[:len(remapped)] = remapped  # node axis may be mesh-padded
    util_range = float(jnp.max(jnp.where(problem.feasible, problem.utility, 0)))
    eps = eps_start if eps_start is not None else max(util_range / 8.0, eps_final)
    price = jnp.asarray(price0)
    free = problem.alloc - problem.used
    total_rounds = 0
    while True:
        x, price, level, rounds = _auction_phase(
            problem.utility, problem.jcap, problem.supply, problem.slots,
            problem.req, free,
            jnp.zeros((g, n), jnp.int32), price, jnp.full((g, n), NEG_INF),
            jnp.float32(eps), max_rounds,
        )
        total_rounds += int(rounds)
        if eps <= eps_final:
            break
        eps = max(eps / scale, eps_final)
    names = tuple(node_names) if node_names else tuple(str(i) for i in range(n))
    new_state = TransportState(
        price=np.asarray(price)[:len(names)],
        node_names=names,
        iterations=total_rounds,
    )
    return np.asarray(x), new_state


def _remap_price(state: TransportState, node_names: List[str]) -> np.ndarray:
    """Carry duals across snapshots by node name (churn: nodes come and go)."""
    idx = {nm: i for i, nm in enumerate(state.node_names)}
    out = np.zeros(len(node_names), np.float32)
    for j, nm in enumerate(node_names):
        i = idx.get(nm)
        if i is not None:
            out[j] = state.price[i]
    return out


# ---------------------------------------------------------------------------
# sinkhorn
# ---------------------------------------------------------------------------


def _effective_cap(problem: GroupProblem) -> jnp.ndarray:
    """Scalarized per-node capacity for the Sinkhorn column marginal: the
    pod-count slot bound tightened by each resource's headroom divided by the
    supply-weighted mean request — so the fractional plan roughly respects the
    multi-resource budget the rounding/repair passes then enforce exactly."""
    supply = problem.supply.astype(jnp.float32)  # [G]
    total = jnp.maximum(jnp.sum(supply), 1.0)
    mean_req = jnp.sum(problem.req.astype(jnp.float32) * supply[:, None], axis=0) / total
    free = (problem.alloc - problem.used).astype(jnp.float32)  # [N, R]
    per_res = jnp.where(
        mean_req[None, :] > 0, free / jnp.maximum(mean_req[None, :], 1e-9), jnp.inf
    )
    cap = jnp.minimum(jnp.min(per_res, axis=1), problem.slots.astype(jnp.float32))
    return jnp.maximum(cap, 0.0)


@functools.partial(jax.jit, static_argnames=("iters",))
def _sinkhorn_iters(utility, feasible, supply, cap, f0, g0, eps, iters: int):
    """Log-domain scaling for  max ⟨C,x⟩ + eps·H(x)  s.t. rows ≤ supply,
    cols ≤ cap, x ≥ 0.  KKT: x = exp((C − f − g)/eps) with duals f,g ≥ 0 and
    complementary slackness, so each update is a clamped-at-zero exact solve:
        f = max(0, eps·(lse_n((C−g)/eps) − log supply))
        g = max(0, eps·(lse_g((C−f)/eps) − log cap))
    """
    logmask = jnp.where(feasible, 0.0, NEG_INF)
    logs = jnp.log(jnp.maximum(supply.astype(jnp.float32), 1e-9))
    logc = jnp.log(jnp.maximum(cap.astype(jnp.float32), 1e-9))
    z = (utility + logmask) / eps  # [G, N]

    def one(i, fg):
        f, g = fg
        row_lse = jax.scipy.special.logsumexp(z - g[None, :] / eps, axis=1)
        f = jnp.maximum(0.0, eps * (row_lse - logs))
        col_lse = jax.scipy.special.logsumexp(z - f[:, None] / eps, axis=0)
        g = jnp.maximum(0.0, eps * (col_lse - logc))
        return f, g

    f, g = jax.lax.fori_loop(0, iters, one, (f0, g0))
    plan = jnp.exp((utility + logmask - f[:, None] - g[None, :]) / eps)
    return f, g, plan


def sinkhorn_solve(
    problem: GroupProblem,
    state: Optional[TransportState] = None,
    node_names: Optional[List[str]] = None,
    eps: float = 2.0,
    iters: int = 60,
) -> Tuple[np.ndarray, TransportState]:
    """Entropic relaxation; returns (fractional plan [G,N], state). The node
    dual g (a price: ≥ 0, rises on contended nodes) is carried in
    TransportState.price — interchangeable with the auction's price vector."""
    gdim, n = problem.utility.shape
    g0 = np.zeros(n, np.float32)
    if state is not None and node_names is not None:
        remapped = np.maximum(_remap_price(state, node_names), 0.0)
        g0[:len(remapped)] = remapped  # node axis may be mesh-padded
    f0 = jnp.zeros(gdim, jnp.float32)
    f, g, plan = _sinkhorn_iters(
        problem.utility, problem.feasible, problem.supply, _effective_cap(problem),
        f0, jnp.asarray(g0), jnp.float32(eps), iters,
    )
    names = tuple(node_names) if node_names else tuple(str(i) for i in range(n))
    new_state = TransportState(
        price=np.asarray(g)[:len(names)],
        node_names=names,
        iterations=iters,
    )
    return np.asarray(plan), new_state


def round_plan(problem: GroupProblem, frac: np.ndarray) -> np.ndarray:
    """Fractional [G,N] → integer counts: floor, then largest-remainder fill
    per group under remaining column capacity and cell caps."""
    jcap = np.asarray(problem.jcap)
    frac = np.minimum(frac, jcap)
    x = np.floor(frac).astype(np.int32)
    # column headroom after floors
    col_room = np.asarray(problem.slots) - x.sum(axis=0)
    supply = np.asarray(problem.supply)
    rema = frac - x
    for gi in range(x.shape[0]):
        want = int(supply[gi] - x[gi].sum())
        if want <= 0:
            continue
        order = np.argsort(-rema[gi])
        for n_i in order:
            if want == 0:
                break
            if rema[gi, n_i] <= 0:
                break
            if col_room[n_i] > 0 and x[gi, n_i] < jcap[gi, n_i]:
                x[gi, n_i] += 1
                col_room[n_i] -= 1
                want -= 1
    return x


def repair_plan(problem: GroupProblem, x: np.ndarray) -> np.ndarray:
    """Enforce the exact multi-resource constraint Σ_g x_gn·req_g ≤ alloc−used
    and the pod-count slot bound, dropping units from lowest-utility cells
    first. Returns a feasible integer plan (reference semantics: a batch
    assignment must never violate Filter — fit.go:499)."""
    x = np.minimum(np.asarray(x, np.int64), np.asarray(problem.jcap))
    req = np.asarray(problem.req, np.int64)  # [G, R]
    free = np.asarray(problem.alloc, np.int64) - np.asarray(problem.used, np.int64)
    slots = np.asarray(problem.slots, np.int64)
    util = np.asarray(problem.utility)
    # clamp supply per group (defensive)
    supply = np.asarray(problem.supply, np.int64)
    for gi in range(x.shape[0]):
        over = int(x[gi].sum() - supply[gi])
        if over > 0:
            order = np.argsort(util[gi])  # drop worst first
            for n_i in order:
                if over <= 0:
                    break
                d = min(over, int(x[gi, n_i]))
                x[gi, n_i] -= d
                over -= d
    node_used = x.T @ req  # [N, R]
    node_cnt = x.sum(axis=0)
    bad = np.nonzero(
        (node_used > free).any(axis=1) | (node_cnt > slots)
    )[0]
    for n_i in bad:
        order = np.argsort(util[:, n_i])  # worst utility first
        for gi in order:
            while x[gi, n_i] > 0 and (
                (node_used[n_i] > free[n_i]).any() or node_cnt[n_i] > slots[n_i]
            ):
                x[gi, n_i] -= 1
                node_used[n_i] -= req[gi]
                node_cnt[n_i] -= 1
            if not (node_used[n_i] > free[n_i]).any() and node_cnt[n_i] <= slots[n_i]:
                break
    return x.astype(np.int32)


def assignment_from_plan(problem: GroupProblem, x: np.ndarray, n_pods: int) -> np.ndarray:
    """Integer plan → per-pod node index (queue order within each group);
    -1 for units the plan couldn't seat (batch driver retries them serially)."""
    out = np.full(n_pods, -1, np.int32)
    for gi, members in enumerate(problem.members):
        nodes = np.repeat(np.arange(x.shape[1]), x[gi])
        k = min(len(nodes), len(members))
        out[members[:k]] = nodes[:k].astype(np.int32)
    return out


def transport_solve(
    inp: SolverInputs,
    groups,
    method: str = "auction",
    state: Optional[TransportState] = None,
    node_names: Optional[List[str]] = None,
    mesh=None,
) -> Optional[Tuple[np.ndarray, TransportState]]:
    """End-to-end: build → solve → round → repair → per-pod assignment.
    Returns None when the batch isn't transport-eligible (host ports).

    With `mesh`, the [G, N] problem's node axis shards over the mesh's
    "nodes" axis (parallel/sharded.py shard_group_problem) and the solver
    runs under it — GSPMD inserts the node-axis collectives over ICI;
    padded nodes are infeasible and never receive units. Warm duals carry
    by node name either way."""
    import contextlib

    problem = build_group_problem(inp, groups)
    if problem is None:
        return None
    ctx = contextlib.nullcontext()
    if mesh is not None:
        from ..parallel.sharded import shard_group_problem

        true_n = problem.utility.shape[1]
        problem, _ = shard_group_problem(problem, mesh)
        if node_names is None:
            # duals must map to TRUE nodes, never mesh padding
            node_names = [str(i) for i in range(true_n)]
        ctx = jax.sharding.set_mesh(mesh)
    with ctx:
        if method == "sinkhorn":
            frac, new_state = sinkhorn_solve(problem, state, node_names)
            x = round_plan(problem, frac)
        else:
            x, new_state = auction_solve(problem, state, node_names)
            x = np.asarray(x)
    x = repair_plan(problem, x)
    n_pods = inp.req.shape[0]
    return assignment_from_plan(problem, x, n_pods), new_state
