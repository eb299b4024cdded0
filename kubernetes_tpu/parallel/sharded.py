"""Mesh construction and sharded solver entry points.

The distributed design (SURVEY.md §2.4): the node axis of every cluster tensor
is sharded across the mesh's "nodes" axis (the tensor-parallel analog — the
direct replacement for the scheduler's 16-goroutine Parallelizer fan-out,
parallelize/parallelism.go:67), and the pod axis of batch matrices across "dp"
(data-parallel analog). Shardings are annotated with NamedSharding and XLA/GSPMD
inserts the collectives (segment-sum psums for topology counts, argmax
all-reduce for host selection) over ICI.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.solver import SolverInputs, greedy_scan_solve
from ..scheduler.framework import MAX_NODE_SCORE


def make_mesh(n_devices: Optional[int] = None, dp: int = 1) -> Mesh:
    devices = jax.devices()
    if n_devices is not None:
        devices = devices[:n_devices]
    n = len(devices)
    assert n % dp == 0, f"dp={dp} must divide device count {n}"
    return Mesh(np.array(devices).reshape(dp, n // dp), ("dp", "nodes"))


# PartitionSpec per SolverInputs field: which axis is the node axis.
_SPECS = dict(
    alloc=P("nodes", None), used=P("nodes", None), used_nz=P("nodes", None),
    pod_count=P("nodes"), max_pods=P("nodes"),
    filter_ok=P(None, "nodes"), aff_ok=P(None, "nodes"),
    napref_raw=P(None, "nodes"), has_napref=P(),
    taint_cnt=P(None, "nodes"), img_score=P(None, "nodes"),
    class_ports=P(), node_ports=P("nodes", None),
    topo_id=P(None, "nodes"), selcls_count=P(None, "nodes"),
    class_matches_selcls=P(),
    ct_class=P(), ct_key=P(), ct_sel=P(), ct_max_skew=P(),
    ct_min_domains=P(), ct_self_match=P(),
    st_class=P(), st_key=P(), st_sel=P(), st_max_skew=P(), st_self_match=P(),
    ra_key=P(), ra_sel=P(),
    rn_key=P(), rn_sel=P(),
    pp_key=P(), pp_sel=P(), pp_weight=P(),
    grp_key=P(), grp_count=P(None, "nodes"), class_holds_grp=P(),
    ea_grp=P(),
    sym_grp=P(), sym_weight=P(),
    class_self_ok=P(), class_has_ra=P(),
    req=P(), req_nz=P(), class_of_pod=P(), balanced_active=P(),
    gang_bonus=P(None, "nodes"),
)


def _pad_nodes(inp: SolverInputs, multiple: int) -> Tuple[SolverInputs, int]:
    """Pad the node axis so it divides the mesh. Padding nodes are infeasible
    (filter_ok false, zero capacity) and can never be selected."""
    n = inp.alloc.shape[0]
    pad = (-n) % multiple
    if pad == 0:
        return inp, n
    def pad_node_axis(name, arr):
        if arr is None:  # optional field absent (e.g. gang_bonus)
            return None
        spec = _SPECS[name]
        axis = None
        for i, s in enumerate(spec):
            if s == "nodes":
                axis = i
        if axis is None:
            return arr
        widths = [(0, 0)] * arr.ndim
        widths[axis] = (0, pad)
        return jnp.pad(arr, widths)
    padded = SolverInputs(**{k: pad_node_axis(k, v) for k, v in inp._asdict().items()})
    # padded topo ids are 0 after padding — mark them missing (-1)
    if padded.topo_id.size:
        mask = jnp.arange(padded.topo_id.shape[1]) >= n
        padded = padded._replace(topo_id=jnp.where(mask[None, :], -1, padded.topo_id))
    return padded, n


def shard_inputs(inp: SolverInputs, mesh: Mesh) -> Tuple[SolverInputs, int]:
    """device_put every field with its NamedSharding (node axis over the mesh)."""
    inp, n = _pad_nodes(inp, mesh.shape["nodes"])
    placed = {
        k: (v if v is None
            else jax.device_put(v, NamedSharding(mesh, _SPECS[k])))
        for k, v in inp._asdict().items()
    }
    return SolverInputs(**placed), n


def sharded_greedy_solve(inp: SolverInputs, d_max: int, mesh: Mesh):
    """greedy_scan_solve with node-axis-sharded inputs: GSPMD partitions the
    per-step filter/score over the mesh and inserts the argmax/segment-sum
    collectives. Assignment indices refer to the padded node axis; callers must
    treat idx >= true_n as unschedulable (cannot happen: padding is infeasible)."""
    with jax.sharding.set_mesh(mesh):
        return greedy_scan_solve(inp, d_max)


def feasibility_cost_matrices(inp: SolverInputs, d_max: int):
    """F[P,N], C[P,N] against the *initial* snapshot state (no intra-batch
    dynamics) — the batch-extender surface (ExtenderArgs -> filtered nodes +
    HostPriority lists, reference: extender/v1/types.go) and the 2D (dp x nodes)
    sharded kernel. Scores use the same default-weight composition as the
    solver."""
    from ..ops.solver import pod_row_feasibility_score

    def per_pod(req, req_nz, cls, bal_active):
        return pod_row_feasibility_score(inp, req, req_nz, cls, bal_active)

    return jax.vmap(per_pod)(inp.req, inp.req_nz, inp.class_of_pod, inp.balanced_active)


def sharded_feasibility_cost(inp: SolverInputs, d_max: int, mesh: Mesh):
    """2D-sharded F/C: pods over 'dp', nodes over 'nodes'."""
    fn = jax.jit(feasibility_cost_matrices, static_argnames=("d_max",),
                 out_shardings=(NamedSharding(mesh, P("dp", "nodes")),
                                NamedSharding(mesh, P("dp", "nodes"))))
    with jax.sharding.set_mesh(mesh):
        return fn(inp, d_max)


# PartitionSpec per GroupProblem field (models/transport.py): the node axis
# of the [G, N] transportation problem shards over the mesh — BASELINE.json
# ladder #4 "Sinkhorn relaxation node-sharded". The group axis stays
# replicated (G is small after class collapse); GSPMD inserts the node-axis
# reductions (sinkhorn row-logsumexp, auction top-k/argmax) over ICI.
_GP_SPECS = dict(
    utility=P(None, "nodes"), feasible=P(None, "nodes"),
    jcap=P(None, "nodes"), supply=P(), slots=P("nodes"), req=P(),
    alloc=P("nodes", None), used=P("nodes", None),
)


def shard_group_problem(problem, mesh: Mesh):
    """Pad the node axis to the mesh multiple (padding is infeasible: zero
    capacity/slots, -inf utility) and device_put every field with its
    NamedSharding. Returns (sharded problem, true node count)."""
    from ..models.transport import NEG_INF

    n = problem.utility.shape[1]
    mult = mesh.shape["nodes"]
    pad = (-n) % mult
    if pad:
        # spec-driven (same pattern as _pad_nodes): every field whose spec
        # names the nodes axis pads along it — a new field added to
        # _GP_SPECS is padded automatically or device_put fails loudly
        padded = {}
        for k, spec in _GP_SPECS.items():
            arr = getattr(problem, k)
            axis = next((i for i, s in enumerate(spec) if s == "nodes"), None)
            if axis is None:
                continue
            widths = [(0, 0)] * arr.ndim
            widths[axis] = (0, pad)
            fill = float(NEG_INF) if k == "utility" else 0
            padded[k] = jnp.pad(arr, widths, constant_values=fill)
        problem = problem._replace(**padded)
    placed = {k: jax.device_put(getattr(problem, k),
                                NamedSharding(mesh, _GP_SPECS[k]))
              for k in _GP_SPECS}
    return problem._replace(**placed), n
