"""Multi-slice / DCN-aware hybrid mesh tests (8-device virtual CPU rig).

The rig emulates 2 slices x 4 chips; real multi-slice hardware differs only
in where the device array rows come from (slice_index grouping), so the
compile-time properties asserted here — parity, padding behavior, and
collective locality (node-axis collectives confined to ICI rows) — carry
over. reference analog: the scheduler's goroutine fan-out never leaves the
process; here per-step collectives never leave the slice (SURVEY.md §5).
"""

import numpy as np
import pytest

import jax

from kubernetes_tpu.ops.solver import greedy_scan_solve
from kubernetes_tpu.parallel.multislice import (
    audit_collectives,
    collective_replica_groups,
    make_hybrid_mesh,
    slice_topology,
)
from kubernetes_tpu.parallel.sharded import (
    feasibility_cost_matrices,
    shard_inputs,
    sharded_feasibility_cost,
    sharded_greedy_solve,
)

from test_sharding import build


class TestHybridMesh:
    def test_emulated_slices_fold(self):
        mesh = make_hybrid_mesh(n_slices=2)
        assert mesh.shape == {"dp": 2, "nodes": 4}
        mesh4 = make_hybrid_mesh(n_slices=4)
        assert mesh4.shape == {"dp": 4, "nodes": 2}
        with pytest.raises(ValueError):
            make_hybrid_mesh(n_slices=3)

    def test_slice_topology_single_domain(self):
        groups = slice_topology()
        assert len(groups) == 1 and len(groups[0]) == 8

    def test_solve_parity_on_hybrid_mesh(self):
        """The greedy scan on a hybrid 2x4 mesh (nodes sharded inside each
        slice, replicated over DCN) is bit-identical to single-device."""
        inp, d_max = build(n_nodes=13, n_pods=20)
        ref, _, _ = greedy_scan_solve(inp, d_max)
        mesh = make_hybrid_mesh(n_slices=2)
        sharded, true_n = shard_inputs(inp, mesh)
        got, _, _ = sharded_greedy_solve(sharded, d_max, mesh)
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))
        assert np.asarray(got).max() < true_n

    def test_2d_cost_kernel_parity_on_hybrid_mesh(self):
        inp, d_max = build(n_nodes=16, n_pods=24)
        mesh = make_hybrid_mesh(n_slices=2)
        sharded, true_n = shard_inputs(inp, mesh)
        f, c = sharded_feasibility_cost(sharded, d_max, mesh)
        f_ref, c_ref = jax.jit(
            feasibility_cost_matrices, static_argnames="d_max")(inp, d_max)
        np.testing.assert_array_equal(np.asarray(f)[:, :true_n], np.asarray(f_ref))


class TestCollectiveLocality:
    def test_replica_group_parser(self):
        text = ("%ar = f32[8] all-reduce(%x), channel_id=1, "
                "replica_groups={{0,1,2,3},{4,5,6,7}}, to_apply=%sum")
        got = collective_replica_groups(text)
        assert got == [("all-reduce", [[0, 1, 2, 3], [4, 5, 6, 7]])]
        # v2 iota format, plain and transposed
        got = collective_replica_groups(
            "%ag = pred[16] all-gather(%x), replica_groups=[2,4]<=[8], foo")
        assert got == [("all-gather", [[0, 1, 2, 3], [4, 5, 6, 7]])]
        got = collective_replica_groups(
            "%ar = f32[2] all-reduce(%x), replica_groups=[4,2]<=[2,4]T(1,0)")
        assert got == [("all-reduce", [[0, 4], [1, 5], [2, 6], [3, 7]])]

    def test_global_collective_reads_as_crossing(self):
        """replica_groups={} (one global group) must count as DCN-crossing."""
        from kubernetes_tpu.parallel.multislice import audit_collectives

        mesh = make_hybrid_mesh(n_slices=2)
        text = "%ar = f32[8] all-reduce(%x), replica_groups={}, to_apply=%sum"
        got = collective_replica_groups(text)
        assert got == [("all-reduce", [[-1, -2]])]
        row_of = {d.id: r for r, row in enumerate(mesh.devices) for d in row}
        assert len({row_of.get(i, i) for i in got[0][1][0]}) > 1

    def test_scan_solver_collectives_stay_on_ici(self):
        """THE multi-slice design property: every per-step collective of the
        scan solver groups within one slice row; nothing rides DCN. Checked
        on the compiled HLO, so no hardware needed."""
        inp, d_max = build(n_nodes=16, n_pods=12)
        mesh = make_hybrid_mesh(n_slices=2)
        sharded, _ = shard_inputs(inp, mesh)

        def solve(s):
            return greedy_scan_solve(s, d_max)

        counts = audit_collectives(solve, mesh, sharded)
        assert counts["dcn"] == 0
        assert counts["ici"] > 0  # the node-axis collectives exist

    def test_audit_flags_dcn_crossing(self):
        """A deliberately slice-crossing psum must be caught."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        mesh = make_hybrid_mesh(n_slices=2)
        x = jax.device_put(np.ones((8, 8), np.float32),
                           NamedSharding(mesh, P("dp", "nodes")))

        def crossing(v):
            # sum over the dp (DCN) axis: all-reduce groups span rows
            return jax.lax.psum(v.sum(axis=0), axis_name="dp")

        def fn(v):
            return jax.shard_map(crossing, mesh=mesh, in_specs=P("dp", "nodes"),
                             out_specs=P("nodes"))(v)

        with pytest.raises(AssertionError):
            audit_collectives(fn, mesh, x)
        counts = audit_collectives(fn, mesh, x, dcn_ok=("all-reduce",))
        assert counts["dcn"] >= 1
