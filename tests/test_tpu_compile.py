"""Ahead-of-time compiles of the main-path kernels for a described TPU v5e.

Nothing runs: the TPU compiler, installed here, compiles for a chip that is
described and not attached, and raises what the chip's compiler would raise
(a slice off the tiling, a program too large for the device, ...). Widths are
the north-star cluster's 10,000 nodes where a compile stays within seconds.

The topology is described inside a fixture, never while a module is imported:
only one process may load the TPU library at a time, and every xdist worker
imports this file. Keep these tests in this one file, and compile in the test's
own process (the worker that describes the topology holds the library).
"""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
N_NODES = 10_000
I32, BOOL, F32 = jnp.int32, jnp.bool_, jnp.float32


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    # no skip: a missing TPU library or a changed topology API must fail
    # these checks, not drop them
    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")


@pytest.fixture(scope="module")
def spec(topo):
    """ShapeDtypeStruct factory on one described chip, with the persistent
    compile cache off: a described-chip compile cannot be read back here."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    one_chip = SingleDeviceSharding(topo.devices[0])
    yield lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                    sharding=one_chip)
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def test_greedy_scan_solve_compiles(spec):
    """The scan oracle on the mixed constrained snapshot (a quarter each of
    spread / anti-affinity / affinity / plain pods), 5,000 x 10,000."""
    from __graft_entry__ import _build_problem
    from kubernetes_tpu.ops.solver import greedy_scan_solve

    inp, d_max = _build_problem(n_nodes=5000, n_pods=10_000, mixed=True)
    shapes = jax.tree.map(lambda a: spec(a.shape, a.dtype), inp)
    compiled = greedy_scan_solve.lower(shapes, d_max).compile()
    assert compiled.memory_analysis().argument_size_in_bytes > 0


def test_waterfill_group_compiles(spec):
    """The fast path's group kernel at 10,000 nodes and the served batch's
    k_slots bucket (4096). The served j_max is 128 (110 pods per node), whose
    compile takes minutes; 16 keeps this test within seconds."""
    from kubernetes_tpu.models.waterfill import waterfill_group

    n = N_NODES
    args = (spec((n, 3), I32), spec((n, 3), I32), spec((n, 3), I32),
            spec((n,), I32), spec((n,), I32), spec((n,), BOOL),
            spec((n,), BOOL), spec((), BOOL), spec((n,), I32),
            spec((), BOOL), spec((n,), I32), spec((n,), I32),
            spec((3,), I32), spec((3,), I32), spec((), BOOL), spec((), I32))
    waterfill_group.lower(*args, j_max=16, k_slots=4096, gang_row=None,
                          has_gang=False).compile()


def test_repair_check_compiles(spec):
    """The repair path's final-state check at 10,000 nodes: 64 spread +
    anti-affinity classes, a 1024-row pod bucket, hostname and zone keys."""
    from kubernetes_tpu.models.repair import repair_check

    n, pb, c = N_NODES, 1024, 64
    sc, g, t = c + 1, c, c
    args = (spec((pb,), I32), spec((pb,), I32), spec((sc, n), I32),
            spec((g, n), I32), spec((2, n), I32),
            *(spec((c, 1), I32) for _ in range(5)),
            spec((c, sc), I32), spec((c, g), I32), spec((g,), I32),
            spec((c, n), BOOL), *(spec((t,), I32) for _ in range(5)))
    repair_check.lower(*args, d_max=n, has_affinity=True,
                       has_ct=True).compile()


def test_sinkhorn_iters_compiles(spec):
    from kubernetes_tpu.models.transport import _sinkhorn_iters

    n, g = N_NODES, 16
    args = (spec((g, n), F32), spec((g, n), BOOL), spec((g,), I32),
            spec((n,), F32), spec((g,), F32), spec((n,), F32), spec((), F32))
    _sinkhorn_iters.lower(*args, iters=60).compile()


def test_import_starts_no_backend():
    """Importing the solver, the transport kernels and the batch scheduler
    must touch no device: on the chip machine that process would hold the
    chip, and a TPU-only platform list fails here at backend start."""
    env = dict(os.environ, JAX_PLATFORMS="tpu")
    proc = subprocess.run(
        [sys.executable, "-c",
         "import kubernetes_tpu.ops.solver, kubernetes_tpu.models.transport, "
         "kubernetes_tpu.scheduler.batch"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
