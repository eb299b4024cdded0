"""Test config: force an 8-device virtual CPU platform BEFORE jax is imported
anywhere, so mesh/sharding tests exercise real multi-device paths without TPU
hardware (`__graft_entry__.dryrun_multichip` rehearses the same way)."""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"  # unconditional: tests never touch the TPU
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _store_lock_order_check(monkeypatch):
    """ISSUE 5 satellite: every APIStore built under pytest runs with the
    runtime lock-order assertion on (the dynamic companion of schedlint
    LK001, store/store.py _OrderedRLock) — acquisition orders the static
    pass cannot prove are caught by the tests that exercise them."""
    monkeypatch.setenv("STORE_LOCK_ORDER_CHECK", "1")


@pytest.fixture(scope="session", autouse=True)
def _lock_graph_witness_gate():
    """ISSUE 20: the lock-graph witness records every ordered-lock
    acquisition edge made by the WHOLE tier-1 run (store/lockgraph.py,
    recorded by _OrderedRLock under the autouse STORE_LOCK_ORDER_CHECK).
    At session teardown the witnessed graph is diffed against the LK001
    ordering table — a never-before-seen inversion edge or a cycle fails
    the run loudly with the first-seen acquisition stacks. Set
    LOCK_GRAPH_EXPORT=<path> to also export the graph as JSON (the input
    `ktl vet --lock-graph` renders)."""
    from kubernetes_tpu.store.lockgraph import WITNESS

    yield
    report = WITNESS.diff()
    export = os.environ.get("LOCK_GRAPH_EXPORT")
    if export:
        WITNESS.export(export)
    if not report["clean"]:  # pragma: no cover - only on a real inversion
        raise AssertionError(
            "lock-graph witness diff against the LK001 ordering table is "
            "DIRTY:\n" + WITNESS.render())
