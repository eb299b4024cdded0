"""Each metric reader on a hand-made window and flight-recorder table."""

import pytest

from benchmark import catalog
from benchmark.run import Window


def _window(**kw):
    base = dict(
        window_s=20.0, setup_s=31.5, binds_in_window=40_000,
        bind_ms=[float(i) for i in range(1, 101)],  # 1..100
        api_ms=[10.0] * 98 + [500.0, 900.0],
        gen_late_ms=[0.5] * 99 + [7.0],
        stages_ms={"ingest": 1000.0, "queue_add": 200.0, "tensorize": 3000.0,
                   "build_pod_batch": 1000.0, "solve": 2000.0,
                   "bind_wait": 400.0, "bind": 800.0},
        compiles_in_window=3, relists=2,
        batches=[{"total_ms": float(t), "pods": p}
                 for t, p in [(100, 4096), (120, 4096), (300, 1808)]],
        trace={"window_s": 4.0, "busy_s": 0.4, "idle_share": 0.9,
               "programs": {"waterfill_group": 0.06, "repair_check": 0.02,
                            "scatter": 0.5}})
    base.update(kw)
    return Window(**base)


EXPECT = {
    "pods_per_s": 2000.0,
    "bind_p99_ms": 99.0,
    "setup_s": 31.5,
    "ingest_ms_per_kpod": 30.0,       # (1000 + 200) / 40
    "bind_wait_ms_per_kpod": 10.0,    # 400 / 40
    "bind_ms_per_kpod": 20.0,         # 800 / 40
    "tensorize_ms_per_kpod": 100.0,   # (3000 + 1000) / 40
    "solve_ms_per_kpod": 50.0,        # 2000 / 40
    "window_compiles.burst": 3.0,
    "kernel_ms_per_kpod": 2.0,        # (60 + 20) ms / 40 kpods
    "device_idle_share": 90.0,
    "window_compiles.rate": 3.0,
    "batch_ms_p99": 300.0,
    "batch_pods_mean": 10000 / 3,
    "controller_relists.burst": 2.0,
    "controller_relists.rate": 2.0,
    "gen_late_p99_ms": 0.5,
}


@pytest.mark.parametrize("name", sorted(EXPECT))
def test_reader(name):
    assert catalog.load_reader(name)(_window()) == pytest.approx(EXPECT[name])


@pytest.mark.parametrize("name", ["kernel_ms_per_kpod", "device_idle_share",
                                  "batch_ms_p99", "batch_pods_mean",
                                  "pods_per_s", "solve_ms_per_kpod",
                                  "bind_wait_ms_per_kpod", "bind_ms_per_kpod"])
def test_reader_with_nothing_to_read_returns_none(name):
    empty = _window(binds_in_window=0, batches=[], trace=None, stages_ms={})
    assert catalog.load_reader(name)(empty) is None


def test_every_metric_of_the_benchmark_has_a_reader():
    spec = catalog.load_spec()
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert callable(catalog.load_reader(m["name"])), m["name"]
