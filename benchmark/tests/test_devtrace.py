"""The trace reduction: busy union, idle share, per-program device time and
gaps, on a hand-made trace and on one recorded by JAX's profiler here."""

import os

import pytest

from benchmark import devtrace

MS = 1_000_000  # ns


def _trace():
    host = ("/host:CPU", [("python3", [(devtrace.WINDOW, 10 * MS, 100 * MS)])])
    dev = ("/device:TPU:0", [
        ("XLA Modules", [("jit_waterfill_group(17)", 0, 20 * MS),
                         ("jit_repair_check(3)", 50 * MS, 10 * MS),
                         ("jit_scatter(9)", 105 * MS, 10 * MS)]),
        ("XLA Ops", [("fusion.1", 0, 15 * MS), ("fusion.2", 12 * MS, 8 * MS),
                     ("fusion.3", 50 * MS, 10 * MS),
                     ("scatter.1", 105 * MS, 10 * MS)]),
        ("Steps", [("0", 0, 200 * MS)]),
    ])
    return [host, dev, ("/device:TPU_NON_CORE:0", [("x", [("y", 0, 500 * MS)])])]


def test_union_merges_overlaps_and_drops_empty():
    assert devtrace.union([(5, 9), (0, 3), (2, 4), (9, 10), (7, 7)]) == [[0, 4], [5, 10]]


def test_reduce_busy_idle_programs_and_gaps():
    red = devtrace.reduce(_trace())
    # window [10, 110] ms; busy [10, 20] + [50, 60] + [105, 110] = 25 ms
    assert red["window_s"] == pytest.approx(0.1)
    assert red["busy_s"] == pytest.approx(0.025)
    assert red["idle_share"] == pytest.approx(0.75)
    assert red["programs"] == pytest.approx(
        {"waterfill_group": 0.010, "repair_check": 0.010, "scatter": 0.005})
    assert red["devices"] == 1
    # gaps longest first, relative to the window start
    assert [g for pair in red["gaps"] for g in pair] == pytest.approx(
        [0.05, 0.095, 0.01, 0.04])


def test_reduce_refuses_a_trace_without_device_or_window():
    host_only = [p for p in _trace() if p[0] == "/host:CPU"]
    with pytest.raises(ValueError, match="device"):
        devtrace.reduce(host_only)
    no_window = [p for p in _trace() if p[0] != "/host:CPU"]
    with pytest.raises(ValueError, match="annotation"):
        devtrace.reduce(no_window)


def test_name_gaps_takes_the_label_that_overlaps_most():
    gaps = [[0.0, 1.0], [2.0, 2.5]]
    spans = [[0.1, 0.3, "ingest"], [0.3, 0.9, "tensorize"]]
    assert devtrace.name_gaps(gaps, spans) == [["tensorize", 1.0], ["no batch", 0.5]]


def test_peaks_table_refuses_an_unknown_kind():
    assert devtrace.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        devtrace.peaks_for("TPU v0")


def test_program_name():
    assert devtrace.program_name("jit_waterfill_group(1234)") == "waterfill_group"
    assert devtrace.program_name("fusion.3") == "fusion.3"


def test_recorded_trace_reads_back(tmp_path):
    """A trace JAX's profiler writes here: the window annotation is found on
    the host plane, and a trace with no TPU plane is refused as such."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(devtrace.WINDOW):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    planes = devtrace.load_xplane(devtrace.find_xplane(str(tmp_path)))
    names = {e[0] for _p, lines in planes for _l, evs in lines for e in evs}
    assert devtrace.WINDOW in names
    with pytest.raises(ValueError, match="device"):
        devtrace.reduce(planes)


RECORDED = os.path.join(os.path.dirname(__file__), "data", "v5e-matmul.xplane.pb")


def test_chip_trace_reduces():
    """A trace recorded on one TPU v5e: a jitted 2048x2048 matmul-and-sum,
    five calls, three of them inside the window annotation (0.204 s). Each
    call is one 90 us program on the device, so busy time is three calls'
    worth, and it is all the one program's."""
    red = devtrace.reduce(devtrace.load_xplane(RECORDED))
    assert red["window_s"] == pytest.approx(0.2042, abs=1e-4)
    assert red["busy_s"] == pytest.approx(3 * 90e-6, rel=0.01)
    assert red["programs"] == pytest.approx({"_lambda": red["busy_s"]}, rel=1e-3)
    assert red["idle_share"] > 0.99
