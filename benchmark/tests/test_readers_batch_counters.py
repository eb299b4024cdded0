"""The readers of the flight records' solve parts, compile and GC counters,
on hand-made windows: with records, with none, and with records from a
program that writes none of these fields."""

import pytest

from benchmark import catalog
from benchmark.run import Window


def _rec(total, parts=None, compile_ms=None, gc_ms=0.0):
    return {"total_ms": total, "pods": 1000, "stages": {"solve": 100.0},
            "parts_ms": parts or {}, "compile_ms": compile_ms or {},
            "compiles": len(compile_ms or {}), "gc_ms": gc_ms,
            "gc_collections": [1, 0, 0]}


RECORDS = [
    _rec(1000.0, {"solve.upload": 30.0, "solve.kernel": 20.0,
                  "solve.readback": 10.0, "solve.host": 40.0},
         {"solve": 25.0, "solve.upload": 25.0, "tensorize": 5.0}, gc_ms=50.0),
    _rec(2000.0, {"solve.upload": 10.0, "solve.readback": 30.0},
         {"solve": 15.0, "solve.kernel": 15.0, "batch": 1.0}, gc_ms=100.0),
    _rec(1000.0, {}, {}, gc_ms=0.0),
]


def _window(batches, binds=4000):
    return Window(window_s=20.0, setup_s=30.0, binds_in_window=binds,
                  bind_ms=[], api_ms=[], gen_late_ms=[], stages_ms={},
                  compiles_in_window=0, relists=0, batches=batches)


EXPECT = {
    "solve_upload_ms_per_kpod": 10.0,     # (30 + 10) / 4 kpods
    "solve_readback_ms_per_kpod": 10.0,   # (10 + 30) / 4
    "solve_compile_ms_per_kpod": 10.0,    # (25 + 15) / 4
    "gc_pause_share.burst": 3.75,         # 100 * 150 / 4000
    "gc_pause_share.rate": 3.75,
    "batch_compile_ms_p99": 30.0,         # max of 30, 16, 0 (stages only)
}


@pytest.mark.parametrize("name", sorted(EXPECT))
def test_reader(name):
    got = catalog.load_reader(name)(_window(RECORDS))
    assert got == pytest.approx(EXPECT[name])


@pytest.mark.parametrize("name", sorted(EXPECT))
def test_reader_without_records_returns_none(name):
    assert catalog.load_reader(name)(_window([])) is None


@pytest.mark.parametrize("name", sorted(EXPECT))
def test_reader_on_records_without_the_fields_returns_none(name):
    old = [{"total_ms": 1000.0, "pods": 1000, "stages": {"solve": 100.0}}]
    assert catalog.load_reader(name)(_window(old)) is None


@pytest.mark.parametrize("name", ["solve_upload_ms_per_kpod",
                                  "solve_readback_ms_per_kpod",
                                  "solve_compile_ms_per_kpod"])
def test_per_kpod_reader_without_binds_returns_none(name):
    assert catalog.load_reader(name)(_window(RECORDS, binds=0)) is None
