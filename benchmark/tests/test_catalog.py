"""The harness finds a new cell, configuration, traffic mix and metric from
added files alone: a throwaway checkout (`checkout.py`) holds them, and
nothing in the harness names them."""

from benchmark import catalog
from benchmark.run import Window
from benchmark.tests.checkout import make_checkout

def test_new_cell_config_mix_and_metric_come_from_files(tmp_path):
    root = make_checkout(tmp_path)
    cell = catalog.load_cell("tiny-cell", root)
    assert cell.config["nodes"]["count"] == 7
    assert cell.traffic["arrivals"] == "poisson"
    assert cell.traffic["rate_per_s"] == 3
    assert [m["name"] for m in cell.per_layer] == ["twice_relists"]
    w = Window(window_s=1.0, setup_s=2.0, binds_in_window=0, bind_ms=[],
               api_ms=[], gen_late_ms=[], stages_ms={}, compiles_in_window=0,
               relists=4)
    got = catalog.read_metrics(cell.per_layer + cell.end_to_end, w, root)
    assert got == {"twice_relists": {"value": 8.0, "unit": "relists"},
                   "setup_s": {"value": 2.0, "unit": "s"}}


def test_metric_that_finds_nothing_is_left_out(tmp_path):
    root = make_checkout(tmp_path)
    cell = catalog.load_cell("tiny-cell", root)
    w = Window(window_s=1.0, setup_s=2.0, binds_in_window=0, bind_ms=[],
               api_ms=[], gen_late_ms=[], stages_ms={}, compiles_in_window=0,
               relists=0)
    assert "twice_relists" not in catalog.read_metrics(cell.per_layer, w, root)


def test_unknown_cell_is_refused(tmp_path):
    root = make_checkout(tmp_path)
    try:
        catalog.load_cell("no-such-cell", root)
    except KeyError as e:
        assert "tiny-cell" in str(e)
    else:
        raise AssertionError("an unknown cell was accepted")


def test_the_benchmark_cells_all_load():
    spec = catalog.load_spec()
    for w in spec["workloads"]:
        cell = catalog.load_cell(w["name"])
        assert cell.traffic["arrivals"] in ("burst", "poisson")
        assert any(m["name"] == "setup_s" for m in cell.end_to_end)
        assert len(cell.end_to_end) >= 2 and cell.per_layer
