"""The harness finds a new cell, configuration, traffic mix and metric from
added files alone: a throwaway checkout holds one of each, and nothing in
the harness names them."""

import json
import os
import shutil

from benchmark import catalog
from benchmark.run import Window

HERE = os.path.dirname(os.path.abspath(__file__))


def _checkout(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(HERE, "fixture"), root / "benchmark")
    spec = {
        "command": ["python3", "benchmark/run.py"], "paths": ["benchmark"],
        "run_seconds": 20,
        "configs": [{"name": "tiny-cfg", "source": "https://example.org/tiny",
                     "file": "benchmark/configs/tiny-cfg.json", "reduced": [],
                     "why": "fixture"}],
        "workloads": [{"name": "tiny-cell", "config": "tiny-cfg",
                       "traffic": "tiny-mix", "chips": 1, "why": "fixture"}],
        "end_to_end": [{"name": "setup_s", "unit": "s", "better": "lower",
                        "bound": 0.25, "source": "host_clock"}],
        "per_layer": [{"name": "twice_relists", "unit": "relists",
                       "better": "lower", "source": "program_counter",
                       "layer": "control plane", "moves": "setup_s",
                       "workloads": ["tiny-cell"]}],
    }
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return str(root)


def test_new_cell_config_mix_and_metric_come_from_files(tmp_path):
    root = _checkout(tmp_path)
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
    root = _checkout(tmp_path)
    cell = catalog.load_cell("tiny-cell", root)
    w = Window(window_s=1.0, setup_s=2.0, binds_in_window=0, bind_ms=[],
               api_ms=[], gen_late_ms=[], stages_ms={}, compiles_in_window=0,
               relists=0)
    assert "twice_relists" not in catalog.read_metrics(cell.per_layer, w, root)


def test_unknown_cell_is_refused(tmp_path):
    root = _checkout(tmp_path)
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
