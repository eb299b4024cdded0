"""A throwaway checkout for the tests: BENCHMARK.json naming the fixture's
cells, configurations, mixes and metrics, with the fixture's files and the
benchmark's own checks under `benchmark/`. Nothing in the harness names
any of them."""

import json
import os
import shutil

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKS = os.path.join(os.path.dirname(HERE), "checks")

SPEC = {
    "command": ["python3", "benchmark/run.py"], "paths": ["benchmark"],
    "run_seconds": 20,
    "configs": [
        {"name": "tiny-cfg", "source": "https://example.org/tiny",
         "file": "benchmark/configs/tiny-cfg.json", "reduced": [],
         "why": "fixture"},
        {"name": "spread-cfg", "source": "https://example.org/spread",
         "file": "benchmark/configs/spread-cfg.json", "reduced": [],
         "why": "fixture: three zones, a spread and an anti-affinity template"}],
    "workloads": [
        {"name": "tiny-cell", "config": "tiny-cfg", "traffic": "tiny-mix",
         "chips": 1, "why": "fixture"},
        {"name": "spread-cell", "config": "spread-cfg", "traffic": "spread-mix",
         "chips": 1, "why": "fixture: zone-spread pods that live on"}],
    "end_to_end": [{"name": "setup_s", "unit": "s", "better": "lower",
                    "bound": 0.25, "source": "host_clock"}],
    "per_layer": [{"name": "twice_relists", "unit": "relists",
                   "better": "lower", "source": "program_counter",
                   "layer": "control plane", "moves": "setup_s",
                   "workloads": ["tiny-cell"]}],
}


def make_checkout(tmp_path, spec=SPEC) -> str:
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(HERE, "fixture"), root / "benchmark")
    shutil.copytree(CHECKS, root / "benchmark" / "checks", dirs_exist_ok=True)
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return str(root)
