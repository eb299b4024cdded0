"""The whole run, past the look for a chip, at rehearsal size on the CPU:
sound, it comes out correct; with the timed path broken underneath
(`faults.py`), `correct` comes out false on the guarantee the fault breaks.
Each case brings up its own control plane for a window of a few seconds.
Beside the benchmark's cells runs the fixture's `spread-cell`
(`checkout.py`): zone-spread pods on the repair path, whose piled answer
only spread_skew has to see."""

import pytest

from benchmark import catalog
from benchmark.run import REHEARSAL_CUT, run_cell
from benchmark.tests.checkout import make_checkout

DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}
BREAKS = {"pile": "overcommit", "firstfit": "fill_gap",
          "half": "false_unschedulable", "nobind": "unbound"}
CELLS = [w["name"] for w in catalog.load_spec()["workloads"]]
CASES = [(c, None) for c in CELLS] + [(c, f) for c in CELLS for f in BREAKS]
SPREAD = "spread-cell"
CASES += [(SPREAD, None), (SPREAD, "pile")]


@pytest.mark.parametrize("cell,fault", CASES)
def test_run(cell, fault, tmp_path):
    # piling onto one node overfills it, and first-fit fills it past the
    # limit, once more pods arrive than that (40 at 100m): a rehearsal's
    # open loop, deleting pods 10 s after they bind, needs more than 3 s
    # for that (360/64 pods/s)
    seconds = 14.0 if fault in ("pile", "firstfit") and cell != SPREAD else 3.0
    root = make_checkout(tmp_path) if cell == SPREAD else catalog.ROOT
    c = catalog.load_cell(cell, root)
    res = run_cell(c, seed=2**31 + 7, seconds=seconds, trace=False,
                   cut=REHEARSAL_CUT, device=DEVICE, fault=fault)
    checks = {k: v["value"] for k, v in res["checks"].items()}
    limits = {k: v["limit"] for k, v in res["checks"].items()}
    if fault is None:
        assert res["correct"], (checks, res["diag"].get("generator_error"))
        assert res["attempted"] > 0 and res["failed"] == 0
        assert set(res["metrics"]) == {m["name"] for m in c.end_to_end}
    else:
        assert not res["correct"]
        broken = "spread_skew" if cell == SPREAD else BREAKS[fault]
        assert checks[broken] > limits[broken], checks
    if cell == SPREAD:  # spread pods take the repair path, never the scan
        diag = res["diag"]  # or the serial fallback
        assert diag["solve_paths"].get("repair", 0) > 0, diag["solve_paths"]
        assert "exact" not in diag["solve_paths"], diag["solve_paths"]
        assert not diag["stages_ms"].get("fallback"), diag["stages_ms"]
        if fault is None:
            assert checks["spread_skew"] == 0, checks
    if fault == "firstfit":  # every placement fits: only fill_gap sees it
        assert checks["overcommit"] == 0, checks
    assert list(res)[-2:] == ["checks", "diag"]
