"""The whole run, past the look for a chip, at rehearsal size on the CPU:
sound, it comes out correct; with the timed path broken underneath
(`faults.py`), `correct` comes out false on the guarantee the fault breaks.
Each case brings up its own control plane for a window of a few seconds."""

import pytest

from benchmark import catalog
from benchmark.run import REHEARSAL_CUT, run_cell

DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}
BREAKS = {"pile": "overcommit", "firstfit": "fill_gap",
          "half": "false_unschedulable", "nobind": "unbound"}
CELLS = [w["name"] for w in catalog.load_spec()["workloads"]]
CASES = [(c, None) for c in CELLS] + [(c, f) for c in CELLS for f in BREAKS]


@pytest.mark.parametrize("cell,fault", CASES)
def test_run(cell, fault):
    # piling onto one node overfills it, and first-fit fills it past the
    # limit, once more pods arrive than that (40 at 100m): a rehearsal's
    # open loop, deleting pods 10 s after they bind, needs more than 3 s
    # for that (360/64 pods/s)
    seconds = 14.0 if fault in ("pile", "firstfit") else 3.0
    res = run_cell(catalog.load_cell(cell), seed=2**31 + 7, seconds=seconds,
                   trace=False, cut=REHEARSAL_CUT, device=DEVICE, fault=fault)
    checks = {k: v["value"] for k, v in res["checks"].items()}
    limits = {k: v["limit"] for k, v in res["checks"].items()}
    if fault is None:
        assert res["correct"], (checks, res["diag"].get("generator_error"))
        assert res["attempted"] > 0 and res["failed"] == 0
        assert set(res["metrics"]) == {m["name"] for m in catalog.load_cell(cell).end_to_end}
    else:
        assert not res["correct"]
        assert checks[BREAKS[fault]] > limits[BREAKS[fault]], checks
    if fault == "firstfit":  # every placement fits: only fill_gap sees it
        assert checks["overcommit"] == 0, checks
    assert list(res)[-2:] == ["checks", "diag"]
