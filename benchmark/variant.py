#!/usr/bin/env python3
"""A cell run with something changed, for the measurements beside the
benchmark; the benchmark's own runs never come through here.

    python3 benchmark/variant.py --workload basic-burst --seed 7 --seconds 10 \\
        [--fault pile|firstfit|half|nobind] [--set rate_per_s=3000 ...]

--fault plants one of `faults.py`'s faults at the window's open (a control:
the run must come out `correct: false`); --set overrides a number of the
cell's traffic mix (the knee sweep of a Poisson cell). Output and exit codes
are run.py's.
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import run  # noqa: E402
from benchmark.faults import FAULTS  # noqa: E402


def _extra(ap):
    ap.add_argument("--fault", choices=FAULTS)
    ap.add_argument("--set", action="append", default=[], metavar="KEY=NUMBER")


if __name__ == "__main__":
    args = run.parse_args(extra=_extra)
    overrides = {}
    for kv in args.set:
        k, v = kv.split("=", 1)
        overrides[k] = float(v)
    sys.exit(run.main(args, fault=args.fault, traffic_overrides=overrides))
