"""Order statistics the metric readers share."""

from __future__ import annotations

import math


def quantile(values, q: float):
    """Nearest-rank quantile over every sample; None when there is none."""
    vals = sorted(values)
    if not vals:
        return None
    return vals[min(len(vals) - 1, max(0, math.ceil(q * len(vals)) - 1))]
