"""p99 of the per-batch `total_ms` over every batch that ended in the
window, read from the flight recorder's ring, drained every 50 ms."""
from benchmark.stats import quantile


def read(w):
    return quantile([r["total_ms"] for r in w.batches], 0.99)
