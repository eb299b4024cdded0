"""p99 of how late the load generator sent each pod due in the window."""
from benchmark.stats import quantile


def read(w):
    return quantile(w.gen_late_ms, 0.99)
