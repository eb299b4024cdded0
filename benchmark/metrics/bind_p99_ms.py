"""p99 over every pod created in the window, from its due time to its
observed binding (a pod never bound counts to the end of the drain)."""
from benchmark.stats import quantile


def read(w):
    return quantile(w.bind_ms, 0.99)
