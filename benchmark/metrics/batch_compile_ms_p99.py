"""p99 over the window's batch records of each batch's XLA compile time
(the sum over its stages; a part's time is inside its stage's)."""
from benchmark.stats import quantile


def read(w):
    recs = [r for r in w.batches if "compile_ms" in r]
    if not recs:
        return None
    return quantile([sum(v for k, v in r["compile_ms"].items() if "." not in k)
                     for r in recs], 0.99)
