"""Garbage-collection pause time while a batch was open, any thread, as a
share of the batches' time, over the window's batch records, in percent."""


def read(w):
    recs = [r for r in w.batches if "gc_ms" in r]
    total = sum(r["total_ms"] for r in recs)
    if not recs or total <= 0:
        return None
    return 100 * sum(r["gc_ms"] for r in recs) / total
