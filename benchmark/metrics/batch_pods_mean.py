"""Mean pods per batch over every batch that ended in the window."""


def read(w):
    if not w.batches:
        return None
    return sum(r["pods"] for r in w.batches) / len(w.batches)
