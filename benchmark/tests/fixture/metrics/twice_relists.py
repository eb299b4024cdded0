"""A metric that exists only in the fixture."""


def read(w):
    return float(2 * w.relists) if w.relists else None
