"""1 - (union of the device's busy intervals / traced span), in percent."""


def read(w):
    if w.trace is None or w.trace["idle_share"] is None:
        return None
    return w.trace["idle_share"] * 100
