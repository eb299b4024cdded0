"""The solve stage's `solve.upload` part (device views and input uploads)
summed over the window's batch records, per thousand pods bound."""


def read(w):
    recs = [r for r in w.batches if "parts_ms" in r]
    if not recs or not w.binds_in_window:
        return None
    ms = sum(r["parts_ms"].get("solve.upload", 0.0) for r in recs)
    return ms / (w.binds_in_window / 1000)
