"""XLA compile time that the solve stage paid (its parts included), summed
over the window's batch records, per thousand pods bound."""


def read(w):
    recs = [r for r in w.batches if "compile_ms" in r]
    if not recs or not w.binds_in_window:
        return None
    ms = sum(r["compile_ms"].get("solve", 0.0) for r in recs)
    return ms / (w.binds_in_window / 1000)
