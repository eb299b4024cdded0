"""Flight recorder `ingest` + `queue_add` over the window, per thousand pods
bound in it: the watch pump's decode, cache ingest and queue admission."""


def read(w):
    if not w.binds_in_window:
        return None
    ms = w.stages_ms.get("ingest", 0.0) + w.stages_ms.get("queue_add", 0.0)
    return ms / (w.binds_in_window / 1000)
