"""Flight recorder `bind` over the window (the bind worker's store.bind_many
wall, overlapped with the scheduling thread), per thousand pods bound."""


def read(w):
    if not w.binds_in_window or "bind" not in w.stages_ms:
        return None
    return w.stages_ms["bind"] / (w.binds_in_window / 1000)
