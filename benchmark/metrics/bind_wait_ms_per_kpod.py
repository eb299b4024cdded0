"""Flight recorder `bind_wait` over the window (the scheduling thread
waiting for the bind worker's store.bind_many), per thousand pods bound."""


def read(w):
    if not w.binds_in_window or "bind_wait" not in w.stages_ms:
        return None
    return w.stages_ms["bind_wait"] / (w.binds_in_window / 1000)
