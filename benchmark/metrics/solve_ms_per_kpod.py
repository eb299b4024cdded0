"""Flight recorder `solve` over the window (device solve with its host side,
ending at readback), per thousand pods bound."""


def read(w):
    if not w.binds_in_window or "solve" not in w.stages_ms:
        return None
    return w.stages_ms["solve"] / (w.binds_in_window / 1000)
