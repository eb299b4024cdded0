"""Device time of the solver programs over the (traced) window, per
thousand pods bound in it."""

PROGRAMS = ("waterfill_group", "repair_check", "greedy_scan_solve",
            "_auction_phase", "_sinkhorn_iters")


def read(w):
    if w.trace is None or not w.binds_in_window:
        return None
    s = sum(v for k, v in w.trace["programs"].items() if k in PROGRAMS)
    if s <= 0:
        return None
    return s * 1000 / (w.binds_in_window / 1000)
