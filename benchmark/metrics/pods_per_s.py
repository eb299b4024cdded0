"""Pods whose binding arrived inside the window, per second of window."""


def read(w):
    return w.binds_in_window / w.window_s if w.binds_in_window else None
