"""Flight recorder `tensorize` + `build_pod_batch` over the window, per
thousand pods bound: snapshot, cluster tensors and the pod batch."""


def read(w):
    if not w.binds_in_window:
        return None
    ms = w.stages_ms.get("tensorize", 0.0) + w.stages_ms.get("build_pod_batch", 0.0)
    return ms / (w.binds_in_window / 1000)
