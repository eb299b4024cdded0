"""Process start to window open: control plane, nodes, leases, init pods,
warm-up (and, on a checkout's first run, compilation)."""


def read(w):
    return w.setup_s
