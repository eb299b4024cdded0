"""XLA compiles inside the window (JAX monitoring events); 0 when every
shape was warmed in set-up."""


def read(w):
    return float(w.compiles_in_window)
