def read(w):
    return w.setup_s
