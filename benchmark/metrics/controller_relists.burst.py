"""Change of the sum of `Controller.relists` over the window: a watch that
overflowed or resumed too late and listed again."""


def read(w):
    return float(w.relists)
