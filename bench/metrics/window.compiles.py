"""jit, program-wide: backend compiles or persistent-cache loads of XLA
programs that happened inside the traced window (there should be none)."""


def read(w):
    return float(w.compiles)
