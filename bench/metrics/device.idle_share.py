"""Device: the share of the traced window in which no operation ran on
the device (1 - busy / window)."""


def read(w):
    if w.trace is None or not w.trace.planes or w.seconds <= 0:
        return None
    return 100.0 * (1.0 - w.trace.busy_s / w.seconds)
