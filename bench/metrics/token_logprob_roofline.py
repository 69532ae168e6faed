"""Kernels: the token-logprob kernel's share of its roofline, for the
completion positions of the rows trained in the traced window (padding
is not counted as work)."""
from bench import flops

KERNEL = "token_logprob"


def read(w):
    if w.trace is None or not w.commits:
        return None
    sec, n = w.trace.time_of(KERNEL)
    if not n or sec <= 0:
        return None
    positions = sum(int((t - p).sum()) for _, _, _, (p, t) in w.commits)
    f, b = flops.token_logprob(w.shape, positions, len(w.commits))
    least, _ = flops.least_seconds(f, b, w.peak())
    return 100.0 * least / sec
