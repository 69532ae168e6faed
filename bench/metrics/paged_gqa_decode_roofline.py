"""Kernels: the paged decode attention kernel's share of its roofline. The
least time is that of the work the algorithm needs (the live context of
each decoded row at each step, counted by the benchmark) at the chip's
peak; the kernel's time is its device time in the trace."""
from bench import flops

KERNEL = "paged_gqa_decode"


def read(w):
    if w.trace is None or not w.row_steps:
        return None
    sec, n = w.trace.time_of(KERNEL)
    if not n or sec <= 0:
        return None
    f, b = flops.decode_attention(w.shape, w.ctx_sum, w.row_steps)
    least, _ = flops.least_seconds(f, b, w.peak())
    return 100.0 * least / sec
