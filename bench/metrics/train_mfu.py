"""Whole trainer: FLOPs of the steps committed in the traced window, on
the rows' real (unpadded) tokens, one forward and the backward without
recomputation, over the window at the chip's peak."""
from bench import flops


def read(w):
    if not w.commits:
        return None
    total = sum(flops.train_flops(w.shape, w.rank, int(n))
                for _, _, _, (_, t) in w.commits for n in t)
    return 100.0 * total / (w.seconds * w.peak()["bf16_flops_per_s"])
