"""Decode engine: mean milliseconds of host time from one decode step's
token read to the next decode dispatch, over pairs of consecutive decoding
steps (Δhost_gap_seconds ÷ Δhost_gaps): time the decode stream gives the
device nothing. None where the program counts no host gaps."""


def read(w):
    n = w.stats.get("host_gaps", 0)
    return 1e3 * w.stats["host_gap_seconds"] / n if n else None
