"""Decode engine: host-clock milliseconds per decode step over the window
(``RolloutStats.decode_steps``)."""


def read(w):
    n = w.stats.get("decode_steps", 0)
    return 1e3 * w.seconds / n if n else None
