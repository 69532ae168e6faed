"""Prefill-to-decode hand-off: mean milliseconds a prefilled row waited
for its splice into a decode slot (Δsplice_wait_seconds ÷ Δsplices)."""


def read(w):
    n = w.stats.get("splices", 0)
    return 1e3 * w.stats["splice_wait_seconds"] / n if n else None
