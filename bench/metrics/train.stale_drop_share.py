"""Trainer hand-off: rows dropped as stale per completed row in the window
(manager ``stale_rows_dropped`` ÷ episodes finished)."""


def read(w):
    n = len(w.episodes)
    return w.drops.get("stale_rows_dropped", 0) / n if n else None
