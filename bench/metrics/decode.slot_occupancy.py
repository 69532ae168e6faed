"""Decode engine: advanced rows per slot-step (Δoccupied_row_steps ÷
Δcapacity_row_steps)."""


def read(w):
    cap = w.stats.get("capacity_row_steps", 0)
    return w.stats["occupied_row_steps"] / cap if cap else None
