"""Scheduler: mean milliseconds a row waited in the scheduler queue, from
its push to the pop that handed it to an install path (prefill worker,
fused refill, radix fill or restore) (Δqueue_wait_seconds ÷ Δqueue_pops).
None where the program counts no such pops."""


def read(w):
    n = w.stats.get("queue_pops", 0)
    return 1e3 * w.stats["queue_wait_seconds"] / n if n else None
