"""Env stage to decode hand-off: mean milliseconds from a tool response's
arrival to its row's return into a decode slot, by restore, splice, radix
fill or refill (Δresume_wait_seconds ÷ Δresumes_installed). None where the
program counts no such installs."""


def read(w):
    n = w.stats.get("resumes_installed", 0)
    return 1e3 * w.stats["resume_wait_seconds"] / n if n else None
