"""Prefill and prefix cache: tokens prefilled or replayed per policy-sampled
token (Δ(prefill_tokens + replay_tokens) ÷ Δsampled_tokens)."""


def read(w):
    n = w.stats.get("sampled_tokens", 0)
    if not n:
        return None
    return (w.stats["prefill_tokens"] + w.stats["replay_tokens"]) / n
