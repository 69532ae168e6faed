"""Whole decode path: FLOPs of the tokens the policy sampled in the traced
window (every projection, LoRA, logits, and attention at their live
context) over the window at the chip's peak. Prefill is not counted."""
from bench import flops


def read(w):
    sampled = w.stats.get("sampled_tokens", 0)
    steps = w.stats.get("occupied_row_steps", 0)
    if not sampled or not w.row_steps or not steps:
        return None
    dense = flops.dense_flops_per_token(w.shape, w.rank) * sampled
    attn = flops.attention_flops(w.shape, w.ctx_sum) * min(1.0, sampled / steps)
    return 100.0 * (dense + attn) / (w.seconds * w.peak()["bf16_flops_per_s"])
