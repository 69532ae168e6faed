"""Operations and bytes that the algorithm needs, from shapes alone.

Counted from the traffic actually processed, never from a kernel's grid,
padded pages or padded positions, so a kernel that skips padding reads
closer to its roofline and none can read above it. ``s`` is the shape
dict of ``bench/configs/dense_gqa.shape``; bf16 weights and KV (2 bytes).
"""
from __future__ import annotations

import json
from pathlib import Path

BF16 = 2


def peaks(device_kind: str) -> dict:
    """The peak table's row for this device; a device missing from it is
    an error, never a default."""
    table = json.loads((Path(__file__).parent / "peaks.json").read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json (have {sorted(table)})")
    return table[device_kind]


def layer_params(s: dict) -> int:
    """Weights of one layer's projections (q, k, v, o, MLP in and out)."""
    d, qd, kvd, ff = s["d"], s["H"] * s["hd"], s["KVH"] * s["hd"], s["ff"]
    return d * qd + 2 * d * kvd + qd * d + d * 2 * ff + ff * d


def lora_params(s: dict, rank: int) -> int:
    """LoRA weights of one layer on q, k, v, o, MLP in and out."""
    d, qd, kvd, ff = s["d"], s["H"] * s["hd"], s["KVH"] * s["hd"], s["ff"]
    dims = [(d, qd), (d, kvd), (d, kvd), (qd, d), (d, 2 * ff), (ff, d)]
    return rank * sum(i + o for i, o in dims)


def dense_flops_per_token(s: dict, rank: int) -> int:
    """Forward matmul FLOPs of one token without attention scores: every
    projection, its LoRA, and the output logits."""
    return 2 * (s["L"] * (layer_params(s) + lora_params(s, rank))
                + s["d"] * s["V"])


def attention_flops(s: dict, ctx: int) -> int:
    """Scores and weighted sum of one query over ``ctx`` keys, all layers."""
    return 4 * s["L"] * s["H"] * s["hd"] * ctx


def decode_attention(s: dict, ctx_sum: int, row_steps: int):
    """(FLOPs, bytes) of paged decode attention over ``row_steps`` decoded
    rows whose live contexts sum to ``ctx_sum``: K and V of every live
    position read once, q read and the output written once per row."""
    flops = attention_flops(s, ctx_sum)
    kv = ctx_sum * s["L"] * 2 * s["KVH"] * s["hd"] * BF16
    qo = row_steps * s["L"] * 2 * s["H"] * s["hd"] * BF16
    return flops, kv + qo


def token_logprob(s: dict, positions: int, calls: int):
    """(FLOPs, bytes) of the log-probability of ``positions`` completion
    tokens over the whole vocabulary: logits matmul, the output weights
    read once per call, hidden states read and logprobs written once."""
    flops = 2 * positions * s["d"] * s["V"]
    nbytes = (calls * s["d"] * s["V"] * BF16 + positions * s["d"] * BF16
              + positions * 4)
    return flops, nbytes


def train_flops(s: dict, rank: int, length: int) -> int:
    """One forward and the backward of one trained row of ``length`` real
    tokens, no recomputation: the forward (projections, LoRA, causal
    attention, logits), the activation gradients through the frozen base
    (the same matmuls again, attention twice), and LoRA's weight
    gradients."""
    n = length
    dense = dense_flops_per_token(s, rank) * n
    attn = 2 * s["L"] * s["H"] * s["hd"] * n * (n + 1)   # causal: sum of ctx
    lora_w = 2 * s["L"] * lora_params(s, rank) * n
    return 2 * dense + 3 * attn + lora_w


def least_seconds(flops: float, nbytes: float, pk: dict):
    """The least time the chip could take, and which bound sets it."""
    tc = flops / pk["bf16_flops_per_s"]
    tm = nbytes / pk["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
