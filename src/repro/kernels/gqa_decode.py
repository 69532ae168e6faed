"""GQA flash-decode — single-token attention over a long KV cache, the
per-step memory-bound core of rollout decode (vLLM's PagedAttention role,
TPU-adapted: contiguous block tiles in VMEM instead of pages — DESIGN.md §3).

One grid step = one (batch row, KV block): the block is ``[BS, KVH, hd]``
— every KV head, because Mosaic requires a block's second-minor dimension
to be a multiple of 8 or the full array dimension, so a one-head block is
refused. A static loop over the KVH groups lets the rep = H/KVH query
heads sharing each KV head attend to its [BS, hd] slice with an online
(running max / sum / weighted-acc) softmax carried in per-group VMEM
scratch across KV blocks. Per-row valid length (`pos`) and optional
sliding window are masked inside; gemma2's score softcap is applied
pre-softmax. BS is the largest divisor of the cache length up to the
requested block (a multiple of 8 where one exists), so every cache length
runs through the kernel.

VMEM per step: 2·BS·KVH·hd cache tiles + H·hd acc ≈ 4 MB at BS=512,
KVH=8, hd=128 in bf16, double-buffered — within the v5e scoped budget;
the kernel is HBM-bandwidth-bound by design (reads each cache byte
exactly once).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BS = 512
NEG_INF = -1e30


def _decode_kernel(pos_ref, q_ref, k_ref, v_ref, o_ref,
                   m_ref, l_ref, acc_ref, *, n_s, bs, softcap, window, scale):
    b = pl.program_id(0)
    s = pl.program_id(1)

    @pl.when(s == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    pos = pos_ref[b]
    for g in range(k_ref.shape[2]):                  # static: one KV head
        q = q_ref[0, g].astype(jnp.float32)          # [rep, hd]
        k = k_ref[0, :, g].astype(jnp.float32)       # [BS, hd]
        v = v_ref[0, :, g].astype(jnp.float32)       # [BS, hd]
        scores = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # [rep, BS]
        if softcap:
            scores = jnp.tanh(scores / softcap) * softcap
        idx = s * bs + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
        valid = idx < pos
        if window:
            valid &= (pos - 1 - idx) < window
        scores = jnp.where(valid, scores, NEG_INF)

        m_prev = m_ref[g]                            # [rep, 1]
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(scores - m_new)                  # [rep, BS]
        l_ref[g] = l_ref[g] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[g] = acc_ref[g] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[g] = m_new

    @pl.when(s == n_s - 1)
    def _flush():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def block_len(S: int, bs: int) -> int:
    """Largest divisor of the cache length S that is at most `bs`,
    preferring multiples of 8 (sublane-aligned KV tiles)."""
    divs = [d for d in range(1, min(bs, S) + 1) if S % d == 0]
    aligned = [d for d in divs if d % 8 == 0]
    return max(aligned or divs)


@functools.partial(jax.jit,
                   static_argnames=("bs", "softcap", "window", "interpret"))
def gqa_decode(q, cache_k, cache_v, pos, *, bs=DEFAULT_BS, softcap=0.0,
               window=0, interpret=None):
    """q: [B, H, hd]; cache_k/v: [B, S, KVH, hd]; pos: [B] valid lengths
    (including the just-written token). Returns [B, H, hd]."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    B, H, hd = q.shape
    S, KVH = cache_k.shape[1], cache_k.shape[2]
    rep = H // KVH
    bs = block_len(S, bs)
    n_s = S // bs
    scale = 1.0 / (hd ** 0.5)

    qg = q.reshape(B, KVH, rep, hd)
    grid = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, n_s),
        in_specs=[
            pl.BlockSpec((1, KVH, rep, hd), lambda b, s, p: (b, 0, 0, 0)),
            pl.BlockSpec((1, bs, KVH, hd), lambda b, s, p: (b, s, 0, 0)),
            pl.BlockSpec((1, bs, KVH, hd), lambda b, s, p: (b, s, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, KVH, rep, hd), lambda b, s, p: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((KVH, rep, 1), jnp.float32),
            pltpu.VMEM((KVH, rep, 1), jnp.float32),
            pltpu.VMEM((KVH, rep, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_decode_kernel, n_s=n_s, bs=bs, softcap=softcap,
                          window=window, scale=scale),
        grid_spec=grid,
        out_shape=jax.ShapeDtypeStruct((B, KVH, rep, hd), q.dtype),
        interpret=interpret,
    )(pos.astype(jnp.int32), qg, cache_k, cache_v)
    return out.reshape(B, H, hd)
