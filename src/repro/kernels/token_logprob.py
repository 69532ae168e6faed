"""Fused token-logprob — logsumexp + target gather + entropy over the vocab,
without ever materializing [tokens, V] softmax in fp32.

This is the GRPO training-side hot-spot at 150k–256k vocabs (qwen/gemma/
nemotron): the naive path writes tokens·V fp32 logits + softmax (≈ 2 TB for
a 1M-token batch at V=256k); this kernel streams vocab tiles through VMEM
keeping only three [BM] running statistics per row:
  m  (running max),  l = Σ e^{logit−m},  s = Σ logit·e^{logit−m}
so  logprob = logit_tgt − (m + log l)   and  entropy = (m + log l) − s/l.

Grid (row_blocks, V_blocks, K_blocks): K innermost accumulates the logits
tile h·W in VMEM scratch; at the last K slice the online stats fold the
tile in, and the target gather hits at most one tile per row. Targets ride
in as a [BM, 1] VMEM block (vector loads from the scalar-prefetch SMEM
channel are refused by Mosaic). A vocabulary that is not a multiple of 128
is zero-padded to one, vocab tiles are multiples of 128 lanes, and every
column at or past V — the padding and the out-of-bounds tail of a last
partial tile alike — is masked to −∞ before the stats fold it in.

The kernel has no VJP of its own; ``ops.token_logprob`` pairs it with the
chunked jnp backward through ``jax.custom_vjp``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BM = 256
DEFAULT_BV = 1024
DEFAULT_BK = 512
NEG_INF = -1e30
LANES = 128


def _logprob_kernel(tgt_ref, h_ref, w_ref, lp_ref, ent_ref,
                    logits_ref, m_ref, l_ref, s_ref, t_ref,
                    *, n_k, bv, vocab, softcap):
    v = pl.program_id(1)
    k = pl.program_id(2)
    n_v = pl.num_programs(1)

    @pl.when((v == 0) & (k == 0))
    def _init_row():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        s_ref[...] = jnp.zeros_like(s_ref)
        t_ref[...] = jnp.full_like(t_ref, NEG_INF)

    @pl.when(k == 0)
    def _init_tile():
        logits_ref[...] = jnp.zeros_like(logits_ref)

    h = h_ref[...].astype(jnp.float32)               # [BM, BK]
    w = w_ref[...].astype(jnp.float32)               # [BK, BV]
    logits_ref[...] += jax.lax.dot_general(
        h, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _fold():
        logits = logits_ref[...]                     # [BM, BV]
        if softcap:
            logits = jnp.tanh(logits / softcap) * softcap
        cols = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
        logits = jnp.where(v * bv + cols < vocab, logits, NEG_INF)
        # target gather: ids within this vocab tile
        local = tgt_ref[...] - v * bv                # [BM, 1]
        hit = cols == local
        t_ref[...] = jnp.maximum(
            t_ref[...],
            jnp.max(jnp.where(hit, logits, NEG_INF), axis=1, keepdims=True))
        # online lse/entropy stats
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(logits, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(logits - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        s_ref[...] = s_ref[...] * alpha + jnp.sum(p * logits, axis=1,
                                                  keepdims=True)
        m_ref[...] = m_new

        @pl.when(v == n_v - 1)
        def _flush():
            lse = m_ref[...] + jnp.log(jnp.maximum(l_ref[...], 1e-30))
            lp_ref[...] = (t_ref[...] - lse).astype(lp_ref.dtype)
            ent_ref[...] = (lse - s_ref[...] /
                            jnp.maximum(l_ref[...], 1e-30)).astype(ent_ref.dtype)


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


@functools.partial(jax.jit, static_argnames=("bm", "bv", "bk", "softcap",
                                             "interpret"))
def token_logprob_flat(h, w, targets, *, bm=DEFAULT_BM, bv=DEFAULT_BV,
                       bk=DEFAULT_BK, softcap=0.0, interpret=None):
    """h: [R, d]; w: [d, V]; targets: [R] int32.
    Returns (logprob [R], entropy [R]) float32. `bv` is rounded up to a
    multiple of 128 lanes."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    R, d = h.shape
    V = w.shape[1]
    Vp = _round_up(V, LANES)
    bm = min(bm, max(8, R))
    bv = min(_round_up(bv, LANES), Vp)
    bk = min(bk, d)
    Rp = _round_up(R, bm)
    dp = _round_up(d, bk)
    if Rp != R:
        h = jnp.pad(h, ((0, Rp - R), (0, 0)))
        targets = jnp.pad(targets, (0, Rp - R))
    if dp != d or Vp != V:
        h = jnp.pad(h, ((0, 0), (0, dp - d)))
        w = jnp.pad(w, ((0, dp - d), (0, Vp - V)))
    n_k = dp // bk
    lp, ent = pl.pallas_call(
        functools.partial(_logprob_kernel, n_k=n_k, bv=bv, vocab=V,
                          softcap=softcap),
        grid=(Rp // bm, pl.cdiv(Vp, bv), n_k),
        in_specs=[
            pl.BlockSpec((bm, 1), lambda i, v, k: (i, 0)),
            pl.BlockSpec((bm, bk), lambda i, v, k: (i, k)),
            pl.BlockSpec((bk, bv), lambda i, v, k: (k, v)),
        ],
        out_specs=[
            pl.BlockSpec((bm, 1), lambda i, v, k: (i, 0)),
            pl.BlockSpec((bm, 1), lambda i, v, k: (i, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((bm, bv), jnp.float32),
            pltpu.VMEM((bm, 1), jnp.float32),
            pltpu.VMEM((bm, 1), jnp.float32),
            pltpu.VMEM((bm, 1), jnp.float32),
            pltpu.VMEM((bm, 1), jnp.float32),
        ],
        out_shape=[jax.ShapeDtypeStruct((Rp, 1), jnp.float32),
                   jax.ShapeDtypeStruct((Rp, 1), jnp.float32)],
        interpret=interpret,
    )(targets.astype(jnp.int32).reshape(Rp, 1), h, w)
    return lp[:R, 0], ent[:R, 0]
