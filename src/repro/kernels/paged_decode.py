"""Paged GQA flash-decode — single-token attention over a BLOCK-POOL KV
cache (the PagedAttention role proper; ``gqa_decode`` is its contiguous
-cache sibling).

The cache is a pool of fixed-size pages ``[n_pages+1, page, KVH, hd]``
shared by every decode slot; each row names its pages through a block
table ``tbl: [B, max_pages]`` (entries are physical page ids; unused
entries point at the scratch page ``n_pages``). One grid step = one
(batch row, LOGICAL page): the block table rides the scalar-prefetch
channel, so the BlockSpec ``index_map`` resolves logical page ``s`` of row
``b`` to its physical page ``tbl[b, s]`` BEFORE the kernel body runs — the
whole ``[page, KVH, hd]`` page tile (every KV head) is DMA'd straight from
its pooled location in one contiguous copy, no gather materializes a
contiguous cache. The block spans all KV heads because Mosaic requires a
block's second-minor dimension to be a multiple of 8 or the full array
dimension; a one-head block (``(1, page, 1, hd)``) is refused. Inside the
body a static loop over the KVH groups runs the ``gqa_decode`` online
(running max / sum / weighted-acc) softmax for the rep = H/KVH query heads
sharing each KV head, with per-group VMEM scratch carried across logical
pages; per-row valid length (`pos`), optional sliding window and gemma2's
score softcap are applied per page.

Pages past a row's live count resolve to the scratch page (or any page —
their positions are ≥ pos and fully masked), so a short row costs the
same DMAs as dense only in grid steps, not in pool HBM: the pool holds
Σ ceil(len_i / page) pages instead of B × max_len rows, which is the
whole point: no per-slot max_len reservation.

VMEM per step: 2·page·KVH·hd cache tile + H·hd acc — the same budget as
``gqa_decode`` at bs == page.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _paged_kernel(pos_ref, tbl_ref, q_ref, k_ref, v_ref, o_ref,
                  m_ref, l_ref, acc_ref, *, n_s, page, softcap, window,
                  scale):
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
        k = k_ref[0, :, g].astype(jnp.float32)       # [page, hd]
        v = v_ref[0, :, g].astype(jnp.float32)       # [page, hd]
        scores = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # [rep, page]
        if softcap:
            scores = jnp.tanh(scores / softcap) * softcap
        # logical (pre-paging) position of each lane in this page
        idx = s * page + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
        valid = idx < pos
        if window:
            valid &= (pos - 1 - idx) < window
        scores = jnp.where(valid, scores, NEG_INF)

        m_prev = m_ref[g]                            # [rep, 1]
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(scores - m_new)                  # [rep, page]
        l_ref[g] = l_ref[g] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[g] = acc_ref[g] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[g] = m_new

    @pl.when(s == n_s - 1)
    def _flush():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("softcap", "window", "interpret"))
def paged_gqa_decode(q, kp, vp, tbl, pos, *, softcap=0.0, window=0,
                     interpret=None):
    """q: [B, H, hd]; kp/vp: [n_pages+1, page, KVH, hd] (page pool, last
    physical page is the scratch page sentinel entries point at);
    tbl: [B, max_pages] int32 physical page ids; pos: [B] valid lengths
    (including the just-written token). Returns [B, H, hd]."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    B, H, hd = q.shape
    page, KVH = kp.shape[1], kp.shape[2]
    n_s = tbl.shape[1]
    rep = H // KVH
    scale = 1.0 / (hd ** 0.5)

    qg = q.reshape(B, KVH, rep, hd)
    grid = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,       # pos, then the block table
        grid=(B, n_s),
        in_specs=[
            pl.BlockSpec((1, KVH, rep, hd), lambda b, s, p, t: (b, 0, 0, 0)),
            pl.BlockSpec((1, page, KVH, hd),
                         lambda b, s, p, t: (t[b, s], 0, 0, 0)),
            pl.BlockSpec((1, page, KVH, hd),
                         lambda b, s, p, t: (t[b, s], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, KVH, rep, hd),
                               lambda b, s, p, t: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((KVH, rep, 1), jnp.float32),
            pltpu.VMEM((KVH, rep, 1), jnp.float32),
            pltpu.VMEM((KVH, rep, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_kernel, n_s=n_s, page=page, softcap=softcap,
                          window=window, scale=scale),
        grid_spec=grid,
        out_shape=jax.ShapeDtypeStruct((B, KVH, rep, hd), q.dtype),
        interpret=interpret,
    )(pos.astype(jnp.int32), tbl.astype(jnp.int32), qg, kp, vp)
    return out.reshape(B, H, hd)
