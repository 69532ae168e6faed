"""Paged GQA flash-decode — single-token attention over a BLOCK-POOL KV
cache (the PagedAttention role proper; ``gqa_decode`` is its contiguous
-cache sibling).

The cache is a pool of fixed-size pages ``[n_pages+1, page, KVH, hd]``
shared by every decode slot; each row names its pages through a block
table ``tbl: [B, max_pages]`` (entries are physical page ids; unused
entries point at the scratch page ``n_pages``).

Each row's walk visits only its LIVE pages: ``ceil(pos / page)`` of them,
or, under a static window, those from the first page inside the window
on (``pos`` and the block table ride the scalar-prefetch channel). Block
-table entries past a row's live count are never read, by DMA or by
compute, and may hold any page id. The walk takes ``ppb`` pages per
compute block: the smallest count with ``ppb·page ≥ 128`` tokens, so a
score row fills the 128 lanes, capped by the table width.

Per block, a static loop over the KV heads runs the ``gqa_decode`` online
(running max / sum / weighted-acc, all float32) softmax for the rep =
H/KVH query heads sharing each KV head, on ``[rep, ppb·page]`` scores.
``q·k`` takes the operands in their own dtype (bf16 products are exact in
float32) with float32 accumulation; ``p·v`` keeps ``p`` in float32. Per
-row valid length (`pos`), optional sliding window and gemma2's score
softcap are applied per block; a masked position takes no part whatever
it holds (NaN included), and a row of length 0 reads nothing and returns
zeros.

The pipeline fetches the pages: grid (row, block), one block spec per
page of a block, each resolved through the table. A block past the row's
last live one repeats that block's page ids, and a page past the live
count names the scratch page, so the pipeline fetches nothing for them;
the step only pays the grid's own overhead.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128


def pages_per_block(page: int, max_pages: int) -> int:
    """The smallest page count whose tokens fill the 128 lanes of a score
    row, capped by the block-table width."""
    return max(1, min(max_pages, -(-LANES // page)))


def _span(p, page, ppb, window):
    """A row of length p: its first visited page, live page count and
    block count."""
    n_live = (p + page - 1) // page
    first = jnp.maximum(p - window, 0) // page if window else 0
    return first, n_live, (n_live - first + ppb - 1) // ppb


def _kernel(pos_ref, tbl_ref, q_ref, *refs, page, ppb, softcap, window,
            scale):
    """Grid (row, block): the pipeline brings the block's pages, one block
    spec each; blocks past the row's last live one compute nothing."""
    k_refs, v_refs = refs[:ppb], refs[ppb:2 * ppb]
    o_ref, m_ref, l_ref, acc_ref = refs[2 * ppb:]
    b, i = pl.program_id(0), pl.program_id(1)
    pos = pos_ref[b]
    first, _, n_blk = _span(pos, page, ppb, window)

    @pl.when(i == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(i < n_blk)
    def _():
        T = ppb * page
        base = (first + i * ppb) * page
        idx = base + jax.lax.broadcasted_iota(jnp.int32, (1, T), 1)
        valid = idx < pos
        if window:
            valid &= (pos - 1 - idx) < window
        rows_valid = (base + jax.lax.broadcasted_iota(jnp.int32, (T, 1), 0)
                      < pos)
        for g in range(q_ref.shape[1]):              # static: one KV head
            k, v = (jnp.concatenate([r[0, :, g, :] for r in pages], axis=0)
                    for pages in (k_refs, v_refs))   # each [T, hd]
            q = q_ref[0, g]                          # [rep, hd]
            cd = jnp.promote_types(q.dtype, k.dtype)
            s = jax.lax.dot_general(
                q.astype(cd), k.astype(cd), (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale   # [rep, T]
            if softcap:
                s = jnp.tanh(s / softcap) * softcap
            s = jnp.where(valid, s, NEG_INF)
            # p is 0 on masked rows of V, and 0·NaN would still poison the sum
            v = jnp.where(rows_valid, v.astype(jnp.float32), 0.0)
            m_prev = m_ref[g]                        # [rep, 1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)                   # [rep, T]
            l_ref[g] = l_ref[g] * alpha + jnp.sum(p, axis=1, keepdims=True)
            acc_ref[g] = acc_ref[g] * alpha + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[g] = m_new

    @pl.when(i == pl.num_programs(1) - 1)
    def _():
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
    n_pages, page, KVH = kp.shape[0] - 1, kp.shape[1], kp.shape[2]
    n_s = tbl.shape[1]
    rep = H // KVH
    ppb = pages_per_block(page, n_s)
    kernel = functools.partial(_kernel, page=page, ppb=ppb, softcap=softcap,
                               window=window, scale=1.0 / (hd ** 0.5))
    row_spec = pl.BlockSpec((1, KVH, rep, hd),
                            lambda b, i, p, t: (b, 0, 0, 0))

    def page_spec(j):
        def index(b, i, p, t):
            first, n_live, n_blk = _span(p[b], page, ppb, window)
            s = first + jnp.minimum(i, jnp.maximum(n_blk - 1, 0)) * ppb + j
            pid = t[b * n_s + jnp.minimum(s, n_s - 1)]
            return jnp.where(s < n_live, pid, n_pages), 0, 0, 0
        return pl.BlockSpec((1, page, KVH, hd), index)

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,   # pos, then the flattened block table
            grid=(B, -(-n_s // ppb)),
            in_specs=[row_spec] + [page_spec(j) for j in range(ppb)] * 2,
            out_specs=row_spec,
            scratch_shapes=[pltpu.VMEM((KVH, rep, 1), jnp.float32),
                            pltpu.VMEM((KVH, rep, 1), jnp.float32),
                            pltpu.VMEM((KVH, rep, hd), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((B, KVH, rep, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="paged_gqa_decode",
    )(pos.astype(jnp.int32), tbl.astype(jnp.int32).reshape(-1),
      q.reshape(B, KVH, rep, hd), *(kp,) * ppb, *(vp,) * ppb)
    return out.reshape(B, H, hd)
