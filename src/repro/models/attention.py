"""GQA attention: chunked-query training/prefill path + cached decode path.

Variants covered (per assigned archs): grouped KV heads, QKV bias (qwen1.5),
qk-norm (chameleon/qwen3), score softcap (gemma2), sliding-window +
local/global alternation (gemma2), bidirectional (seamless encoder) and
cross-attention (seamless decoder).

`window` may be a python int OR a traced scalar (gemma2 passes a per-layer
window array through the layer scan); 0 means global attention. All masking
uses data-dependent `jnp.where`, never python branches.

Memory: the training path scans over query chunks so peak live score memory
is [B, H, q_chunk, S] instead of [B, H, S, S]; combined with per-layer remat
this keeps 32k-prefill lowerable at full config. The decode path is a single
masked softmax over the cache (the Pallas ``gqa_decode`` kernel and the
shard_map flash-decode in ``launch`` are the optimized variants).
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs import ModelConfig
from .common import LoraCtx, apply_rope, dense_init, proj, rmsnorm, rmsnorm_init, softcap

_NO_WINDOW = jnp.iinfo(jnp.int32).max - 1


class AttnParams(NamedTuple):
    wq: jax.Array
    wk: jax.Array
    wv: jax.Array
    wo: jax.Array
    bq: Optional[jax.Array] = None
    bk: Optional[jax.Array] = None
    bv: Optional[jax.Array] = None
    q_norm: Optional[jax.Array] = None
    k_norm: Optional[jax.Array] = None


def attn_init(key, cfg: ModelConfig, dtype) -> AttnParams:
    kq, kk, kv, ko = jax.random.split(key, 4)
    d, qd, kvd = cfg.d_model, cfg.q_dim, cfg.kv_dim
    return AttnParams(
        wq=dense_init(kq, d, qd, dtype),
        wk=dense_init(kk, d, kvd, dtype),
        wv=dense_init(kv, d, kvd, dtype),
        wo=dense_init(ko, qd, d, dtype),
        bq=jnp.zeros((qd,), dtype) if cfg.qkv_bias else None,
        bk=jnp.zeros((kvd,), dtype) if cfg.qkv_bias else None,
        bv=jnp.zeros((kvd,), dtype) if cfg.qkv_bias else None,
        q_norm=rmsnorm_init(cfg.head_dim, dtype) if cfg.qk_norm else None,
        k_norm=rmsnorm_init(cfg.head_dim, dtype) if cfg.qk_norm else None,
    )


def qkv(x, p: AttnParams, cfg: ModelConfig, positions, lora: Optional[LoraCtx],
        rope: bool = True):
    """Project + reshape to heads (+ qk-norm + RoPE). x: [B, S, d]."""
    B, S, _ = x.shape
    q = proj(x, p.wq, p.bq, lora=lora, name="attn_q").reshape(B, S, cfg.num_heads, cfg.head_dim)
    k = proj(x, p.wk, p.bk, lora=lora, name="attn_k").reshape(B, S, cfg.num_kv_heads, cfg.head_dim)
    v = proj(x, p.wv, p.bv, lora=lora, name="attn_v").reshape(B, S, cfg.num_kv_heads, cfg.head_dim)
    if cfg.qk_norm:
        q = rmsnorm(q, p.q_norm, cfg.norm_eps)
        k = rmsnorm(k, p.k_norm, cfg.norm_eps)
    if rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def repeat_kv(k, n_rep: int):
    """[B, S, KVH, hd] -> [B, S, KVH*n_rep, hd]."""
    if n_rep == 1:
        return k
    b, s, h, d = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (b, s, h, n_rep, d)).reshape(b, s, h * n_rep, d)


def _effective_window(window):
    """int-or-traced window; 0 → 'no window' sentinel."""
    w = jnp.asarray(window, jnp.int32)
    return jnp.where(w > 0, w, _NO_WINDOW)


def _pair_mask(q_pos, k_pos, *, causal: bool, window):
    """[Sq, Sk] boolean mask (True = attend)."""
    diff = q_pos[:, None] - k_pos[None, :]
    m = diff < _effective_window(window)
    if causal:
        m &= diff >= 0
    return m


def attention_dense(q, k, v, cfg: ModelConfig, *, causal: bool, window=0):
    """Plain softmax attention. q:[B,Sq,H,hd], k/v:[B,Sk,KVH,hd]."""
    B, Sq, H, hd = q.shape
    Sk = k.shape[1]
    k = repeat_kv(k, H // cfg.num_kv_heads)
    v = repeat_kv(v, H // cfg.num_kv_heads)
    scale = 1.0 / jnp.sqrt(jnp.float32(hd))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    s = softcap(s, cfg.attn_softcap)
    mask = _pair_mask(jnp.arange(Sq), jnp.arange(Sk), causal=causal, window=window)
    s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def attention_chunked(q, k, v, cfg: ModelConfig, *, causal: bool,
                      window=0, q_chunk: int = 512):
    """Query-chunked attention: scan over q chunks; peak memory
    [B, H, q_chunk, Sk]. Used for train/prefill at long sequence length."""
    B, Sq, H, hd = q.shape
    if Sq <= q_chunk:
        return attention_dense(q, k, v, cfg, causal=causal, window=window)
    assert Sq % q_chunk == 0, (Sq, q_chunk)
    Sk = k.shape[1]
    k = repeat_kv(k, H // cfg.num_kv_heads)
    v = repeat_kv(v, H // cfg.num_kv_heads)
    scale = 1.0 / jnp.sqrt(jnp.float32(hd))
    nq = Sq // q_chunk
    qc = q.reshape(B, nq, q_chunk, H, hd).transpose(1, 0, 2, 3, 4)
    k_pos = jnp.arange(Sk)
    win = _effective_window(window)

    def body(carry, inp):
        qi, i = inp
        q_pos = i * q_chunk + jnp.arange(q_chunk)
        s = jnp.einsum("bqhd,bkhd->bhqk", qi, k).astype(jnp.float32) * scale
        s = softcap(s, cfg.attn_softcap)
        diff = q_pos[:, None] - k_pos[None, :]
        m = diff < win
        if causal:
            m &= diff >= 0
        s = jnp.where(m[None, None], s, -1e30)
        p = jax.nn.softmax(s, axis=-1).astype(qi.dtype)
        return carry, jnp.einsum("bhqk,bkhd->bqhd", p, v)

    _, out = jax.lax.scan(body, None, (qc, jnp.arange(nq)))
    return out.transpose(1, 0, 2, 3, 4).reshape(B, Sq, H, hd)


def attention_prefill_chunk(q, k, v, cfg: ModelConfig, *, q_start: int,
                            window=0):
    """Chunk-incremental prefill attention: queries sit at ABSOLUTE
    positions ``q_start .. q_start+Sq``, keys/values are the cache read
    back over ``[0, k_len)`` (prior chunks + this one, already RoPE'd at
    their absolute positions when written).

    Numerically this is `attention_dense` with an offset causal mask: the
    same repeat_kv / einsum / softcap / softmax op sequence, so a prompt
    prefilled chunk-by-chunk reproduces the fused whole-prompt prefill
    token-for-token (masked lanes contribute exact zeros either way)."""
    B, Sq, H, hd = q.shape
    Sk = k.shape[1]
    k = repeat_kv(k, H // cfg.num_kv_heads)
    v = repeat_kv(v, H // cfg.num_kv_heads)
    scale = 1.0 / jnp.sqrt(jnp.float32(hd))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k.astype(q.dtype)).astype(jnp.float32) * scale
    s = softcap(s, cfg.attn_softcap)
    mask = _pair_mask(q_start + jnp.arange(Sq), jnp.arange(Sk),
                      causal=True, window=window)
    s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(q.dtype))


def _static_window(window) -> bool:
    """True when `window` is a python int (the Pallas decode kernels take
    it as a static arg). gemma2 passes its per-layer window as an array
    TRACED through the layer scan; that one case runs the einsum path
    even under ``use_kernel=True`` — every other decode shape runs the
    kernel."""
    return isinstance(window, int)


def attention_decode(q, cache_k, cache_v, pos, cfg: ModelConfig, *, window=0,
                     use_kernel: bool = False):
    """Single-token decode. q: [B, H, hd]; cache: [B, Smax, KVH, hd];
    pos: [B] number of valid cache entries (incl. the just-written token).

    GQA is computed in grouped-einsum form — materializing repeat_kv'd
    caches costs rep× the decode step's HBM traffic (measured 10GB/step at
    granite decode_32k — EXPERIMENTS.md §Perf iter A2). Under
    ``use_kernel=True`` the Pallas ``gqa_decode`` flash-decode kernel runs
    instead for every cache length (its KV block is a divisor of Smax);
    only gemma2's traced per-layer window stays on the einsum path, which
    is retained as the oracle the kernel is parity-tested against.
    """
    B, H, hd = q.shape
    Smax = cache_k.shape[1]
    if use_kernel and _static_window(window):
        from repro.kernels.gqa_decode import gqa_decode
        return gqa_decode(q, cache_k, cache_v, pos,
                          softcap=float(cfg.attn_softcap or 0.0),
                          window=int(window))
    KVH = cache_k.shape[2]
    rep = H // KVH
    qg = q.reshape(B, KVH, rep, hd)
    scale = 1.0 / jnp.sqrt(jnp.float32(hd))
    s = jnp.einsum("bgrd,bkgd->bgrk", qg, cache_k).astype(jnp.float32) * scale
    s = softcap(s, cfg.attn_softcap)
    idx = jnp.arange(Smax)
    valid = idx[None, :] < pos[:, None]                       # [B, Smax]
    valid &= (pos[:, None] - 1 - idx[None, :]) < _effective_window(window)
    s = jnp.where(valid[:, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    o = jnp.einsum("bgrk,bkgd->bgrd", p, cache_v)
    return o.reshape(B, H, hd)


def attention_decode_paged(q, kp, vp, tbl, pos, cfg: ModelConfig, *,
                           window=0, use_kernel: bool = False):
    """Single-token decode over the block-pool (paged) KV cache.

    q: [B, H, hd]; kp/vp: [n_pages+1, page, KVH, hd] — the shared page
    pool (physical page ``n_pages`` is the scratch page that sentinel
    block-table entries point at); tbl: [B, max_pages] int32 physical page
    ids; pos: [B] valid entries.

    Oracle path: gather each row's pages into a contiguous
    [B, max_pages·page, KVH, hd] view and reuse ``attention_decode`` — the
    gathered values are bit-identical to what a dense cache would hold at
    the same positions, and every position ≥ pos (incl. anything a
    sentinel entry dragged in from the scratch page) is masked, so paged
    output == dense output exactly. Under ``use_kernel=True`` the Pallas
    ``paged_gqa_decode`` kernel reads the pages in place via a
    scalar-prefetched block table instead (no contiguous gather ever
    materializes); gemma2's traced per-layer window is the one case that
    keeps the oracle path."""
    if use_kernel and _static_window(window):
        from repro.kernels.paged_decode import paged_gqa_decode
        return paged_gqa_decode(q, kp, vp, tbl, pos,
                                softcap=float(cfg.attn_softcap or 0.0),
                                window=int(window))
    B = q.shape[0]
    page, KVH, hd = kp.shape[1], kp.shape[2], kp.shape[3]
    n_pg = tbl.shape[1]
    ck = jnp.take(kp, tbl, axis=0).reshape(B, n_pg * page, KVH, hd)
    cv = jnp.take(vp, tbl, axis=0).reshape(B, n_pg * page, KVH, hd)
    return attention_decode(q, ck, cv, pos, cfg, window=window)
