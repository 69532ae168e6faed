"""Public jit'd wrappers around the Pallas kernels — the API surface the
model/RL layers call (kernels auto-interpret on CPU, compile on TPU)."""
from __future__ import annotations

import functools

import jax

from repro.rl.grpo import token_logprobs_chunked
from .gqa_decode import gqa_decode as _gqa_decode
from .sgmv import sgmv as _sgmv
from .token_logprob import token_logprob_flat


def sgmv(rows, a, b, ids, **kw):
    """Multi-LoRA delta for a batch of rows: rows[i] @ a[g] @ b[g].
    rows: [R, d]; a: [T, d, r]; b: [T, r, dout]; ids: [R]. -> [R, dout]"""
    return _sgmv(rows, a, b, ids, **kw)


def gqa_decode(q, cache_k, cache_v, pos, *, softcap=0.0, window=0, **kw):
    """Flash-decode GQA attention over a KV cache (one query token/row)."""
    return _gqa_decode(q, cache_k, cache_v, pos, softcap=softcap,
                       window=window, **kw)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def token_logprob(hidden, vocab_w, targets, softcap: float = 0.0):
    """Fused logprob+entropy. hidden: [B, S, d]; vocab_w: [d, V];
    targets: [B, S]. Returns (logprob [B, S], entropy [B, S]) fp32.

    Differentiable: the forward is the kernel, the backward is the
    chunked jnp math of ``token_logprobs_chunked`` (the
    (onehot − softmax)·Wᵀ cotangent), since a ``pallas_call`` has no
    VJP of its own."""
    B, S, d = hidden.shape
    lp, ent = token_logprob_flat(hidden.reshape(B * S, d), vocab_w,
                                 targets.reshape(B * S), softcap=softcap)
    return lp.reshape(B, S), ent.reshape(B, S)


def _token_logprob_fwd(hidden, vocab_w, targets, softcap):
    return (token_logprob(hidden, vocab_w, targets, softcap),
            (hidden, vocab_w, targets))


def _token_logprob_bwd(softcap, res, cts):
    hidden, vocab_w, targets = res
    _, vjp = jax.vjp(
        lambda h, w: token_logprobs_chunked(h, w, targets, softcap),
        hidden, vocab_w)
    d_hidden, d_vocab_w = vjp(cts)
    return d_hidden, d_vocab_w, None


token_logprob.defvjp(_token_logprob_fwd, _token_logprob_bwd)
