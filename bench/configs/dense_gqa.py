"""Plain reference for the dense GQA decoders of this benchmark (Qwen3-0.6B,
Granite-3.0-2B) with per-tenant LoRA, their GRPO loss and Adam.

Straightforward ``jax.numpy`` in float32 at the highest matmul precision,
written from the published descriptions and importing nothing of the
program: pre-norm decoder layers (RMSNorm; causal grouped-query attention
with rotate-half RoPE, optional per-head q/k RMSNorm as Qwen3 has; SwiGLU
MLP), a final RMSNorm and tied output embeddings. LoRA adds
``(alpha / rank) * (x @ A) @ B`` to each adapted projection. Attention
scores are scaled by 1/sqrt(head_dim) and no embedding, residual or logits
multiplier is applied, as in the program's dense model: Granite's
published multipliers are a departure that its configuration file states
under ``assumed``.

Weights are the benchmark's own (made from the seed), held in the layout
the program is fed: stacked per layer, norm gains stored as ``g - 1``, the
MLP input projection as ``[gate | up]``.

``low="fp8"`` is the control: every matmul's two inputs rounded to
float8_e4m3fn in the forward pass (the step below the bfloat16 this
configuration states), products accumulated in float32, gradients passed
through the rounding unchanged.

GRPO as the program's trainer runs it (the paper's algorithm): advantages
normalised per group with the population std (+1e-4), a PPO-clip ratio to
the recomputed (proximal) logprobs, a decoupled-PPO weight
``min(exp(proximal - behaviour), is_cap)`` on stale rows, the loss summed
over completion tokens that were sampled (tool responses masked) and
divided by their count; global-norm clipping, then Adam.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

F8 = jnp.float8_e4m3fn


def shape(config: dict) -> dict:
    """The sizes the reference needs, from a configuration file's block."""
    c = config
    heads = c["num_attention_heads"]
    return {"L": c["num_hidden_layers"], "d": c["hidden_size"],
            "H": heads, "KVH": c["num_key_value_heads"],
            "hd": c.get("head_dim") or c["hidden_size"] // heads,
            "ff": c["intermediate_size"], "V": c["vocab_size"],
            "eps": c["rms_norm_eps"], "theta": float(c["rope_theta"]),
            "qk_norm": c["model_type"] == "qwen3"}


def _f8(x):
    """x rounded to float8_e4m3fn in the forward pass; the gradient passes
    through unrounded (float8 is the forward's precision here)."""
    x = x.astype(jnp.float32)
    return x + jax.lax.stop_gradient(x.astype(F8).astype(jnp.float32) - x)


def _mm(x, w, low):
    if low == "fp8":
        x, w = _f8(x), _f8(w)
    return jnp.matmul(x.astype(jnp.float32), w.astype(jnp.float32))


def _rms(x, g_minus_1, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * (1.0 + g_minus_1.astype(jnp.float32))


def _rope(x, theta):
    """x [S, heads, hd]: rotate-half RoPE at positions 0..S-1."""
    S, _, hd = x.shape
    inv = 1.0 / theta ** (np.arange(0, hd, 2, dtype=np.float32) / hd)
    ang = np.arange(S, dtype=np.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _proj(x, w, lora_l, name, scaling, low):
    y = _mm(x, w, low)
    if lora_l is not None and name in lora_l:
        a, b = lora_l[name]["a"], lora_l[name]["b"]
        y = y + scaling * _mm(_mm(x, a, low), b, low)
    return y


def _layer(x, lw, lora_l, s, scaling, low):
    """One decoder layer on one sequence x [S, d]."""
    S = x.shape[0]
    H, KVH, hd = s["H"], s["KVH"], s["hd"]
    h = _rms(x, lw["ln1"], s["eps"])
    q = _proj(h, lw["wq"], lora_l, "attn_q", scaling, low).reshape(S, H, hd)
    k = _proj(h, lw["wk"], lora_l, "attn_k", scaling, low).reshape(S, KVH, hd)
    v = _proj(h, lw["wv"], lora_l, "attn_v", scaling, low).reshape(S, KVH, hd)
    if s["qk_norm"]:
        q = _rms(q, lw["q_norm"], s["eps"])
        k = _rms(k, lw["k_norm"], s["eps"])
    q, k = _rope(q, s["theta"]), _rope(k, s["theta"])
    rep = H // KVH
    k = jnp.repeat(k, rep, axis=1)           # head h reads kv head h // rep
    v = jnp.repeat(v, rep, axis=1)
    scale = 1.0 / np.sqrt(hd)
    if low == "fp8":
        q, k, v = _f8(q), _f8(k), _f8(v)
    sc = jnp.einsum("qhd,khd->hqk", q, k) * scale
    causal = np.tril(np.ones((S, S), bool))
    p = jax.nn.softmax(jnp.where(causal[None], sc, -jnp.inf), axis=-1)
    if low == "fp8":
        p = _f8(p)
    o = jnp.einsum("hqk,khd->qhd", p, v).reshape(S, H * hd)
    x = x + _proj(o, lw["wo"], lora_l, "attn_o", scaling, low)
    h = _rms(x, lw["ln2"], s["eps"])
    gu = _proj(h, lw["w_in"], lora_l, "mlp_in", scaling, low)
    gate, up = gu[:, :s["ff"]], gu[:, s["ff"]:]
    return x + _proj(jax.nn.silu(gate) * up, lw["w_out"], lora_l, "mlp_out",
                     scaling, low)


def hidden(weights, lora, tokens, s, scaling, low=None):
    """Final normed hidden states [S, d] of one sequence ``tokens [S]``."""
    x = weights["embed"][tokens].astype(jnp.float32)
    xs = {"lw": weights["layers"]}
    if lora is not None:
        xs["lora"] = lora

    @jax.checkpoint
    def body(x, xs):
        return _layer(x, xs["lw"], xs.get("lora"), s, scaling, low), None

    x, _ = jax.lax.scan(body, x, xs)
    return _rms(x, weights["final_norm"], s["eps"])


def logits(weights, h, low=None):
    """[S, V] float32 logits (tied output embedding)."""
    return _mm(h, weights["embed"].T, low)


def token_stats(weights, lora, tokens, s, scaling, low=None):
    """Per position t of one sequence: log p(tokens[t+1]), the logit of
    tokens[t+1], the best logit, and the argmax (all [S-1])."""
    with jax.default_matmul_precision("highest"):
        lg = logits(weights, hidden(weights, lora, tokens, s, scaling, low),
                    low)[:-1]
        nxt = tokens[1:]
        tgt = jnp.take_along_axis(lg, nxt[:, None], axis=-1)[:, 0]
        lse = jax.nn.logsumexp(lg, axis=-1)
        return tgt - lse, tgt, jnp.max(lg, axis=-1), jnp.argmax(lg, axis=-1)


def _row_logprobs(weights, lora, tokens, s, scaling, low):
    h = hidden(weights, lora, tokens, s, scaling, low)[:-1]
    lg = logits(weights, h, low)
    nxt = tokens[1:]
    tgt = jnp.take_along_axis(lg, nxt[:, None], axis=-1)[:, 0]
    return tgt - jax.nn.logsumexp(lg, axis=-1)


def row_loss(lora, weights, tokens, mask, adv, behaviour, denom, s, scaling,
             trainer, low=None):
    """One row's share of the GRPO loss (summing rows gives the batch's)."""
    lp = _row_logprobs(weights, lora, tokens, s, scaling, low)
    old = jax.lax.stop_gradient(lp)
    ratio = jnp.exp(lp - old)
    obj = jnp.minimum(ratio * adv, jnp.clip(ratio, 1 - trainer["clip_eps"],
                                           1 + trainer["clip_eps"]) * adv)
    if trainer["is_cap"] > 0:
        obj = obj * jnp.minimum(jnp.exp(old - behaviour), trainer["is_cap"])
    return -jnp.sum(obj * mask) / denom


def advantages(rewards, group_size: int, eps: float = 1e-4) -> np.ndarray:
    g = np.asarray(rewards, np.float64).reshape(-1, group_size)
    return ((g - g.mean(1, keepdims=True))
            / (g.std(1, keepdims=True) + eps)).reshape(-1)


def loss_mask(batch) -> np.ndarray:
    """[R, S-1]: positions predicting a sampled completion token."""
    R, S = batch["tokens"].shape
    idx = np.arange(S - 1)[None, :]
    lo = (batch["prompt_lens"] - 1)[:, None]
    hi = (batch["total_lens"] - 1)[:, None]
    m = ((idx >= lo) & (idx < hi)).astype(np.float32)
    return m * batch["loss_mask"][:, :S - 1]


def make_row_grad(s, scaling, trainer, low=None):
    def f(lora, weights, tokens, mask, adv, behaviour, denom):
        with jax.default_matmul_precision("highest"):
            return jax.value_and_grad(row_loss)(
                lora, weights, tokens, mask, adv, behaviour, denom, s,
                scaling, trainer, low)
    return jax.jit(f)


def train_step(row_grad, weights, lora, opt, batch, group_size: int,
               trainer: dict):
    """One GRPO step: returns (new lora, new opt, loss, clipped grads)."""
    mask = loss_mask(batch)
    adv = advantages(batch["rewards"], group_size)
    denom = np.float32(max(mask.sum(), 1.0))
    loss, grads = 0.0, None
    for r in range(batch["tokens"].shape[0]):
        lr_, g = row_grad(lora, weights, jnp.asarray(batch["tokens"][r]),
                          jnp.asarray(mask[r]), jnp.float32(adv[r]),
                          jnp.asarray(batch["behavior_logprobs"][r]), denom)
        loss += float(lr_)
        grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
    new, opt, clipped = adam(lora, grads, opt, trainer)
    return new, opt, loss, clipped


def adam_init(lora):
    z = jax.tree.map(lambda t: jnp.zeros(t.shape, jnp.float32), lora)
    return {"m": z, "v": z, "t": 0}


def adam(params, grads, opt, tr):
    """Global-norm clipping, then Adam with bias correction (no decay)."""
    leaves = jax.tree.leaves(grads)
    norm = float(jnp.sqrt(sum(jnp.sum(g.astype(jnp.float32) ** 2)
                              for g in leaves)))
    scale = min(1.0, tr["grad_clip"] / (norm + 1e-9))
    g = jax.tree.map(lambda x: x * scale, grads)
    t = opt["t"] + 1
    m = jax.tree.map(lambda m, x: tr["b1"] * m + (1 - tr["b1"]) * x,
                     opt["m"], g)
    v = jax.tree.map(lambda v, x: tr["b2"] * v + (1 - tr["b2"]) * x * x,
                     opt["v"], g)
    c1, c2 = 1 - tr["b1"] ** t, 1 - tr["b2"] ** t
    new = jax.tree.map(
        lambda p, m, v: p - tr["lr"] * (m / c1) / (jnp.sqrt(v / c2)
                                                  + tr["eps"]),
        params, m, v)
    return new, {"m": m, "v": v, "t": t}, g


def weights_view(params) -> dict:
    """The reference's plain view of the weight tree it is fed."""
    lay = params["layers"]
    attn, mlp = lay["attn"], lay["mlp"]
    view = {"embed": params["embed"], "final_norm": params["final_norm"],
            "layers": {"ln1": lay["ln1"], "ln2": lay["ln2"],
                       "wq": attn[0], "wk": attn[1], "wv": attn[2],
                       "wo": attn[3], "w_in": mlp[0], "w_out": mlp[1]}}
    if attn[7] is not None:
        view["layers"]["q_norm"] = attn[7]
        view["layers"]["k_norm"] = attn[8]
    return view


def lora_view(tree: Optional[dict]) -> Optional[dict]:
    return None if tree is None else tree["layers"]
