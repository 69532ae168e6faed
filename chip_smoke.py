#!/usr/bin/env python3
"""Chip smoke test: the MARLaaS pipeline on one TPU at Qwen3-0.6B's full
published widths (28 layers, d_model 1024, 16/8 heads of 128, d_ff 3072,
vocabulary 151936, bf16 base, fp32 LoRA), random weights from --seed.

    python chip_smoke.py [--seed 0]

Four phases, run in one process; any failure exits nonzero without the
result line:

  1. device   — jax.devices()[0].platform must be "tpu" (no CPU fallback).
  2. kernels  — paged_gqa_decode, gqa_decode, sgmv and token_logprob,
                compiled (not interpreted), against the kernels/ref.py
                oracles at Qwen3-0.6B widths: decode batch 8, page 16,
                rank-16 LoRA, 4x96 logprob rows over the full vocabulary.
  3. model    — prefill a few prompts, then 16 decode steps through the
                paged KV cache with multi-LoRA adapters, once with the
                kernels and once with the jnp path; the logits must agree
                with each other and with a float32 reference decode.
  4. runtime  — MARLaaSRuntime from launch.train.build_runtime with every
                disaggregated stage and the kernels on: agentic tenants,
                GRPO group 4, two commits each. Checks target steps, exact
                row conservation, zero supervisor restarts, finite losses.

The last line of stdout is one JSON object:
{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}.
Times printed on the way are host-clock seconds, compilation included.
"""
import argparse
import contextlib
import json
import math
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "qwen3-0.6b"
# Kernel-vs-oracle tolerances, |out - ref| <= ATOL + RTOL * |ref|. The
# decode kernels and sgmv take bf16 inputs and the decode kernels emit
# bf16: two bf16 ulps (2^-7) plus rounding headroom. token_logprob
# accumulates bf16 products exactly in f32 and emits f32.
KERNEL_TOL = {"paged_gqa_decode": 2e-2, "gqa_decode": 2e-2, "sgmv": 2e-2,
              "token_logprob": 1e-3}
# Phase 3: the jnp decode path rounds attention scores and probabilities
# to bf16 (einsums in the cache dtype) where the kernels keep them in f32,
# and both run a bf16 model against the float32 reference; over 28 layers
# that leaves the logits within a few percent of their scale, so the bound
# is relative to max|logits|.
LOGIT_REL_TOL = 5e-2
CALL_AT = 2        # sampled-token counter at which the forced sampler
                   # emits CALL (phase 4)
# phase 2 shapes: decode batch, KV page, cache length, LoRA rank and
# tenants, and GRPO rows x sequence for the logprob kernel
BATCH, PAGE, MAX_LEN, RANK, TENANTS, LOGPROB_ROWS = 8, 16, 96, 16, 4, 4 * 96


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str):
    if not ok:
        raise SmokeFailure(what)


def log(msg: str):
    print(msg, flush=True)


# -- phase 1 -----------------------------------------------------------------

def phase_device() -> dict:
    import jax
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    log(f"[1 device] platform={dev['platform']} kind={dev['kind']} "
        f"count={dev['count']}")
    check(dev["platform"] == "tpu",
          f"no TPU: JAX runs on {dev['platform']!r}")
    return dev


# -- phase 2 -----------------------------------------------------------------

def _within(name: str, out, want) -> float:
    import jax.numpy as jnp
    out = jnp.asarray(out, jnp.float32)
    want = jnp.asarray(want, jnp.float32)
    tol = KERNEL_TOL[name]
    err = float(jnp.max(jnp.abs(out - want)))
    ok = bool(jnp.all(jnp.abs(out - want) <= tol + tol * jnp.abs(want)))
    log(f"[2 kernels] {name}: max abs err {err!r} "
        f"(tolerance {tol} + {tol}*|ref|) {'ok' if ok else 'FAIL'}")
    check(ok and math.isfinite(err), f"{name} disagrees with its oracle")
    return err


def phase_kernels(cfg, seed: int) -> dict:
    """Each kernel, compiled, against its oracle at `cfg`'s widths (the
    oracles run at highest matmul precision)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import ref
    from repro.kernels.gqa_decode import gqa_decode
    from repro.kernels.ops import token_logprob
    from repro.kernels.paged_decode import paged_gqa_decode
    from repro.kernels.sgmv import sgmv

    H, KVH, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    d, V, bf = cfg.d_model, cfg.vocab_size, jnp.bfloat16
    ks = iter(jax.random.split(jax.random.PRNGKey(seed), 16))
    n_pg = MAX_LEN // PAGE
    pool = BATCH * n_pg
    q = jax.random.normal(next(ks), (BATCH, H, hd), bf)
    kp = jax.random.normal(next(ks), (pool + 1, PAGE, KVH, hd), bf)
    vp = jax.random.normal(next(ks), (pool + 1, PAGE, KVH, hd), bf)
    tbl = jax.random.permutation(next(ks), pool).reshape(BATCH, n_pg)
    pos = jax.random.randint(next(ks), (BATCH,), 1, MAX_LEN + 1)
    ck = jax.random.normal(next(ks), (BATCH, MAX_LEN, KVH, hd), bf)
    cv = jax.random.normal(next(ks), (BATCH, MAX_LEN, KVH, hd), bf)
    rows = jax.random.normal(next(ks), (BATCH, d), bf)
    a = jax.random.normal(next(ks), (TENANTS, d, RANK)) / np.sqrt(d)
    b = jax.random.normal(next(ks), (TENANTS, RANK, cfg.q_dim)) * 0.02
    ids = jax.random.randint(next(ks), (BATCH,), 0, TENANTS)
    h = jax.random.normal(next(ks), (LOGPROB_ROWS, d), bf)
    w = (jax.random.normal(next(ks), (d, V)) * 0.02).astype(bf)
    tgt = jax.random.randint(next(ks), (LOGPROB_ROWS,), 0, V)

    got = {
        "paged_gqa_decode": paged_gqa_decode(q, kp, vp, tbl, pos,
                                             interpret=False),
        "gqa_decode": gqa_decode(q, ck, cv, pos, interpret=False),
        "sgmv": sgmv(rows, a, b, ids, interpret=False),
        "token_logprob": jnp.stack(token_logprob(h[None], w, tgt[None])),
    }
    with jax.default_matmul_precision("highest"):
        want = {
            "paged_gqa_decode": ref.paged_gqa_decode_ref(q, kp, vp, tbl, pos),
            "gqa_decode": ref.gqa_decode_ref(q, ck, cv, pos),
            "sgmv": ref.sgmv_ref(rows, a, b, ids),
            "token_logprob": jnp.stack(
                ref.token_logprob_ref(h[None], w, tgt[None])),
        }
    return {name: _within(name, got[name], want[name]) for name in got}


# -- phase 3 -----------------------------------------------------------------

def phase_model(cfg, params, seed: int, *, steps: int = 16) -> dict:
    """Prefill 4 rows of 24 tokens into a dense cache, lay it out as pages,
    then decode `steps` fixed tokens through the paged cache with multi-LoRA
    adapters: with the kernels, with the jnp path, and with the jnp path on
    the same weights in float32 at highest matmul precision (the
    reference). Returns the max logit differences between the three."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    from repro.lora.adapters import batched_ctx, init_lora, stack_adapters
    from repro.models.model import decode_step, forward_seq, init_cache

    B, prompt_len, tenants = 4, 24, 2
    ks = jax.random.split(jax.random.PRNGKey(seed + 1), 2 + tenants)
    V = cfg.vocab_size
    n_pg = -(-(prompt_len + steps) // PAGE)
    tokens = jax.random.randint(ks[0], (B, prompt_len), 0, V)
    stream = jax.random.randint(ks[1], (steps, B), 0, V)
    # trained-looking adapters: init_lora's b is zero, so perturb it
    adapters = stack_adapters([
        jax.tree.map(lambda x, k=k: x + 0.01 * jax.random.normal(
            k, x.shape, x.dtype), init_lora(k, cfg)) for k in ks[2:]])
    row_ids = jnp.arange(B, dtype=jnp.int32) % tenants

    def decode(cfg, params, use_kernel: bool):
        @jax.jit
        def prefill(params):
            cache = init_cache(cfg, B, n_pg * PAGE)
            _, cache, _ = forward_seq(params, tokens, cfg, None, cache)
            L = cache["k"].shape[0]

            def pages(x):        # row b owns pages b*n_pg .. b*n_pg+n_pg-1
                x = x.reshape((L, B * n_pg, PAGE) + x.shape[3:])
                return jnp.concatenate([x, jnp.zeros_like(x[:, :1])], axis=1)

            return {"pos": jnp.full((B,), prompt_len, jnp.int32),
                    "tbl": jnp.arange(B * n_pg,
                                      dtype=jnp.int32).reshape(B, n_pg),
                    "kp": pages(cache["k"]), "vp": pages(cache["v"])}

        step = jax.jit(lambda p, t, c: decode_step(
            p, t, c, cfg, batched_ctx(adapters, row_ids, cfg, use_kernel),
            use_kernel=use_kernel))
        cache, out = prefill(params), []
        for t in range(steps):
            lg, cache = step(params, stream[t], cache)
            out.append(lg)
        return jnp.stack(out)

    logits = {"kernel": decode(cfg, params, True),
              "jnp": decode(cfg, params, False)}
    with jax.default_matmul_precision("highest"):
        logits["f32"] = decode(
            dataclasses.replace(cfg, dtype="float32"),
            jax.tree.map(lambda x: x.astype(jnp.float32), params), False)
    diff = {f"{x}_vs_{y}": float(jnp.max(jnp.abs(logits[x] - logits[y])))
            for x, y in (("kernel", "jnp"), ("kernel", "f32"),
                         ("jnp", "f32"))}
    scale = float(jnp.max(jnp.abs(logits["f32"])))
    log(f"[3 model] {B} prompts x {prompt_len} tokens prefilled, {steps} "
        f"paged decode steps: max |logits| differences {diff} (max |logits| "
        f"{scale!r}, bound {LOGIT_REL_TOL} x max |logits| on kernel_vs_jnp "
        f"and kernel_vs_f32)")
    for name in ("kernel_vs_jnp", "kernel_vs_f32"):
        check(math.isfinite(diff[name])
              and diff[name] <= LOGIT_REL_TOL * scale,
              f"decode logits disagree: {name} = {diff[name]!r}")
    return diff


# -- phase 4 -----------------------------------------------------------------

@contextlib.contextmanager
def forced_calls(call_at: int = CALL_AT):
    """Deterministic forced-CALL sampler (the benchmarks/bench_async_train
    idiom): every row samples CALL at token counter `call_at` and EOS is
    remapped away, so park -> env -> resume runs although a random model
    almost never samples CALL. Restores the real sampler on exit."""
    import jax.numpy as jnp
    import repro.rollout.engine as eng_mod
    import repro.rollout.prefill as pf_mod
    from repro.data import tokenizer as tok
    orig = pf_mod._sample_rows

    def biased(logits, keys, counters, temps):
        s = orig(logits, keys, counters, temps)
        s = jnp.where(s == tok.EOS, 10, s)
        return jnp.where(counters == call_at, tok.CALL, s)

    pf_mod._sample_rows = eng_mod._sample_rows = biased
    try:
        yield
    finally:
        pf_mod._sample_rows = eng_mod._sample_rows = orig


def phase_runtime(cfg, params, seed: int, *, tasks: int = 3, steps: int = 2,
                  timeout_s: float = 900.0) -> dict:
    """The main path through the shared builder; returns its counts."""
    from repro.core.runtime import RuntimeConfig
    from repro.launch.train import build_runtime, unfinished

    log("[4 runtime] forced-CALL sampler on: every row samples CALL at "
        f"token {CALL_AT} (a random model almost never does)")
    with forced_calls():
        rcfg = RuntimeConfig(
            policy="marlaas", rollout_mode="continuous", max_len=64,
            seed=seed, disagg_prefill=True, env_stage=True, paged_kv=True,
            prefix_cache=True, async_train=True, use_kernel=True)
        rt = build_runtime(cfg, rcfg, tasks=tasks, steps=steps,
                           mix="agentic", params=params)
        t0 = time.monotonic()
        rt.run(timeout_s=timeout_s)
        wall = time.monotonic() - t0
    st = rt.cengine.stats
    acc = rt.row_accounting()
    counters = rt.rec.counters_snapshot()
    restarts = {k: v for k, v in counters.items()
                if k.startswith("supervisor_") and v}
    losses = {tid: s.loss_history for tid, s in rt.mgr.task_items()}
    commits = {tid: s.steps_done for tid, s in rt.mgr.task_items()}
    out = {"commits": commits, "tokens_generated": st.tokens_generated,
           "decode_steps": st.decode_steps, "parks": st.parks,
           "resumes": st.resumes, "device_resident_resumes":
           st.device_resident_resumes, "row_accounting": acc,
           "losses": losses}
    log(f"[4 runtime] commits {commits}; tokens decoded "
        f"{st.tokens_generated} in {st.decode_steps} decode steps; parks "
        f"{st.parks}, resumes {st.resumes} (device-resident "
        f"{st.device_resident_resumes}); losses {losses}")
    log(f"[4 runtime] row accounting {acc}; supervisor restarts "
        f"{restarts or 0}; host-clock wall {wall:.1f} s incl. compiles")
    check(not unfinished(rt), f"tenants short of target: {unfinished(rt)}")
    check(acc["completed"] == sum(v for k, v in acc.items()
                                  if k != "completed"),
          f"row accounting not conserved: {acc}")
    check(not restarts, f"supervisor restarts: {restarts}")
    check(all(len(v) == steps and all(map(math.isfinite, v))
              for v in losses.values()), f"bad losses: {losses}")
    check(st.parks > 0 and st.resumes > 0, "no park -> env -> resume ran")
    return out


# -- driver ------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    t0 = time.monotonic()
    try:
        dev = phase_device()
        import jax
        from repro.launch.compile_cache import enable_compile_cache
        from repro.launch.train import model_config
        from repro.models import init_params
        log(f"compile cache: {enable_compile_cache()}")
        cfg = model_config(ARCH)
        log(f"config {cfg.name}: {cfg.num_layers} layers, d_model "
            f"{cfg.d_model}, heads {cfg.num_heads}/{cfg.num_kv_heads}x"
            f"{cfg.head_dim}, d_ff {cfg.d_ff}, vocab {cfg.vocab_size}, "
            f"{cfg.dtype} base, {cfg.lora.dtype} LoRA rank {cfg.lora.rank}")
        phase_kernels(cfg, args.seed)
        params = init_params(jax.random.PRNGKey(args.seed), cfg)
        phase_model(cfg, params, args.seed)
        phase_runtime(cfg, params, args.seed)
    except SmokeFailure as e:
        log(f"FAILED: {e}")
        return 1
    log(f"all phases passed; host-clock total {time.monotonic() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
