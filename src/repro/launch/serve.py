"""Multi-LoRA serving service CLI (the rollout engine in serve-only mode).

    PYTHONPATH=src python -m repro.launch.serve --arch granite-3-2b --tenants 4

The arch's registry config is served unchanged; --reduced swaps in its
tiny fp32 twin with the tokenizer's vocabulary.
"""
import argparse
import random

import jax

from repro.data import tokenizer as tok
from repro.envs.tasks import make_env
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.train import model_config
from repro.lora.adapters import init_lora
from repro.models import init_params
from repro.rollout.engine import RolloutEngine, RolloutRequest


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tenants", type=int, default=4)
    ap.add_argument("--per-tenant", type=int, default=2)
    ap.add_argument("--use-kernel", action="store_true",
                    help="route adapter matmuls through the Pallas SGMV "
                         "kernel (interpret mode on CPU)")
    ap.add_argument("--arch", default="granite-3-2b")
    ap.add_argument("--reduced", action="store_true",
                    help="tiny fp32 config with the tokenizer vocabulary")
    args = ap.parse_args()

    enable_compile_cache()
    cfg = model_config(args.arch, args.reduced)
    params = init_params(jax.random.PRNGKey(0), cfg)
    adapters = [init_lora(jax.random.PRNGKey(100 + t), cfg)
                for t in range(args.tenants)]
    engine = RolloutEngine(cfg, params, max_len=64, seed=0,
                           use_kernel=args.use_kernel)
    env = make_env("gsm8k")
    rng = random.Random(0)

    reqs = []
    for t in range(args.tenants):
        for _ in range(args.per_tenant):
            prompt, truth = env.sample_prompt(rng)
            reqs.append(RolloutRequest(f"tenant-{t}", t, prompt, truth, env,
                                       max_new_tokens=6, temperature=0.8))
    results, stats = engine.generate(reqs, adapters)
    print(f"served {len(reqs)} requests for {args.tenants} tenants in ONE "
          f"fused batch: {stats.decode_steps} decode steps, "
          f"{stats.wall_seconds:.2f}s wall")
    for r in results:
        txt = tok.decode_with_specials(r["tokens"])
        print(f"  {r['task_id']:10s} {txt!r}")


if __name__ == "__main__":
    main()
