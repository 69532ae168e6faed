"""Multi-tenant training service CLI.

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-0.6b \
        --tasks 4 --steps 5 --mix agentic --policy marlaas \
        --disagg-prefill --env-stage --paged-kv --async-train --use-kernel \
        [--checkpoint-dir DIR] [--resume]

Without --reduced the arch's registry config is built unchanged: published
widths and the full vocabulary (the toy tokenizer's ids are a subset of
it). --reduced swaps in the family-faithful tiny config with the
tokenizer's vocabulary, small enough for a CPU. The process exits 1 when
the run ends with any tenant short of --steps.

``build_runtime`` is the one place a runtime is assembled from a config;
``chip_smoke.py`` drives the same function.
"""
import argparse
import dataclasses
import sys
from typing import Dict, Optional

import jax

from repro.checkpoint.store import latest_checkpoint
from repro.configs import ModelConfig, get_config, reduced
from repro.core.manager import TaskSpec
from repro.core.metrics import summarize
from repro.core.runtime import MARLaaSRuntime, RuntimeConfig
from repro.data import tokenizer as tok
from repro.launch.compile_cache import enable_compile_cache
from repro.models import init_params

# tenant env rotations: classic = the paper's three archetypes; agentic =
# multi-turn tool-heavy tenants mixed with plain math (the env-stage
# workload — pair with --env-stage)
MIXES = {
    "classic": ["gsm8k", "amc12", "search"],
    "agentic": ["gsm8k", "hopsearch", "calcrepl", "guess"],
}
AGENTIC_ENVS = {"search", "hopsearch", "calcrepl", "guess"}
STAGES = ("disagg_prefill", "env_stage", "paged_kv", "async_train")


def model_config(arch: str, reduce: bool = False) -> ModelConfig:
    """The registry config unchanged, or (``reduce``) its tiny fp32 twin
    with the tokenizer's vocabulary."""
    cfg = get_config(arch)
    if reduce:
        cfg = dataclasses.replace(reduced(cfg, dtype="float32"),
                                  vocab_size=tok.VOCAB_SIZE)
    return cfg


def build_runtime(cfg: ModelConfig, rcfg: RuntimeConfig, *, tasks: int,
                  steps: int, mix: str = "classic", params=None,
                  resume: Optional[str] = None
                  ) -> MARLaaSRuntime:
    """Seeded base params (unless given), the runtime, and its tenants:
    ``tasks`` tenants round-robin over ``mix``, one GRPO group of 4 per
    round, ``steps`` commits each — or, with
    ``resume``, the tenants of that checkpoint."""
    if params is None:
        params = init_params(jax.random.PRNGKey(rcfg.seed), cfg)
    rt = MARLaaSRuntime(cfg, params, rcfg)
    if resume:
        rt.adopt_checkpoint(resume)
        return rt
    envs = MIXES[mix]
    for i in range(tasks):
        env = envs[i % len(envs)]
        rt.submit_task(TaskSpec(
            f"{env}-{i}", env, group_size=4, num_groups=1,
            max_new_tokens=12 if env in AGENTIC_ENVS else 6,
            target_steps=steps))
    return rt


def unfinished(rt: MARLaaSRuntime) -> Dict[str, int]:
    """Tenants short of their target steps -> steps they committed."""
    return {tid: st.steps_done for tid, st in rt.mgr.task_items()
            if st.steps_done < st.spec.target_steps}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-3-2b")
    ap.add_argument("--reduced", action="store_true",
                    help="tiny fp32 config with the tokenizer vocabulary")
    ap.add_argument("--tasks", type=int, default=2)
    ap.add_argument("--steps", type=int, default=3)
    ap.add_argument("--mix", default="classic", choices=sorted(MIXES))
    ap.add_argument("--policy", default="marlaas")
    for stage in STAGES:
        ap.add_argument("--" + stage.replace("_", "-"), action="store_true")
    ap.add_argument("--use-kernel", action="store_true",
                    help="Pallas kernels (interpreted on a CPU)")
    ap.add_argument("--checkpoint-dir", default=None)
    ap.add_argument("--checkpoint-every", type=int, default=5)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--timeout", type=float, default=1800.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfg = model_config(args.arch, args.reduced)
    rcfg = RuntimeConfig(
        policy=args.policy, max_len=64, seed=args.seed,
        use_kernel=args.use_kernel, checkpoint_dir=args.checkpoint_dir,
        checkpoint_every=(args.checkpoint_every
                          if args.checkpoint_dir else 0),
        **{stage: getattr(args, stage) for stage in STAGES})
    snap = (latest_checkpoint(args.checkpoint_dir)
            if args.resume and args.checkpoint_dir else None)
    if snap:
        print(f"resuming from {snap}")
    rt = build_runtime(cfg, rcfg,
                       tasks=args.tasks,  # noqa: RA102 — argparse attr
                       steps=args.steps, mix=args.mix, resume=snap)
    rt.run(timeout_s=args.timeout)
    print("tasks:", {t: f"v{s.version} r={s.reward_history[-1:]}"
                     for t, s in rt.mgr.task_items()})
    print("metrics:", {k: round(v, 3)
                       for k, v in summarize(rt.mgr, rt.rec).items()})
    short = unfinished(rt)
    if short:
        print(f"tenants short of target steps: {short}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
