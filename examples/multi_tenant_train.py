"""End-to-end driver (deliverable b): multi-tenant asynchronous RL training
— Algorithm 1 on real threads with real GRPO updates.

    PYTHONPATH=src python examples/multi_tenant_train.py \
        --tasks 3 --steps 5 --policy marlaas [--preset 100m]

Tenants (gsm8k / amc12 / agentic search, round-robin) share one frozen base
model; each owns LoRA adapters + optimizer state in the multi-task manager.
Rollouts are fused cross-task multi-LoRA batches; training is serialized;
environment tool calls overlap decode. Prints per-task reward curves and the
paper's system metrics (util/idle/TTFS/TPTS).

--preset tiny (default) runs in ~a minute on 1 CPU core; --preset 100m
builds a ~100M-param base (use on a real machine; a few hundred steps of
GRPO at that scale is hours on laptop CPUs, minutes on accelerators).
"""
import argparse
import dataclasses
import json

import jax

from repro.configs import REGISTRY, reduced, ModelConfig, LoRAConfig
from repro.core.chaos import ChaosConfig
from repro.core.manager import TaskSpec
from repro.core.metrics import summarize
from repro.core.runtime import MARLaaSRuntime, RuntimeConfig
from repro.data import tokenizer as tok
from repro.launch.train import AGENTIC_ENVS, MIXES
from repro.models import init_params


def base_config(preset: str) -> ModelConfig:
    if preset == "tiny":
        return dataclasses.replace(
            reduced(REGISTRY["granite-3-2b"], dtype="float32"),
            vocab_size=tok.VOCAB_SIZE)
    if preset == "100m":
        return dataclasses.replace(
            REGISTRY["granite-3-2b"], num_layers=12, d_model=768,
            num_heads=12, num_kv_heads=4, head_dim=64, d_ff=2048,
            vocab_size=tok.VOCAB_SIZE, dtype="float32", remat=False,
            lora=LoRAConfig(rank=16))
    raise ValueError(preset)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tasks", type=int, default=3)
    ap.add_argument("--steps", type=int, default=5)
    ap.add_argument("--policy", default="marlaas",
                    choices=["marlaas", "multilora_sync", "single_disagg"])
    ap.add_argument("--preset", default="tiny", choices=["tiny", "100m"])
    ap.add_argument("--timeout", type=float, default=3600.0)
    ap.add_argument("--checkpoint-dir", default=None)
    ap.add_argument("--disagg-prefill", action="store_true",
                    help="async prefill stage (Fig 5): prefills run on "
                         "worker threads, decode only splices")
    ap.add_argument("--prefill-workers", type=int, default=1)
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="chunked prefill size (0 = whole prompt)")
    ap.add_argument("--env-stage", action="store_true",
                    help="disaggregated env-interaction stage: rows park "
                         "on tool calls instead of freezing in their slot")
    ap.add_argument("--env-workers", type=int, default=2)
    ap.add_argument("--env-inflight-per-tenant", type=int, default=0,
                    help="max concurrent tool calls per tenant in the env "
                         "stage (0 = uncapped)")
    ap.add_argument("--max-turns", type=int, default=0,
                    help="per-episode tool-turn budget (0 = env default)")
    ap.add_argument("--paged-kv", action="store_true",
                    help="paged KV-cache block pool: shared fixed-size "
                         "pages + block tables instead of a dense "
                         "[slots, max_len] cache; park/preempt resume "
                         "restores saved pages instead of replaying")
    ap.add_argument("--kv-page-size", type=int, default=16,
                    help="tokens per KV page (max_len must divide)")
    ap.add_argument("--kv-pool-pages", type=int, default=0,
                    help="page-pool size (0 = dense-equivalent auto)")
    ap.add_argument("--no-resume-restore", action="store_true",
                    help="paged mode: disable snapshot/restore resume "
                         "(always token-replay — the parity baseline)")
    ap.add_argument("--snapshot-budget-bytes", type=int, default=0,
                    help="host arena for parked KV snapshots (0 = "
                         "unlimited; overflow falls back to replay)")
    ap.add_argument("--no-prefix-cache", action="store_true",
                    help="paged mode: disable the copy-on-write prefix "
                         "cache (ISSUE 8) and allocate every row's pages "
                         "privately. When enabled (the default with "
                         "--paged-kv), sharing happens at three levels: "
                         "(1) GRPO-group sharing — same-prompt group "
                         "siblings map their block tables onto one "
                         "prefilled page set and fork pages copy-on-write "
                         "on first divergent decode write; (2) device-"
                         "resident snapshots — park/preempt of a row "
                         "whose pages are in-pool retains them on device "
                         "and resume is a block-table splice (host "
                         "snapshots demoted to a spill tier); (3) radix "
                         "prefix reuse — new requests and tool-turn "
                         "resumes match their longest cached page-aligned "
                         "prefix and prefill only the suffix")
    ap.add_argument("--mix", default="classic", choices=sorted(MIXES),
                    help="tenant env rotation; 'agentic' is the multi-turn "
                         "tool-heavy mix the env stage targets")
    ap.add_argument("--async-train", action="store_true",
                    help="event-driven off-policy trainer (ROADMAP §2): "
                         "train micro-batches the moment enough complete "
                         "GRPO groups arrive instead of waiting for "
                         "full-round assembly")
    ap.add_argument("--max-staleness", type=int, default=1,
                    help="bounded staleness window in versions (async "
                         "only): rollout may run this many rounds ahead "
                         "of the last commit; 0 = on-policy, identical "
                         "to the synchronous baseline")
    ap.add_argument("--min-train-rows", type=int, default=0,
                    help="micro-batch threshold in rows, rounded up to "
                         "complete GRPO groups (0 = a full round)")
    ap.add_argument("--chaos-seed", type=int, default=0,
                    help="deterministic fault-injection seed (ISSUE 10); "
                         "each site gets an independent RNG stream, so "
                         "the same seed replays the same fault script")
    ap.add_argument("--chaos-prefill-kill", type=float, default=0.0,
                    metavar="P", help="P(kill a prefill worker per job "
                                      "pickup); the supervisor recovers "
                                      "the job and respawns with backoff")
    ap.add_argument("--chaos-env-kill", type=float, default=0.0,
                    metavar="P", help="P(kill an env-stage worker per "
                                      "tool-call pickup)")
    ap.add_argument("--chaos-tool-transient", type=float, default=0.0,
                    metavar="P", help="P(transient tool error per call); "
                                      "retried with exponential backoff")
    ap.add_argument("--chaos-tool-permanent", type=float, default=0.0,
                    metavar="P", help="P(permanent tool error per call); "
                                      "fails the episode and counts "
                                      "toward the tenant's circuit "
                                      "breaker")
    ap.add_argument("--chaos-snapshot-drop", type=float, default=0.0,
                    metavar="P", help="P(drop a parked-row KV snapshot); "
                                      "resume falls back to token replay")
    ap.add_argument("--chaos-torn-checkpoint", type=float, default=0.0,
                    metavar="P", help="P(tear a checkpoint mid-publish); "
                                      "restart must fall back to the "
                                      "previous valid snapshot")
    ap.add_argument("--chaos-max-faults", type=int, default=0,
                    metavar="N", help="cap each site at N faults total "
                                      "(0 = uncapped)")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="end-to-end episode tracing (ISSUE 9): write a "
                         "Perfetto-loadable Chrome trace JSON here (open "
                         "at ui.perfetto.dev) and print the critical-path "
                         "latency report (per-tenant p50/p95/p99 and the "
                         "dominant bottleneck stage)")
    args = ap.parse_args()

    chaos = ChaosConfig(
        seed=args.chaos_seed,
        prefill_worker_kill=args.chaos_prefill_kill,
        env_worker_kill=args.chaos_env_kill,
        tool_error_transient=args.chaos_tool_transient,
        tool_error_permanent=args.chaos_tool_permanent,
        snapshot_drop=args.chaos_snapshot_drop,
        torn_checkpoint=args.chaos_torn_checkpoint,
        max_faults_per_site=args.chaos_max_faults)

    cfg = base_config(args.preset)
    params = init_params(jax.random.PRNGKey(0), cfg)
    n_par = sum(x.size for x in jax.tree.leaves(params))
    print(f"base model: {cfg.name}-{args.preset} ({n_par/1e6:.1f}M params), "
          f"policy={args.policy}")

    rt = MARLaaSRuntime(cfg, params, RuntimeConfig(
        policy=args.policy, max_len=64, seed=0,
        checkpoint_dir=args.checkpoint_dir,
        checkpoint_every=5 if args.checkpoint_dir else 0,
        disagg_prefill=args.disagg_prefill,
        prefill_workers=args.prefill_workers,
        prefill_chunk=args.prefill_chunk,
        env_stage=args.env_stage,
        env_workers=args.env_workers,
        env_inflight_per_tenant=args.env_inflight_per_tenant,
        max_turns=args.max_turns,
        paged_kv=args.paged_kv,
        kv_page_size=args.kv_page_size,
        kv_pool_pages=args.kv_pool_pages,
        resume_restore=not args.no_resume_restore,
        snapshot_budget_bytes=args.snapshot_budget_bytes,
        prefix_cache=not args.no_prefix_cache,
        async_train=args.async_train,
        max_staleness=args.max_staleness,
        min_train_rows=args.min_train_rows,
        chaos=chaos if chaos.enabled else None,
        trace=bool(args.trace_out)))
    envs = MIXES[args.mix]
    for i in range(args.tasks):
        env = envs[i % len(envs)]
        rt.submit_task(TaskSpec(f"{env}-{i}", env, group_size=4, num_groups=1,
                                max_new_tokens=12 if env in AGENTIC_ENVS
                                else 6,
                                target_steps=args.steps, lr=3e-3))
    rt.run(timeout_s=args.timeout)

    print("\nper-task reward curves (graded verifier reward ∈ [0,1]):")
    for tid, st in rt.mgr.tasks.items():
        curve = " ".join(f"{r:.2f}" for r in st.reward_history)
        print(f"  {tid:12s} v{st.version}: {curve}")
    print("\nsystem metrics:")
    print(json.dumps({k: round(v, 3) for k, v in
                      summarize(rt.mgr, rt.rec).items()}, indent=2))
    if rt.chaos is not None:
        c = rt.rec.counters_snapshot()
        fault = {k: v for k, v in sorted(c.items())
                 if k.startswith(("chaos_", "supervisor_", "quarantine_"))
                 or k in ("env_retries", "env_recovered", "env_wedged")}
        acc = rt.row_accounting()
        print(f"\nchaos: injected={dict(rt.chaos.counts())}")
        print(f"fault handling: {json.dumps(fault)}")
        print(f"row accounting: {json.dumps(acc)}")
    if args.paged_kv:
        st = rt.cengine.stats
        print(f"\npaged KV: restores={st.restores} replays={st.replays} "
              f"replay_tokens={st.replay_tokens} "
              f"replay_tokens_saved={st.replay_tokens_saved} "
              f"snapshot_drops={st.snapshot_drops} "
              f"pool_exhausted={st.pool_exhausted} "
              f"prefix_hits={st.prefix_hits} "
              f"prefix_hit_tokens={st.prefix_hit_tokens} "
              f"cow_forks={st.cow_forks} "
              f"device_resident_resumes={st.device_resident_resumes} "
              f"fused_forced_tokens={st.fused_forced_tokens} "
              f"pool={rt.cengine.page_stats()}")
    if args.trace_out:
        from repro.obs.report import analyze, format_report, load_episodes
        trace = rt.tracer.dump_json(args.trace_out)
        print(f"\ntrace written to {args.trace_out} "
              f"(open at ui.perfetto.dev; "
              f"{rt.tracer.dropped_events} events dropped)")
        print(format_report(analyze(load_episodes(trace))))


if __name__ == "__main__":
    main()
