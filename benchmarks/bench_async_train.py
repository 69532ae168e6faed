"""Event-driven off-policy trainer vs round-synchronous baseline (ISSUE 7
tentpole gate).

Workload: 16 tenants through one threaded MARLaaS runtime — 8 plain gsm8k
tenants plus 8 agentic search tenants whose forced tool call costs
ENV_LATENCY seconds in the disaggregated env stage (the row parks, its
decode slot is recycled). The regime is LATENCY-BOUND by construction:
the model is tiny and budgets are short, so an arm's time-to-final-commit
is dominated by how well it hides the per-round tool-latency chain.

Two arms over the IDENTICAL tenant set (same seeds, same deterministic
forced-CALL pattern):

  sync   — baseline: strict on-policy round loop. A tenant's round N+1
           cannot start until round N commits, so each agentic tenant
           serializes TARGET_STEPS park latencies end to end.
  async  — this PR: bounded staleness (max_staleness versions ahead) with
           per-tenant completed-episode queues. Rollout pipelines the
           whole issue window at once, so successive rounds' parks
           overlap and each tenant pays the latency roughly once.

Metrics: time-to-final-commit (wall seconds from run start to the LAST
commit of any tenant) and the trainer idle-with-work fraction (seconds
the trainer sat waiting while a dispatchable micro-batch existed, over
its first-to-last-train span — sub-threshold partial assemblies are not
dispatchable work). Gates:

    ttfc(sync) / ttfc(async)   >= 1.2x
    trainer_idle_frac(async)   <= 0.1

A third arm re-runs async with end-to-end episode tracing ON (ISSUE 9):
it must reproduce each episode's submission→commit latency as the sum of
its per-stage components (±1%), name a bottleneck stage for every one of
the 16 tenants, and stay within the tracing-overhead gate

    ttfc(traced) / ttfc(async) <= 1.03

(the workload is deterministic — both arms generate identical tokens, so
the ttfc ratio IS the tokens/sec ratio). The traced arm's Perfetto trace
lands in BENCH_async_train_trace.json (CI artifact; open at
ui.perfetto.dev — park→env→resume flow arrows link the stage tracks).

Measured arms run against a persistent JAX compilation cache populated by
a full-size warm pass of each arm: the engine jits per-instance closures,
so without the on-disk cache every fresh runtime would re-XLA-compile all
~90 refill/decode/train shape buckets and the bench would time the
compiler, not the scheduler.

  PYTHONPATH=src python -m benchmarks.bench_async_train [--json out.json]
"""
from __future__ import annotations

import dataclasses
import json
import sys
import time

import jax
import jax.numpy as jnp

from repro.configs import REGISTRY, reduced
from repro.core.manager import TaskSpec
from repro.core.runtime import MARLaaSRuntime, RuntimeConfig
from repro.data import tokenizer as tok
from repro.launch.compile_cache import enable_compile_cache
from repro.models import init_params
import repro.rollout.engine as eng_mod
import repro.rollout.prefill as pf_mod

PLAIN_TENANTS = 8
AGENTIC_TENANTS = 8
N_TENANTS = PLAIN_TENANTS + AGENTIC_TENANTS
DECODE_SLOTS = 16
MAX_LEN = 32
GROUP_SIZE = 2
NUM_GROUPS = 1
TARGET_STEPS = 3
PLAIN_BUDGET, AGENTIC_BUDGET = 4, 6
ENV_LATENCY = 1.5             # per forced tool call (deterministic: std 0)
CALL_AT = 2                   # sampled-token counter that emits CALL
MAX_STALENESS = 2
ENV_WORKERS = 32              # >= concurrent parks: workers never queue
GATE_SPEEDUP = 1.2
GATE_IDLE_FRAC = 0.1
GATE_TRACE_OVERHEAD = 1.03    # ttfc(traced) / ttfc(async) ceiling
GATE_TRACE_RESIDUAL = 0.01    # max |Σcomponents - e2e| / e2e per episode
TRACE_ARTIFACT = "BENCH_async_train_trace.json"

_STATE = {}


def _bias_sampler():
    """Deterministic forced-CALL pattern: every row samples CALL at token
    counter CALL_AT (a no-op for the non-agentic gsm8k tenants) and EOS is
    remapped away so row lengths are exactly their budgets. Applied once,
    identically to both arms."""
    if _STATE.get("biased"):
        return
    orig = pf_mod._sample_rows

    def biased(logits, keys, counters, temps):
        s = orig(logits, keys, counters, temps)
        s = jnp.where(s == tok.EOS, 10, s)
        return jnp.where(counters == CALL_AT, tok.CALL, s)

    pf_mod._sample_rows = biased
    eng_mod._sample_rows = biased
    _STATE["biased"] = True


def _model():
    if "cfg" not in _STATE:
        cfg = dataclasses.replace(reduced(REGISTRY["granite-3-2b"],
                                          dtype="float32"),
                                  vocab_size=tok.VOCAB_SIZE)
        _STATE["cfg"] = cfg
        _STATE["params"] = init_params(jax.random.PRNGKey(0), cfg)
    return _STATE["cfg"], _STATE["params"]


def _runtime(async_train: bool, trace: bool = False):
    """One arm's runtime over the mixed 16-tenant workload. Both arms build
    from the same base params and the same per-tenant seeds."""
    enable_compile_cache()
    _bias_sampler()
    cfg, params = _model()
    rt = MARLaaSRuntime(cfg, params, RuntimeConfig(
        policy="marlaas", max_len=MAX_LEN, max_slots=DECODE_SLOTS,
        max_adapter_slots=N_TENANTS, seed=0,
        env_stage=True, env_workers=ENV_WORKERS,
        async_train=async_train, max_staleness=MAX_STALENESS,
        min_train_rows=0, trace=trace))
    for i in range(N_TENANTS):
        agentic = i >= N_TENANTS // 2
        env = "search" if agentic else "gsm8k"
        rt.submit_task(TaskSpec(
            f"{env}-{i}", env, group_size=GROUP_SIZE, num_groups=NUM_GROUPS,
            max_new_tokens=AGENTIC_BUDGET if agentic else PLAIN_BUDGET,
            target_steps=TARGET_STEPS))
        if agentic:
            rt.envs[f"{env}-{i}"].env_latency_mean = ENV_LATENCY
            rt.envs[f"{env}-{i}"].env_latency_std = 0.0
    return rt


def _run_once(async_train: bool, trace: bool = False) -> dict:
    rt = _runtime(async_train, trace=trace)
    t0 = time.monotonic()
    rt.run(timeout_s=600.0)
    assert rt.mgr.all_done(), "arm did not complete"
    last_commit = max(st.last_step_at for _, st in rt.mgr.task_items())
    idle = rt.rec.trainer_idle_stats()
    d = rt.mgr.drop_counters()
    out = {
        "time_to_final_commit_s": last_commit - t0,
        "wall_s": time.monotonic() - t0,
        "total_steps": rt.mgr.total_steps_done(),
        "rows_trained": rt._rows_trained,
        "rows_completed": rt._rows_completed,
        "trainer_idle_with_work_s": idle["trainer_idle_with_work_s"],
        "trainer_idle_frac": idle["trainer_idle_frac"],
        "trainer_span_s": idle["trainer_span_s"],
        **d,
    }
    if trace:
        out["trace_doc"] = rt.tracer.export_chrome()
        out["trace_dropped_events"] = rt.tracer.dropped_events
    return out


def run_arm(async_train: bool, reps: int = 2, trace: bool = False) -> dict:
    """Best-of-`reps` measured runs (min time-to-final-commit): refill
    shape buckets are timing-dependent, so even after the warm pass a
    measured run can stumble into one novel bucket and pay its compile —
    the repeated run takes the cached path. Drop counters and row totals
    must agree across reps (the workload is deterministic)."""
    runs = [_run_once(async_train, trace=trace) for _ in range(reps)]
    best = min(runs, key=lambda r: r["time_to_final_commit_s"])
    best["ttfc_runs"] = [round(r["time_to_final_commit_s"], 3)
                         for r in runs]
    return best


def _validate_trace(doc: dict) -> dict:
    """Critical-path acceptance on the traced arm's export: every
    committed episode's per-stage components sum to its E2E latency
    (within GATE_TRACE_RESIDUAL), every tenant gets a named bottleneck,
    and the park→env→resume hand-offs appear as s/f flow-event pairs."""
    from repro.obs.report import analyze, load_episodes
    res = analyze(load_episodes(doc))
    tenants = res["tenants"]
    flows = [e for e in doc["traceEvents"] if e["ph"] in ("s", "f")]
    kinds = {e["name"] for e in flows}
    ok = (res["episodes"] > 0
          and res["max_relative_residual"] <= GATE_TRACE_RESIDUAL
          and len(tenants) == N_TENANTS
          and all(v["bottleneck"] for v in tenants.values())
          and {"park", "resume"} <= kinds)
    return {
        "trace_episodes": res["episodes"],
        "trace_max_residual": res["max_relative_residual"],
        "trace_tenants": len(tenants),
        "trace_flow_events": len(flows),
        "trace_bottlenecks": {t: v["bottleneck"]
                              for t, v in sorted(tenants.items())},
        "trace_valid": bool(ok),
    }


def bench():
    # warm pass: a FULL-SIZE run of each arm AT THE REAL tool latency
    # compiles every jit shape bucket the measured arms will hit — refill
    # width x length buckets are timing-dependent (they depend on how many
    # rows return from the env stage between refills), so a smaller or
    # faster warm run would miss buckets and the measured arms would time
    # XLA, not scheduling. The compiled executables land in the
    # persistent cache where the measured runtimes' fresh jit closures
    # find them.
    for mode in (False, True):
        _runtime(mode).run(timeout_s=600.0)
    out = {"config": {
        "plain_tenants": PLAIN_TENANTS, "agentic_tenants": AGENTIC_TENANTS,
        "decode_slots": DECODE_SLOTS, "group_size": GROUP_SIZE,
        "num_groups": NUM_GROUPS, "target_steps": TARGET_STEPS,
        "budgets": [PLAIN_BUDGET, AGENTIC_BUDGET],
        "env_latency_s": ENV_LATENCY, "max_staleness": MAX_STALENESS}}
    out["async"] = run_arm(True)
    out["sync"] = run_arm(False)
    # tracing-overhead arm: async again with the tracer on — same tokens,
    # same schedule pressure, plus the trace acceptance checks
    traced = run_arm(True, trace=True)
    doc = traced.pop("trace_doc")
    with open(TRACE_ARTIFACT, "w") as f:
        json.dump(doc, f)
    print(f"wrote {TRACE_ARTIFACT}")
    traced.update(_validate_trace(doc))
    out["traced"] = traced
    speedup = (out["sync"]["time_to_final_commit_s"]
               / out["async"]["time_to_final_commit_s"])
    overhead = (traced["time_to_final_commit_s"]
                / out["async"]["time_to_final_commit_s"])
    out["ttfc_speedup"] = float(speedup)
    out["trace_overhead"] = float(overhead)
    out["gate_speedup"] = GATE_SPEEDUP
    out["gate_idle_frac"] = GATE_IDLE_FRAC
    out["gate_trace_overhead"] = GATE_TRACE_OVERHEAD
    ok = (speedup >= GATE_SPEEDUP
          and out["async"]["trainer_idle_frac"] <= GATE_IDLE_FRAC
          and overhead <= GATE_TRACE_OVERHEAD
          and traced["trace_valid"])
    # all arms must do the same amount of committed training
    if any(out[arm]["total_steps"] != out["async"]["total_steps"]
           or out[arm]["rows_trained"] != out["async"]["rows_trained"]
           for arm in ("sync", "traced")):
        ok = False
    out["pass"] = bool(ok)
    print(f"bench_async_train,tenants={N_TENANTS},slots={DECODE_SLOTS},"
          f"steps={TARGET_STEPS},staleness={MAX_STALENESS},"
          f"sync_ttfc={out['sync']['time_to_final_commit_s']:.2f}s,"
          f"async_ttfc={out['async']['time_to_final_commit_s']:.2f}s,"
          f"speedup={speedup:.2f}x,"
          f"async_idle_frac={out['async']['trainer_idle_frac']:.3f},"
          f"sync_idle_frac={out['sync']['trainer_idle_frac']:.3f},"
          f"stale_dropped={out['async']['stale_rows_dropped']},"
          f"trace_overhead={overhead:.3f},"
          f"trace_residual={traced['trace_max_residual']:.4f},"
          f"trace_eps={traced['trace_episodes']},"
          f"{'ok' if out['pass'] else 'FAIL'}")
    return out


def main(argv):
    json_path = None
    if "--json" in argv:
        i = argv.index("--json")
        if i + 1 >= len(argv):
            print("usage: bench_async_train [--json OUT.json]")
            return 2
        json_path = argv[i + 1]
    out = bench()
    if json_path:
        with open(json_path, "w") as f:
            json.dump(out, f, indent=2)
        print(f"wrote {json_path}")
    from benchmarks.common import bench_record, write_bench_json
    rec = bench_record(
        "async_train", GATE_SPEEDUP,
        out["async"]["time_to_final_commit_s"],
        out["sync"]["time_to_final_commit_s"],
        higher_is_better=False,
        extra={"trainer_idle_frac": out["async"]["trainer_idle_frac"],
               "gate_idle_frac": GATE_IDLE_FRAC,
               "stale_rows_dropped": out["async"]["stale_rows_dropped"],
               "trace_overhead": out["trace_overhead"],
               "gate_trace_overhead": GATE_TRACE_OVERHEAD,
               "trace_max_residual": out["traced"]["trace_max_residual"],
               "trace_episodes": out["traced"]["trace_episodes"],
               "trace_valid": out["traced"]["trace_valid"]})
    rec["pass"] = out["pass"]
    write_bench_json("BENCH_async_train.json", rec)
    return 0 if out["pass"] else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
