"""Fault-tolerance gate: chaos-scripted 16-tenant mixed workload (ISSUE 10).

Workload: the bench_async_train shape — 8 plain gsm8k tenants + 8 agentic
search tenants with a deterministic forced-CALL pattern — through the
fully disaggregated threaded runtime (async prefill workers, env-stage
workers, event-driven off-policy trainer).

Three arms:

  base    — fault-free. Run TWICE: the first doubles as the jit warm
            pass, and the two runs' reward histories must be
            bit-identical (chaos-off determinism — with ``chaos=None``
            no injector object exists, so the fault hooks cost one
            attribute check and cannot perturb the stream).
  chaos   — a capped deterministic fault script over every site the
            supervisor covers: prefill-worker kills and env-worker kills
            (restart + in-flight recovery), transient tool errors
            (retry-then-succeed), and a permanent tool-error burst that
            trips at least one agentic tenant's circuit breaker
            (fail_threshold=1) through quarantine and back out.

Gates (all must hold):

  - the chaos run COMPLETES: every tenant reaches target_steps (faults
    are capped, so every breaker trip must recover — an abandoned or
    wedged tenant fails the bench);
  - the extended row-conservation invariant holds EXACTLY on both arms:
    completed == trained + stale_dropped + discarded_tails + failed
    + quarantine_dropped + orphaned;
  - the script actually fired: worker kills on both stages, supervisor
    restarts, >= 1 quarantine trip;
  - healthy-tenant goodput (trained rows/sec over tenants untouched by
    faults) >= GATE_GOODPUT x the fault-free arm's;
  - chaos-off determinism: the two base runs' rewards are identical and
    an all-zero ChaosConfig builds no injector at all.

  PYTHONPATH=src python -m benchmarks.bench_chaos [--json out.json]
"""
from __future__ import annotations

import dataclasses
import json
import sys
import time

import jax
import jax.numpy as jnp

from repro.configs import REGISTRY, reduced
from repro.core.chaos import ChaosConfig
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
ENV_LATENCY = 0.2             # per forced tool call (deterministic: std 0)
CALL_AT = 2                   # sampled-token counter that emits CALL
MAX_STALENESS = 2
ENV_WORKERS = 16
GATE_GOODPUT = 0.85           # healthy-tenant goodput vs fault-free

CHAOS = ChaosConfig(
    seed=0,
    prefill_worker_kill=1.0,      # first pickups die; supervisor restarts
    env_worker_kill=1.0,
    tool_error_transient=1.0,     # retry-then-succeed burst
    transient_fail_count=1,
    tool_error_permanent=1.0,     # breaker-tripping burst
    max_faults_per_site=2)        # ...all exactly twice, then never again

_STATE = {}


def _bias_sampler():
    """Deterministic forced-CALL pattern (see bench_async_train): every
    row samples CALL at token counter CALL_AT and EOS is remapped away,
    so tool-call traffic never depends on what the tiny random model
    happens to sample."""
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


def _runtime(chaos):
    enable_compile_cache()
    _bias_sampler()
    cfg, params = _model()
    rt = MARLaaSRuntime(cfg, params, RuntimeConfig(
        policy="marlaas", max_len=MAX_LEN, max_slots=DECODE_SLOTS,
        max_adapter_slots=N_TENANTS, seed=0,
        disagg_prefill=True, prefill_workers=2,
        env_stage=True, env_workers=ENV_WORKERS,
        async_train=True, max_staleness=MAX_STALENESS, min_train_rows=0,
        chaos=chaos, tool_retry_base_s=0.01, tool_retry_max_s=0.1,
        breaker_fail_threshold=1, breaker_cooldown_s=0.3,
        breaker_max_trips=4))
    for i in range(N_TENANTS):
        agentic = i >= PLAIN_TENANTS
        env = "search" if agentic else "gsm8k"
        rt.submit_task(TaskSpec(
            f"{env}-{i}", env, group_size=GROUP_SIZE, num_groups=NUM_GROUPS,
            max_new_tokens=AGENTIC_BUDGET if agentic else PLAIN_BUDGET,
            target_steps=TARGET_STEPS))
        if agentic:
            rt.envs[f"{env}-{i}"].env_latency_mean = ENV_LATENCY
            rt.envs[f"{env}-{i}"].env_latency_std = 0.0
    return rt


def _accounting(rt) -> dict:
    acc = rt.row_accounting()
    acc["exact"] = acc["completed"] == (
        acc["trained"] + acc["stale_dropped"] + acc["discarded_tails"]
        + acc["failed"] + acc["quarantine_dropped"] + acc["orphaned"])
    return acc


def _healthy_goodput(rt, t0: float) -> dict:
    """Trained rows/sec over the tenants no fault ever touched (every
    tenant in the fault-free arm). Timed to the LAST healthy commit —
    quarantined tenants' cooldown stalls must not dilate the healthy
    denominator."""
    healthy = [st for _, st in rt.mgr.task_items()
               if st.failed_rows == 0 and st.quarantine_dropped_rows == 0]
    rows = sum(st.steps_done * rt.mgr.train_threshold(st.spec)
               for st in healthy)
    t1 = max((st.last_step_at for st in healthy if st.last_step_at),
             default=t0)
    span = max(1e-9, t1 - t0)
    return {"healthy_tenants": len(healthy), "healthy_rows": rows,
            "healthy_span_s": span, "goodput_rows_per_s": rows / span}


def _run_arm(chaos) -> dict:
    rt = _runtime(chaos)
    t0 = time.monotonic()
    rt.run(timeout_s=600.0)
    done = all(st.done for _, st in rt.mgr.task_items())
    at_target = all(st.steps_done >= TARGET_STEPS
                    for _, st in rt.mgr.task_items())
    c = rt.rec.counters_snapshot()
    out = {
        "wall_s": time.monotonic() - t0,
        "completed": done, "all_at_target": at_target,
        "rewards": {tid: list(st.reward_history)
                    for tid, st in rt.mgr.task_items()},
        "accounting": _accounting(rt),
        "goodput": _healthy_goodput(rt, t0),
        "chaos_injected": dict(rt.chaos.counts()) if rt.chaos else {},
        "supervisor": {k: v for k, v in c.items()
                       if k.startswith(("supervisor_", "env_", "chaos_"))},
        "quarantine_trips": c.get("quarantine_trips", 0),
        "quarantine_recoveries": c.get("quarantine_recoveries", 0),
        "quarantine_abandoned": c.get("quarantine_abandoned", 0),
        "breaker_timeline": [(round(t, 3), tid, s)
                             for t, tid, s in rt.rec.breaker_timeline()],
        **rt.mgr.drop_counters(),
    }
    return out


def bench():
    out = {"config": {
        "plain_tenants": PLAIN_TENANTS, "agentic_tenants": AGENTIC_TENANTS,
        "decode_slots": DECODE_SLOTS, "group_size": GROUP_SIZE,
        "target_steps": TARGET_STEPS, "env_latency_s": ENV_LATENCY,
        "max_staleness": MAX_STALENESS,
        "chaos": dataclasses.asdict(CHAOS)}}
    warm = _run_arm(None)               # fault-free + jit warm pass
    base = _run_arm(None)               # fault-free, cache-hot (measured)
    chaos = _run_arm(CHAOS)
    # chaos-off determinism: identical reward streams run-to-run, and a
    # disabled config builds no injector object at all
    deterministic = warm["rewards"] == base["rewards"]
    no_injector = _runtime(ChaosConfig()).chaos is None
    for arm in (warm, base, chaos):
        arm.pop("rewards")
    out["base"], out["chaos"] = base, chaos
    ratio = (chaos["goodput"]["goodput_rows_per_s"]
             / max(1e-9, base["goodput"]["goodput_rows_per_s"]))
    inj = chaos["chaos_injected"]
    faults_fired = (inj.get("prefill_worker_kill", 0) >= 1
                    and inj.get("env_worker_kill", 0) >= 1
                    and inj.get("tool_error_permanent", 0) >= 1
                    and chaos["supervisor"].get(
                        "supervisor_prefill_worker_restarts", 0) >= 1
                    and chaos["supervisor"].get(
                        "supervisor_env_worker_restarts", 0) >= 1
                    and chaos["quarantine_trips"] >= 1)
    out["goodput_ratio"] = float(ratio)
    out["gate_goodput"] = GATE_GOODPUT
    out["chaos_off_deterministic"] = bool(deterministic and no_injector)
    ok = (chaos["completed"] and chaos["all_at_target"]
          and base["accounting"]["exact"] and chaos["accounting"]["exact"]
          and faults_fired
          and ratio >= GATE_GOODPUT
          and out["chaos_off_deterministic"])
    out["pass"] = bool(ok)
    print(f"bench_chaos,tenants={N_TENANTS},slots={DECODE_SLOTS},"
          f"steps={TARGET_STEPS},"
          f"base_wall={base['wall_s']:.2f}s,"
          f"chaos_wall={chaos['wall_s']:.2f}s,"
          f"goodput_ratio={ratio:.3f},"
          f"kills={inj.get('prefill_worker_kill', 0)}+"
          f"{inj.get('env_worker_kill', 0)},"
          f"tool_faults={inj.get('tool_error_transient', 0)}+"
          f"{inj.get('tool_error_permanent', 0)},"
          f"trips={chaos['quarantine_trips']},"
          f"recoveries={chaos['quarantine_recoveries']},"
          f"failed_rows={chaos['failed_rows']},"
          f"quarantine_dropped={chaos['quarantine_dropped_rows']},"
          f"invariant={'exact' if chaos['accounting']['exact'] else 'BROKEN'},"
          f"{'ok' if out['pass'] else 'FAIL'}")
    return out


def main(argv):
    json_path = None
    if "--json" in argv:
        i = argv.index("--json")
        if i + 1 >= len(argv):
            print("usage: bench_chaos [--json OUT.json]")
            return 2
        json_path = argv[i + 1]
    out = bench()
    if json_path:
        with open(json_path, "w") as f:
            json.dump(out, f, indent=2)
        print(f"wrote {json_path}")
    from benchmarks.common import bench_record, write_bench_json
    rec = bench_record(
        "chaos", GATE_GOODPUT,
        out["chaos"]["goodput"]["goodput_rows_per_s"],
        out["base"]["goodput"]["goodput_rows_per_s"],
        extra={"chaos_completed": out["chaos"]["completed"],
               "invariant_exact": out["chaos"]["accounting"]["exact"],
               "chaos_injected": out["chaos"]["chaos_injected"],
               "quarantine_trips": out["chaos"]["quarantine_trips"],
               "quarantine_recoveries": out["chaos"]["quarantine_recoveries"],
               "failed_rows": out["chaos"]["failed_rows"],
               "quarantine_dropped_rows":
                   out["chaos"]["quarantine_dropped_rows"],
               "chaos_off_deterministic": out["chaos_off_deterministic"]})
    rec["pass"] = out["pass"]
    write_bench_json("BENCH_chaos.json", rec)
    return 0 if out["pass"] else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
