"""How ``correct`` is decided: what the timed path produced, against the
plain reference of the cell's configuration (``bench/configs/<ref>.py``).

Numbers compared, each against its own limit (``bench/limits/<cell>.json``,
set from the readings in PERF.md):

- ``rows_unaccounted``: episodes issued to the engine that reached no
  terminal fate (trained, dropped stale, failed, quarantined, orphaned)
  and were not still queued for the trainer or in the engine at stop;
  exact, limit 0;
- ``supervisor_restarts``: stage workers the supervisor had to restart;
  limit 0;
- ``lp_gap``: the widest gap between a served token's recorded logprob and
  the reference's, over a seeded sample of the check tenants' episodes
  finished in the window (the one with the most tokens compared among
  them): every generated token, sampled or forced, through the paged
  cache and tool resumes, up to the first write of a newer adapter into
  the tenant's slot while the episode ran (``harness.CheckRow``). The
  reference follows each check tenant's steps on the batches the program
  trained, so it holds the adapter version each sampled row ran under;
- ``greedy_gap``: on the greedy check tenant's sampled tokens of those
  rows, the widest gap by which the served token's reference logit lies
  below the reference's best;
- ``train_loss_gap``: the widest gap between the program's GRPO loss and
  the reference's over each check tenant's first ``CHECK_STEPS`` steps
  (the reference follows its own state from the benchmark's initial
  adapters);
- ``train_grad_gap``: over the leaves of the first step's gradient as the
  optimizer got it (Adam's first moment / (1 - b1)), the widest gap
  between the program's leaf norm and the reference's, relative to the
  larger of the reference's leaf norm and the median leaf's;
- ``train_update_gap``: the same for the adapters' change over the first
  ``CHECK_STEPS`` steps, leaving out leaves whose reference gradient is
  under a thousandth of the median leaf's.
"""
from __future__ import annotations

import importlib
import json
import random
from pathlib import Path
from typing import Dict, List

import numpy as np

BENCH = Path(__file__).resolve().parent
ROWS_PER_TENANT = 6
TINY_LEAF = 1e-3          # leaves under this share of the median leaf's
                          # reference gradient are left out of the update
NOT_FINITE = 1e300        # a reading that is not a number fails every limit


def reference(conf_file: dict):
    return importlib.import_module(f"bench.configs.{conf_file['reference']}")


def shape_of(conf_file: dict) -> dict:
    return reference(conf_file).shape(conf_file["config"])


def limits(workload: str) -> Dict[str, float]:
    return json.loads((BENCH / "limits" / f"{workload}.json").read_text())


def trainer_settings(mix: dict) -> dict:
    """The trainer's published settings as the reference runs them."""
    return {"lr": mix["lr"], "b1": 0.9, "b2": 0.999, "eps": 1e-8,
            "grad_clip": 1.0, "clip_eps": 0.2, "is_cap": 2.0}


def assert_trainer_matches(rt, trainer: dict) -> None:
    """The runtime trains with the settings the reference follows."""
    spec = rt.mgr.spec_for(next(iter(dict(rt.mgr.task_items()))))
    tc = rt._tc(spec)
    a = tc.adamw
    got = {"lr": a.lr, "b1": a.b1, "b2": a.b2, "eps": a.eps,
           "grad_clip": a.grad_clip, "clip_eps": tc.clip_eps,
           "is_cap": tc.is_cap}
    if a.weight_decay or a.warmup_steps or tc.kl_coef or tc.ent_coef:
        raise ValueError(f"trainer has terms the reference lacks: {tc}")
    if got != trainer or not tc.recompute_old:
        raise ValueError(f"trainer settings {got} differ from the "
                         f"reference's {trainer}")


def row_accounting(rt) -> Dict[str, int]:
    """The runtime's row accounting after stop (its ``completed`` counts
    every row issued to the engine), with the two places a row can still
    be when the run is stopped: queued for the trainer (ready, or awaiting
    group siblings) and in the engine (queued, resident or parked)."""
    acc = dict(rt.row_accounting())
    acc["queued_for_trainer"] = rt.mgr.ready_rows() + rt.mgr.partial_rows()
    acc["in_engine_at_stop"] = sum(rt.mgr.inflight_rows().values())
    return acc


def _worst(*xs) -> float:
    """The largest of the readings, NaN where any is NaN (Python's max
    would drop a NaN that is not first)."""
    x = np.asarray(xs, np.float64)
    return float(np.max(x)) if x.size else 0.0


def _leaf_gaps(prog: Dict[str, float], ref: Dict[str, float],
               keep=None) -> float:
    med = float(np.median(list(ref.values())))
    gaps = [abs(prog[k] - ref[k]) / max(ref[k], med, 1e-30)
            for k in ref if keep is None or k in keep]
    return _worst(*gaps)


def _norms(tree) -> Dict[str, float]:
    import jax
    out = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        out[jax.tree_util.keystr(path)] = float(
            np.sqrt(np.sum(np.asarray(leaf, np.float64) ** 2)))
    return out


def reference_training(ref, s, scaling, weights, init_lora, steps, mix,
                       trainer, check_steps, low=None, batch_fault=None):
    """Follow each check tenant's steps with the reference, on the batches
    the program trained: returns per tenant the losses, the first step's
    clipped-gradient leaf norms, the change's leaf norms after
    ``check_steps`` steps, and the adapters after each step (keyed by
    version)."""
    import jax
    row_grad = ref.make_row_grad(s, scaling, trainer, low)
    out = {}
    for tid, st in steps.items():
        lora = ref.lora_view(init_lora[tid])
        opt = ref.adam_init(lora)
        res = {"loss": [], "versions": {0: lora}}
        for k, step in enumerate(st):
            batch = step["batch"]
            if batch_fault is not None:
                batch = batch_fault(batch)
            lora, opt, loss, clipped = ref.train_step(
                row_grad, weights, lora, opt, batch, mix["group_size"],
                trainer)
            res["loss"].append(loss)
            res["versions"][k + 1] = lora
            if k == 0:
                res["grad"] = _norms(clipped)
            if k + 1 == check_steps:
                res["delta"] = _norms(jax.tree.map(
                    lambda a, b: a - b, lora, ref.lora_view(init_lora[tid])))
        out[tid] = res
    return out


def training_gaps(steps, refs, check_steps) -> Dict[str, float]:
    loss = grad = upd = 0.0
    for tid, st in steps.items():
        r = refs[tid]
        for k, step in enumerate(st[:check_steps]):
            loss = _worst(loss, abs(step["loss"] - r["loss"][k]))
        prog_g = _norms(st[0]["grad_norms"]["layers"])
        grad = _worst(grad, _leaf_gaps(prog_g, r["grad"]))
        med = float(np.median(list(r["grad"].values())))
        keep = {k for k, v in r["grad"].items() if v >= TINY_LEAF * med}
        prog_d = _norms(st[check_steps - 1]["delta_norms"]["layers"])
        upd = _worst(upd, _leaf_gaps(prog_d, r["delta"], keep))
    return {"train_loss_gap": loss, "train_grad_gap": grad,
            "train_update_gap": upd}


def sample_rows(rows: Dict[str, list], seed: int, max_version) -> List:
    """A seeded sample of each check tenant's rows (``harness.CheckRow``)
    with tokens of their own version alone, of versions the program
    committed by the end of the run (``max_version`` per tenant); the row
    with the most such tokens always among them."""
    out = []
    for tid in sorted(rows):
        cand = [r for r in rows[tid] if r.clean > 0
                and r.comp.version <= max_version[tid]]
        if not cand:
            continue
        cand.sort(key=lambda r: r.comp.submit_index)
        longest = max(cand, key=lambda r: r.clean)
        rest = [r for r in cand if r is not longest]
        random.Random(seed).shuffle(rest)
        out += [longest] + rest[:ROWS_PER_TENANT - 1]
    return out


def rollout_readings(ref, s, scaling, weights, versions, rows, max_len,
                     greedy_tids, low=None, alter=False, dump=None):
    """Per sampled row, the reference over prompt + served tokens under
    the row's adapter version, read on the row's tokens of that version
    alone (``CheckRow.clean``). Returns (lp_gap, greedy_gap, served tokens
    compared). With ``low`` the readings are the control's: its own
    logprobs against the reference's, and the reference gap of the token
    it puts first."""
    import functools
    import jax
    import jax.numpy as jnp
    from repro.data import tokenizer as tok
    from bench.traffic.generator import REMAP_ID
    stats = jax.jit(functools.partial(ref.token_stats, s=s, scaling=scaling))
    stats_low = (jax.jit(functools.partial(ref.token_stats, s=s,
                                           scaling=scaling, low=low))
                 if low else None)
    policy_ids = {tok.CALL, tok.EOS, REMAP_ID}
    lp_gap = greedy_gap = 0.0
    n = 0
    for row in rows:
        comp = row.comp
        lora = versions[comp.task_id][comp.version]
        toks = np.zeros((max_len,), np.int32)
        toks[:len(comp.tokens)] = comp.tokens
        p, g = comp.prompt_len, row.clean
        served_lp = np.asarray(comp.gen_logprobs[:g], np.float64)
        if alter:                      # a token altered where produced
            toks[p + g // 2] = (toks[p + g // 2] + 1) % s["V"]
        pos = np.arange(p - 1, p - 1 + g)
        lp, tgt, best, _ = (np.asarray(x) for x in stats(
            weights, lora, jnp.asarray(toks)))
        ref_lp = lp[pos].astype(np.float64)
        if stats_low is not None:
            lp_l, _, _, arg_l = (np.asarray(x) for x in stats_low(
                weights, lora, jnp.asarray(toks)))
            gap_tok = np.abs(lp_l[pos] - ref_lp)
        else:
            gap_tok = np.abs(served_lp - ref_lp)
        lp_gap = _worst(lp_gap, *gap_tok)
        if dump is not None:
            dump.append(_row_detail(row, served_lp, gap_tok))
        n += g
        if comp.task_id in greedy_tids:
            mask = np.asarray(comp.gen_loss_mask[:g]) == 1.0
            mask &= ~np.isin(np.asarray(comp.tokens[p:p + g]),
                             list(policy_ids))
            if mask.any():
                if stats_low is not None:
                    # reference gap of the token the control puts first
                    full = np.asarray(stats_all(ref, s, scaling, weights,
                                                lora, toks))
                    gap = best[pos] - full[pos, arg_l[pos]]
                else:
                    gap = best[pos] - tgt[pos]
                greedy_gap = _worst(greedy_gap, *gap[mask])
    return lp_gap, greedy_gap, n


def _row_detail(row, served_lp, gap, top: int = 12) -> dict:
    """Where one row's served logprobs depart from the reference (for the
    calibration tool's dump)."""
    comp = row.comp
    order = np.argsort(-gap)[:top]
    return {"task": comp.task_id, "version": comp.version,
            "clean": row.clean, "submit_index": comp.submit_index,
            "prompt_len": comp.prompt_len,
            "gen_len": len(comp.tokens) - comp.prompt_len,
            "finish": comp.finish_reason, "max_gap": float(gap.max()),
            "mean_gap": float(gap.mean()),
            "worst": [[int(i), float(comp.gen_loss_mask[i]),
                       int(comp.tokens[comp.prompt_len + i]),
                       float(served_lp[i]), float(gap[i])]
                      for i in order]}


def stats_all(ref, s, scaling, weights, lora, toks):
    """[S-1, V] reference logits of one sequence (the control's greedy
    reading needs the reference logit of another token than the served)."""
    import jax
    import jax.numpy as jnp
    with jax.default_matmul_precision("highest"):
        h = ref.hidden(weights, lora, jnp.asarray(toks), s, scaling)
        return ref.logits(weights, h)[:-1]


def half_batch(batch):
    """Fault: half of the batch left out, the mean taken over the rest."""
    b = dict(batch)
    m = np.array(b["loss_mask"], copy=True)
    m[m.shape[0] // 2:] = 0.0
    b["loss_mask"] = m
    return b


def _as_program(refs, low_refs, check_steps):
    """Readings of another trainer (control or fault) taken the way the
    program's are: its losses, first-step gradient and change."""
    steps = {}
    for tid, r in low_refs.items():
        steps[tid] = [{"loss": l} for l in r["loss"]]
        steps[tid][0]["grad_norms"] = {"layers": r["grad_n"]}
        steps[tid][check_steps - 1]["delta_norms"] = {"layers": r["delta_n"]}
    return training_gaps(steps, refs, check_steps)


def judge(numbers: Dict[str, float], lim: Dict[str, float]):
    """Each number beside its limit, and ``correct``: every number within
    its limit. The program's readings and the control's go through this
    one comparison; a reading that is not finite is printed as
    ``NOT_FINITE`` and fails."""
    checks = {k: {"value": float(v) if np.isfinite(v) else NOT_FINITE,
                  "limit": float(lim[k])} for k, v in numbers.items()}
    return checks, all(c["value"] <= c["limit"] for c in checks.values())


def calibration_readings(ref, s, scaling, weights, init, steps, mix,
                         trainer, refs, versions, sample, greedy, numbers,
                         lim, check_steps):
    """The control (the reference in float8, put in the program's place)
    and the planted faults, read by the same numbers as the program and
    judged by the same limits: each has to come out not correct."""
    out = {}
    first = {tid: st[:check_steps] for tid, st in steps.items()}
    lp, gg, _ = rollout_readings(ref, s, scaling, weights, versions, sample,
                                 mix["max_len"], greedy, low="fp8")
    control = {"lp_gap": lp, "greedy_gap": gg}

    def readings(low, fault):
        r = reference_training(ref, s, scaling, weights, init, first, mix,
                               trainer, check_steps, low=low,
                               batch_fault=fault)
        for v in r.values():
            v["grad_n"] = _tree_of(v["grad"])
            v["delta_n"] = _tree_of(v["delta"])
        return _as_program(refs, r, check_steps)

    control.update(readings("fp8", None))
    halfbatch = readings(None, half_batch)
    lp, _, _ = rollout_readings(ref, s, scaling, weights, versions, sample,
                                mix["max_len"], greedy, alter=True)
    altered = {"lp_gap": lp}
    for name, got in (("control", control), ("halfbatch", halfbatch),
                      ("altered_token", altered)):
        for k, v in got.items():
            out[f"{name}_{k}"] = v
        out[f"{name}_correct"] = judge(dict(numbers, **got), lim)[1]
    return out


def _tree_of(norms: Dict[str, float]):
    """{"['t']['a']": x} -> {"t": {"a": x}} (norm readings as a tree)."""
    tree: Dict[str, dict] = {}
    for key, v in norms.items():
        parts = [p.strip("'") for p in key.strip("[]").split("][")]
        node = tree
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = v
    return tree


def run_checks(*, conf_file, cfg, params, mix, seed, tenants, check_tids,
               adapters, steps, rows, trainer, accounting, restarts,
               log, workload, calibrate=False, limit_values=None):
    """Every number compared, each with its limit, and ``correct``; with
    ``calibrate`` also the control's and the planted faults' readings
    (under ``"_calibration"`` of the checks)."""
    import time
    from bench import harness
    t0 = time.monotonic()
    ref = reference(conf_file)
    s = ref.shape(conf_file["config"])
    lcfg = conf_file["lora"]
    scaling = lcfg["alpha"] / lcfg["rank"]
    if (cfg.lora.rank, cfg.lora.alpha) != (lcfg["rank"], lcfg["alpha"]):
        raise ValueError("LoRA rank/alpha differ from the configuration file")
    weights = ref.weights_view(params)
    init = {tid: adapters[i] for tid, i in check_tids.items()}
    k = harness.CHECK_STEPS
    sample = sample_rows(rows, seed, {t: len(st) for t, st in steps.items()})
    need = {tid: max([r.comp.version for r in sample
                      if r.comp.task_id == tid], default=0)
            for tid in steps}
    follow = {tid: st[:max(need[tid], k)] for tid, st in steps.items()}
    refs = reference_training(ref, s, scaling, weights, init, follow, mix,
                              trainer, k)
    numbers = {
        "rows_unaccounted": float(
            accounting["completed"] - sum(v for key, v in accounting.items()
                                          if key != "completed")),
        "supervisor_restarts": float(sum(restarts.values())),
    }
    numbers.update(training_gaps(steps, refs, k))
    versions = {tid: r["versions"] for tid, r in refs.items()}
    greedy = {t.task_id for t in tenants if t.temperature == 0.0}
    detail = [] if calibrate else None
    lp_gap, greedy_gap, n = rollout_readings(
        ref, s, scaling, weights, versions, sample, mix["max_len"], greedy,
        dump=detail)
    numbers["lp_gap"], numbers["greedy_gap"] = lp_gap, greedy_gap
    lim = limit_values or limits(workload)
    cut = sum(r.clean < len(r.comp.tokens) - r.comp.prompt_len
              for r in sample)
    log(f"checks: {len(sample)} episodes ({cut} cut at an in-flight adapter "
        f"write), {n} served tokens compared, versions "
        f"{sorted({r.comp.version for r in sample})}, reference followed "
        f"{max(len(f) for f in follow.values())} steps; it took "
        f"{time.monotonic() - t0:.1f} s")
    checks, correct = judge(numbers, lim)
    for key, v in checks.items():
        log(f"check {key}: {v['value']!r} (limit {v['limit']!r})")
    if calibrate:
        checks["_calibration"] = calibration_readings(
            ref, s, scaling, weights, init, steps, mix, trainer, refs,
            versions, sample, greedy, numbers, lim, k)
        checks["_calibration"]["rows"] = detail
    return checks, correct
