"""Rollout engines (paper §4.1/§4.4/§4.5): cross-task multi-LoRA batched
generation with agentic tool-call force-feeding.

Two engines share one set of jitted kernels and one per-row sampling rule:

``RolloutEngine.generate()`` — the round-fused baseline. One fixed batch
runs to completion; every row waits for the slowest before the next round
can start. This is the barrier MARLaaS measures against (§4.1).

``ContinuousRolloutEngine`` — the slot model. A persistent pool of
``max_slots`` decode slots holds rows from *any* tenant, each tagged with a
per-slot adapter id into a fixed-capacity stacked-LoRA buffer. Decode is
one jitted step over the pool and never drains: the moment a row finishes
(EOS / sampled budget / cache capacity) it is evicted, its
``RolloutCompletion`` streams back to the scheduler, and freed slots are
filled from a cross-task request queue. Two fill paths (Fig 5):

  fused (default)       — prefill of the incoming rows runs as its own
    jitted call ON THE DECODE STREAM (batched over every slot freed that
    step) whose KV/SSM state and sampled first tokens are spliced into the
    running pool. A long prompt stalls decode for every resident tenant —
    this stall is booked as ``stats.decode_stall_seconds``.
  disaggregated (``disagg_prefill=True``) — ``prefill_workers`` async
    worker threads (rollout/prefill.py) pop the SAME scheduler-ordered
    queue, run (optionally ``prefill_chunk``-chunked) prefill on their own
    caches, and emit ready row states; the decode stream installs them
    with a scatter-only jitted splice (``_build_splice_fn``). Decode never
    executes a prefill graph: ``decode_stall_seconds`` stays 0 while
    prefills are in flight, and outputs are bit-identical to the fused
    path (same forward math, same per-row sampling rule).

Agentic rows and the environment-interaction stage (two modes):

  freeze-in-slot (default baseline) — a row that emits ``tok.CALL`` keeps
    its decode slot with advance=0 for the whole env latency while the
    rest of the pool decodes; every such frozen step is booked as
    ``stats.tool_wait_slot_steps`` (the dead weight Fig 5 is about).
  env stage (``env_stage=True``) — the row is PARKED instead: its
    generated prefix is already host-side (the preemption snapshot), so
    the slot is vacated and instantly refilled from the scheduler queue
    while an EnvWorker (rollout/env_stage.py) runs the tool call. The
    response turns into a resume job: the row re-enters the scheduler
    queue with its force-feed queue pre-loaded and flows through the
    ordinary (fused or disaggregated) prefill path — prefix replay plus a
    FORCED first token (the RESP opener) — then splices back. No decode
    slot is ever occupied by an I/O-waiting row
    (``tool_wait_slot_steps == 0`` by construction), and the token stream
    is bit-identical to freeze-in-slot given the same tool responses.

Multi-turn episodes: each agentic row owns one stateful ``ToolSession``
(created at its first call, carried across park/preempt/replay) and a turn
budget (``request.max_turns``, default ``env.max_turns``; 0 = unlimited).
A CALL sampled with the budget spent ends the episode
(``finish_reason="turn_limit"``).

Paged KV cache + snapshot/restore resume (``paged_kv=True``, ISSUE 5):
attention K/V lives in a SHARED block pool of ``kv_pool_pages`` fixed-size
pages (``kv_page_size`` tokens each; rollout/kvcache.py owns the free
list, ``models.init_paged_cache`` lays out the device side, and decode
reads pages through per-slot block tables — the Pallas
``kernels/paged_decode.py`` kernel under ``use_kernel``). A slot holds
``ceil(len/page)`` pages instead of a ``max_len`` reservation, growing
one page at a time as it decodes; a row the pool cannot serve finishes
via cache-capacity eviction (never a crash). Park (env stage) and
preemption SNAPSHOT the row's live pages + SSM/conv state to host
(``resume_restore``), and resume SPLICES them back — no prefill replay, so
an N-turn agentic episode stops paying O(N·len) recomputation
(``stats.replay_tokens_saved``; ``stats.restores`` vs ``stats.replays``).
A snapshot dropped under ``snapshot_budget_bytes`` pressure falls back to
the RETAINED token-replay path — output is token-for-token identical
either way (property-tested across attention/SSM/hybrid, both fill
paths, preempt-at-any-turn).

Copy-on-write prefix cache (``prefix_cache=True``, default with paged
KV; ISSUE 8). Pages are shared at three levels over the ref-counted
pool, pure-attention families only (SSM/hybrid degrade to the private
behaviour above):

  GRPO-group sharing — same-``(tenant, prompt)`` rows are recognized in
    the queue: the leader prefills privately and publishes its prompt
    pages (full pages + the exact-remainder tail) to the per-tenant
    ``PrefixIndex``; siblings install via ``_radix_fill_rows`` with ZERO
    prompt writes — every page retained, the final chunk recomputed only
    for the first-token logits. The first decode write past the shared
    boundary hits a page with refcount > 1 and ``_ensure_decode_pages``
    COW-forks just that page (``stats.cow_forks``); earlier pages stay
    shared for the group's lifetime.
  device-resident snapshots — park/preempt of an in-pool row moves page
    OWNERSHIP from the slot to the row (pure retain, zero host bytes for
    attention; hybrid recurrent rows still snapshot) and resume is a
    block-table splice (``stats.device_resident_resumes``). The host
    ``KVSnapshot`` arena is demoted to a spill tier: under pool pressure
    ``_alloc_pages`` evicts cold radix entries, then spills the oldest
    device-parked row to host (or token replay).
  radix prefix reuse — any new request or tool-turn resume matches its
    longest cached page-aligned prefix and prefills only the suffix
    (``stats.prefix_hits`` / ``prefix_hit_tokens``; ``prefill_tokens``
    drops by exactly the matched length).

Response-prefill fusion (paged mode): a replay-path resume folds its
forced RESP…ENDRESP block into the one (re)prefill call — forced
logprobs gathered from the prefill logits, ``stats.fused_forced_tokens``
— instead of force-feeding one decode step per token; restore-mode
resumes never prefill at all, which subsumes it. Token streams are
bit-identical on every path (``tests/test_prefix_cache.py``), and
``check_page_invariants`` asserts exact refcount conservation across
slots, device-parked rows, and radix nodes.

Determinism: sampling is per-row — each request carries a base PRNG key
(``fold_in(master, request.seed or submit-index)``) folded with the row's
own generated-token count. A row's tokens therefore depend only on its own
(key, prefix), never on batch layout, so continuous-mode output matches
one-shot ``generate()`` token-for-token for families without cross-row
coupling (dense/hybrid; dropping-MoE capacity is batch-global).

Budget: only *sampled* tokens (loss_mask == 1) count against
``max_new_tokens``; force-fed tool-response tokens are budget-exempt, so a
long tool response cannot terminate a row before it samples its answer.

Preemption protocol (admission-driven, paper §4.3): ``preempt_slots`` /
``preempt_tenant`` evict *resident* rows mid-decode. A victim's generated
prefix lives entirely on the host (``_Row.gen``/``lps``/``lmask``), so
preemption is free of device copies: the slot is simply marked empty and
the row re-queued. When the scheduler later pops it, the refill call
prefill-replays ``prompt + gen`` as one sequence and samples the *next*
token with counter ``len(gen)`` — exactly the (key, counter) the
uninterrupted run would have used — so a row preempted at any decode step
finishes with bit-identical tokens/logprobs. Rows awaiting a tool response
or mid force-feed are not preemptible (a replayed first token is always
sampled, never forced); they keep their slot until the forced queue
drains. Queue pop order is pluggable (``scheduler=``):
shortest-predicted-remaining with priority tiers and a starvation bound
(default), or FIFO — see ``rollout/scheduler.py``.
"""
from __future__ import annotations

import time
from collections import deque
from concurrent.futures import Future, ThreadPoolExecutor
from dataclasses import dataclass, field
from typing import Callable, Deque, Dict, List, Optional, Sequence, Tuple

import threading

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ModelConfig
from repro.data import tokenizer as tok
from repro.core.supervisor import StageSupervisor
from repro.envs.base import CancelToken, Env, ToolError, call_session
from repro.lora.adapters import batched_ctx, init_stacked_buffer, stack_adapters
from repro.models import (decode_step, forward_prefill_chunk, forward_seq,
                          init_cache, init_paged_cache, lm_logits)
from repro.obs.phase import phase
from repro.rl.types import RolloutCompletion, TrajectoryBatch
from repro.rollout.env_stage import EnvStage
from repro.rollout.kvcache import (KVSnapshot, PagePool, PrefixIndex,
                                   SnapshotStore, pages_for)
from repro.rollout.prefill import (PrefillKernels, PrefillWorker, ReadyRow,
                                   _bucket_len, _sample_rows, effective_chunk)
from repro.rollout.scheduler import LengthPredictor, SlotScheduler

STEP_TRACK = ("rollout", "step")    # tracer track of the decode thread's
                                    # per-step phases


@dataclass
class RolloutRequest:
    task_id: str
    adapter_index: int            # row id into the stacked adapter tree
    prompt: List[int]
    truth: object
    env: Env
    max_new_tokens: int
    temperature: float = 1.0
    seed: Optional[int] = None    # per-row key = fold_in(master, seed)
                                  # (defaults to batch/submission index)
    priority: int = 0             # scheduler tier: higher pops first and is
                                  # never chosen as a preemption victim over
                                  # a lower tier
    max_turns: Optional[int] = None   # tool-turn budget for this episode
                                      # (None -> env.max_turns; 0 = unlimited)


@dataclass
class RolloutStats:
    decode_steps: int = 0
    prefill_tokens: int = 0
    decode_seconds: float = 0.0     # host time from each decode dispatch
                                    # to its token read (device step plus
                                    # transfer; the host's own time between
                                    # steps is host_gap_seconds)
    prefill_seconds: float = 0.0    # prefill-stage device time (fused refill
                                    # OR async prefill-worker calls)
    env_wait_seconds: float = 0.0
    wall_seconds: float = 0.0
    # continuous-engine extras (zero for round-fused generate())
    prefills: int = 0
    refills: int = 0
    completions: int = 0
    tokens_generated: int = 0
    sampled_tokens: int = 0
    occupied_row_steps: int = 0    # Σ over decode steps of advanced rows
    capacity_row_steps: int = 0    # decode_steps × max_slots
    preemptions: int = 0           # rows evicted mid-decode and re-queued
    replays: int = 0               # preempted rows re-prefilled into a slot
    replay_tokens: int = 0         # prompt+prefix tokens re-processed
    # disaggregated-prefill extras
    splices: int = 0               # ready rows scatter-installed into slots
    splice_seconds: float = 0.0    # decode-side scatter time (≪ prefill)
    splice_wait_seconds: float = 0.0    # Σ (install time - prefill-ready
                                        # time): hand-off latency between
                                        # the two stages (slot availability)
    prefill_chunks: int = 0        # prefill device calls (≥ rows prefilled)
    decode_stall_seconds: float = 0.0   # prefill-stage work executed ON the
                                        # decode stream (fused refill); 0 by
                                        # construction when disaggregated
    # environment-interaction stage extras
    parks: int = 0                 # rows vacated from their slot on CALL
    resumes: int = 0               # tool responses turned into resume jobs
    tool_errors: int = 0           # episodes finished by a permanent tool
                                   # failure / exhausted retry budget
                                   # (finish_reason "tool_error")
    # paged-KV / snapshot-restore extras (rollout/kvcache.py)
    restores: int = 0              # rows resumed by splicing saved KV pages
                                   # back (NO prefill replay ran)
    replay_tokens_saved: int = 0   # prompt+prefix tokens a replay would
                                   # have re-prefilled but restore skipped
    snapshots: int = 0             # park/preempt snapshots taken to host
    snapshot_drops: int = 0        # snapshots rejected under host memory
                                   # pressure (row fell back to replay)
    pool_exhausted: int = 0        # rows finished by cache-capacity
                                   # eviction when the page pool ran dry
    # prefix-cache extras (ISSUE 8: COW page sharing, rollout/kvcache.py)
    prefix_hits: int = 0           # rows installed off a radix/trie match
                                   # (retained prefix pages, suffix-only
                                   # prefill)
    prefix_hit_tokens: int = 0     # prefix tokens those hits did NOT
                                   # re-prefill (prefill_tokens drops by
                                   # exactly this much)
    cow_forks: int = 0             # shared pages privatized on first
                                   # decode write (alloc + 1-page copy)
    device_resident_resumes: int = 0   # park/preempt resumes whose KV
                                       # pages never left the pool (pure
                                       # retain; zero host snapshot bytes)
    fused_forced_tokens: int = 0   # forced RESP…ENDRESP tokens folded into
                                   # a resume's prefill call instead of one
                                   # decode step each (response fusion)
    tool_wait_slot_steps: int = 0  # Σ over decode steps of resident rows
                                   # frozen on a tool wait — the slot dead
                                   # weight env_stage drives to 0 by
                                   # construction
    # host-side waits, each booked where its work happens
    host_gap_seconds: float = 0.0  # Σ from one decode step's token read to
                                   # the next decode dispatch, over pairs of
                                   # consecutive steps that both decoded:
                                   # time the decode stream gives the
                                   # device nothing
    host_gaps: int = 0
    queue_wait_seconds: float = 0.0    # Σ from a row's push into the
                                       # scheduler queue to the pop that
                                       # hands it to an install path
                                       # (prefill worker, fused refill,
                                       # radix fill, restore)
    queue_pops: int = 0
    resume_wait_seconds: float = 0.0   # Σ from a tool response's arrival
                                       # to its row's return into a decode
                                       # slot
    resumes_installed: int = 0
    # paged decode attention's reach (paged_kv), a property of the traffic:
    # the pages the kernel at most has to visit, not a count of what it
    # read (a static window visits fewer)
    kv_live_pages: int = 0         # Σ over decode steps of the decoded
                                   # rows' live pages, ceil(pos / page)
                                   # with pos counting the step's token
    kv_table_pages: int = 0        # Σ over decode steps of decoded rows ×
                                   # block-table width
    env_wait_by_task: Dict[str, float] = field(default_factory=dict)
                                   # per-tenant env-interaction wait seconds

    def add_env_wait(self, task_id: str, wait: float):
        """Book one resolved tool call's wait (global + per-tenant)."""
        self.env_wait_seconds += wait
        self.env_wait_by_task[task_id] = (
            self.env_wait_by_task.get(task_id, 0.0) + wait)

    def slot_utilization(self) -> float:
        if self.capacity_row_steps <= 0:
            return 0.0
        return self.occupied_row_steps / self.capacity_row_steps


def _decode_sample_core(cfg, use_kernel, params, adapters, row_ids,
                        cur_tokens, cache, keys, counters, temps, forced,
                        forced_mask, advance):
    """The one decode-step body BOTH engines jit — identical math is what
    keeps continuous output token-for-token equal to one-shot output."""
    lora = batched_ctx(adapters, row_ids, cfg, use_kernel)
    logits, cache = decode_step(params, cur_tokens, cache, cfg, lora,
                                advance=advance, use_kernel=use_kernel)
    logp_all = jax.nn.log_softmax(logits, axis=-1)
    sampled = _sample_rows(logits, keys, counters, temps)
    nxt = jnp.where(forced_mask > 0, forced, sampled).astype(jnp.int32)
    lp = jnp.take_along_axis(logp_all, nxt[:, None], axis=-1)[:, 0]
    return nxt, lp, cache


def _build_fns(cfg: ModelConfig, use_kernel: bool):
    """The three jitted kernels of the round-fused engine."""

    def prefill(params, adapters, row_ids, tokens, prompt_lens, cache):
        lora = batched_ctx(adapters, row_ids, cfg, use_kernel)
        h, cache, _ = forward_seq(params, tokens, cfg, lora, cache,
                                  seq_lens=prompt_lens)
        cache = dict(cache, pos=prompt_lens)
        last = jnp.take_along_axis(
            h, (prompt_lens - 1)[:, None, None].astype(jnp.int32), axis=1)[:, 0]
        logits = lm_logits(last, params, cfg)
        return logits, cache

    def first(logits, keys, counters, temps):
        sampled = _sample_rows(logits, keys, counters, temps)
        lp = jnp.take_along_axis(jax.nn.log_softmax(logits, -1),
                                 sampled[:, None], axis=-1)[:, 0]
        return sampled.astype(jnp.int32), lp

    def step(params, adapters, row_ids, cur_tokens, cache, keys, counters,
             temps, forced, forced_mask, advance):
        return _decode_sample_core(cfg, use_kernel, params, adapters,
                                   row_ids, cur_tokens, cache, keys,
                                   counters, temps, forced, forced_mask,
                                   advance)

    return (jax.jit(prefill, donate_argnums=(5,)), jax.jit(first),
            jax.jit(step, donate_argnums=(4,)))


def _build_cont_step_fn(cfg: ModelConfig, use_kernel: bool):
    """Continuous-engine decode step with device-resident row state: cur
    tokens and per-row counters are carried through the call (frozen/empty
    lanes keep their previous token), so the host uploads nothing per step
    beyond the occasionally-changing advance/forced masks."""

    def step(params, adapters, row_ids, cur_tokens, cache, keys, counters,
             temps, forced, forced_mask, advance):
        nxt, lp, cache = _decode_sample_core(cfg, use_kernel, params,
                                             adapters, row_ids, cur_tokens,
                                             cache, keys, counters, temps,
                                             forced, forced_mask, advance)
        nxt = jnp.where(advance > 0, nxt, cur_tokens)
        return nxt, lp, cache, counters + advance

    return jax.jit(step, donate_argnums=(3, 4, 6))


def _build_refill_fn(cfg: ModelConfig, use_kernel: bool, max_len: int):
    """ONE jitted call that prefills a batch of incoming rows on a fresh
    width-k cache, samples their first tokens (counter 0), and splices every
    row's KV/SSM state into the persistent pool at its target slot.

    Ghost rows (queue shorter than the padded width) carry slot index ==
    pool size: their scatters are out of bounds and XLA drops them, so the
    call has a single static shape per (width, prompt-bucket) and the refill
    path costs one dispatch regardless of how many slots freed this step.
    The pool's device-resident row state (cur/counters/keys/temps/row_ids)
    is updated in the same call.

    `init_counters` is the per-row sampling counter for the token sampled
    off the prefill logits: 0 for fresh rows, `len(gen)` for
    preemption-replayed rows (whose `tokens` are prompt + generated prefix)
    — the replayed row's next token therefore uses the identical
    fold_in(key, counter) an uninterrupted run would have.

    `forced`/`forced_mask` override the sampled first token for env-stage
    resume rows: the prefix ends in CALL, so the installed token is the
    forced RESP opener with its logprob read off the same final-position
    logits — exactly what the freeze-in-slot baseline records when it
    feeds CALL through a decode step."""

    def refill(params, adapters, tokens, prompt_lens, init_counters, slots,
               new_row_ids, new_keys, new_temps, forced, forced_mask, cache,
               cur, counters, keys, temps, row_ids):
        pcache = init_cache(cfg, tokens.shape[0], max_len,
                            enc_len=8 if cfg.family == "encdec" else 0)
        lora = batched_ctx(adapters, new_row_ids, cfg, use_kernel)
        h, pcache, _ = forward_seq(params, tokens, cfg, lora, pcache,
                                   seq_lens=prompt_lens)
        pcache = dict(pcache, pos=prompt_lens)
        last = jnp.take_along_axis(
            h, (prompt_lens - 1)[:, None, None].astype(jnp.int32), axis=1)[:, 0]
        logits = lm_logits(last, params, cfg)
        sampled = _sample_rows(logits, new_keys, init_counters, new_temps)
        first = jnp.where(forced_mask > 0, forced, sampled).astype(jnp.int32)
        lp = jnp.take_along_axis(jax.nn.log_softmax(logits, -1),
                                 first[:, None], axis=-1)[:, 0]
        out = {}
        for name in cache:
            if cache[name].ndim == 1:              # "pos": [B]
                out[name] = cache[name].at[slots].set(pcache[name])
            else:                                   # [L, B, ...]
                out[name] = cache[name].at[:, slots].set(pcache[name])
        state = (cur.at[slots].set(first),
                 counters.at[slots].set(init_counters + 1),
                 keys.at[slots].set(new_keys),
                 temps.at[slots].set(new_temps),
                 row_ids.at[slots].set(new_row_ids))
        return first, lp, out, state

    return jax.jit(refill, donate_argnums=(11, 12, 13, 14, 15, 16))


def _build_splice_fn(cfg: ModelConfig):
    """Scatter-ONLY install of one prefilled row into the persistent pool
    (the decode half of the disaggregated split): copies every cache leaf of
    the ready row's width-1 prefill cache into the pool at `slot` and
    updates the device-resident row state. No forward pass, no prefill
    graph — the decode stream pays one cheap scatter per incoming row
    instead of the whole prompt."""

    def splice(cache, pcache, slot, seq_len, first, init_counter, key, temp,
               row_id, cur, counters, keys, temps, row_ids):
        out = {}
        for name in cache:
            if cache[name].ndim == 1:              # "pos": [B]
                out[name] = cache[name].at[slot].set(seq_len)
            else:                                   # [L, B, ...]
                out[name] = cache[name].at[:, slot].set(pcache[name][:, 0])
        state = (cur.at[slot].set(first),
                 counters.at[slot].set(init_counter + 1),
                 keys.at[slot].set(key),
                 temps.at[slot].set(temp),
                 row_ids.at[slot].set(row_id))
        return out, state

    return jax.jit(splice, donate_argnums=(0, 9, 10, 11, 12, 13))


def _paged_scatter(cfg: ModelConfig, cache, pcache_k, pcache_v, dest_pages,
                   page: int):
    """Scatter a dense prefill scratch cache's K/V ([L, W, S, KVH, hd],
    S % page == 0) into the shared page pool at the physical pages named
    by ``dest_pages`` [W, S//page] (sentinel entries land on the scratch
    page and are effectively dropped). Returns (kp', vp')."""
    L, W, S, KVH, hd = pcache_k.shape
    n_chunks = S // page
    src_k = pcache_k.reshape(L, W * n_chunks, page, KVH, hd)
    src_v = pcache_v.reshape(L, W * n_chunks, page, KVH, hd)
    dest = dest_pages.reshape(W * n_chunks)
    return (cache["kp"].at[:, dest].set(src_k.astype(cache["kp"].dtype)),
            cache["vp"].at[:, dest].set(src_v.astype(cache["vp"].dtype)))


def _build_refill_fn_paged(cfg: ModelConfig, use_kernel: bool, max_len: int,
                           page: int):
    """Paged twin of ``_build_refill_fn``: the batched prefill still runs
    on a dense width-k SCRATCH cache (prefill is contiguous by nature),
    but the splice writes page-granular — each incoming row's K/V
    scatters into the physical pages the host allocator handed it
    (`dest_pages`), its block-table row is mirrored host-side by the
    engine, and only ``ceil(seq_len/page)`` pages are consumed instead of
    a ``max_len`` reservation. Recurrent SSM/conv state is per-row and
    dense, spliced exactly as before.

    Response-prefill fusion: an env-stage resume's forced RESP…ENDRESP
    block is part of ``tokens`` (the host appends it to prompt+prefix), so
    the whole response prefills in THIS call instead of force-feeding one
    decode step per token. ``fpos``/``ftoks`` [W, F_B] name the positions
    whose logits predict each forced token and the tokens themselves;
    ``flp`` returns their logprobs — bit-equal to what the step-wise path
    records, because prefill logits at a position are identical to the
    decode step's logits there."""

    def refill(params, adapters, tokens, prompt_lens, init_counters, slots,
               dest_pages, new_row_ids, new_keys, new_temps, forced,
               forced_mask, fpos, ftoks, cache, cur, counters, keys, temps,
               row_ids):
        pcache = init_cache(cfg, tokens.shape[0], max_len)
        lora = batched_ctx(adapters, new_row_ids, cfg, use_kernel)
        h, pcache, _ = forward_seq(params, tokens, cfg, lora, pcache,
                                   seq_lens=prompt_lens)
        last = jnp.take_along_axis(
            h, (prompt_lens - 1)[:, None, None].astype(jnp.int32), axis=1)[:, 0]
        logits = lm_logits(last, params, cfg)
        sampled = _sample_rows(logits, new_keys, init_counters, new_temps)
        first = jnp.where(forced_mask > 0, forced, sampled).astype(jnp.int32)
        lp = jnp.take_along_axis(jax.nn.log_softmax(logits, -1),
                                 first[:, None], axis=-1)[:, 0]
        fh = jnp.take_along_axis(
            h, fpos[:, :, None].astype(jnp.int32), axis=1)
        flogits = lm_logits(fh, params, cfg)
        flp = jnp.take_along_axis(jax.nn.log_softmax(flogits, -1),
                                  ftoks[:, :, None], axis=-1)[:, :, 0]
        out = dict(cache)
        if "kp" in cache:
            out["kp"], out["vp"] = _paged_scatter(
                cfg, cache, pcache["k"], pcache["v"], dest_pages, page)
        if "ssm" in cache:
            out["ssm"] = cache["ssm"].at[:, slots].set(pcache["ssm"])
            out["conv"] = cache["conv"].at[:, slots].set(pcache["conv"])
        out["pos"] = cache["pos"].at[slots].set(prompt_lens)
        state = (cur.at[slots].set(first),
                 counters.at[slots].set(init_counters + 1),
                 keys.at[slots].set(new_keys),
                 temps.at[slots].set(new_temps),
                 row_ids.at[slots].set(new_row_ids))
        return first, lp, flp, out, state

    return jax.jit(refill, donate_argnums=(14, 15, 16, 17, 18, 19))


def _build_splice_fn_paged(cfg: ModelConfig, page: int):
    """Paged twin of ``_build_splice_fn``: installs one async-prefilled
    row (width-1 dense worker cache) by scattering its K/V into the pool
    pages the allocator assigned the row. Still scatter-only — no prefill
    graph touches the decode stream."""

    def splice(cache, pcache, slot, dest_pages, seq_len, first, init_counter,
               key, temp, row_id, cur, counters, keys, temps, row_ids):
        out = dict(cache)
        if "kp" in cache:
            out["kp"], out["vp"] = _paged_scatter(
                cfg, cache, pcache["k"], pcache["v"], dest_pages[None], page)
        if "ssm" in cache:
            out["ssm"] = cache["ssm"].at[:, slot].set(pcache["ssm"][:, 0])
            out["conv"] = cache["conv"].at[:, slot].set(pcache["conv"][:, 0])
        out["pos"] = cache["pos"].at[slot].set(seq_len)
        state = (cur.at[slot].set(first),
                 counters.at[slot].set(init_counter + 1),
                 keys.at[slot].set(key),
                 temps.at[slot].set(temp),
                 row_ids.at[slot].set(row_id))
        return out, state

    return jax.jit(splice, donate_argnums=(0, 10, 11, 12, 13, 14))


def _build_snap_fn(cfg: ModelConfig):
    """Gather one resident row's cache state for a host snapshot: its live
    KV pages (padded page list — sentinel entries gather the scratch page
    and are trimmed host-side) and its SSM/conv rows. Read-only: nothing
    is donated."""

    def snap(cache, pages, slot):
        out = {}
        if "kp" in cache:
            out["kp"] = jnp.take(cache["kp"], pages, axis=1)
            out["vp"] = jnp.take(cache["vp"], pages, axis=1)
        if "ssm" in cache:
            out["ssm"] = cache["ssm"][:, slot]
            out["conv"] = cache["conv"][:, slot]
        return out

    return jax.jit(snap)


def _build_restore_fn(cfg: ModelConfig):
    """Splice a host snapshot back into the pool: KV pages into freshly
    allocated physical pages, SSM/conv rows into the slot, `pos` to the
    snapshot position, and the device row state to (pending token,
    counter) — the next ordinary decode step then continues the row with
    the exact logits/sample an uninterrupted run would produce. NO
    prefill graph runs: this is the call that kills O(prefix) replay."""

    def restore(cache, kpages, vpages, dest_pages, slot, pos_val, ssm_row,
                conv_row, cur_tok, counter, key, temp, row_id, cur,
                counters, keys, temps, row_ids):
        out = dict(cache)
        if "kp" in cache:
            out["kp"] = cache["kp"].at[:, dest_pages].set(
                kpages.astype(cache["kp"].dtype))
            out["vp"] = cache["vp"].at[:, dest_pages].set(
                vpages.astype(cache["vp"].dtype))
        if "ssm" in cache:
            out["ssm"] = cache["ssm"].at[:, slot].set(ssm_row)
            out["conv"] = cache["conv"].at[:, slot].set(conv_row)
        out["pos"] = cache["pos"].at[slot].set(pos_val)
        state = (cur.at[slot].set(cur_tok),
                 counters.at[slot].set(counter),
                 keys.at[slot].set(key),
                 temps.at[slot].set(temp),
                 row_ids.at[slot].set(row_id))
        return out, state

    return jax.jit(restore, donate_argnums=(0, 13, 14, 15, 16, 17))


def _build_cow_fn(cfg: ModelConfig):
    """Copy-on-write fork of ONE page: duplicate physical page `src` into
    freshly allocated page `dst` (all attention layers). Runs when a row is
    about to decode-write into a page with refcount > 1 — the writer gets a
    private copy of just that page; every earlier shared page stays shared.
    src/dst are traced scalars, so one compiled variant serves every
    fork."""

    def cow(cache, src, dst):
        out = dict(cache)
        out["kp"] = cache["kp"].at[:, dst].set(cache["kp"][:, src])
        out["vp"] = cache["vp"].at[:, dst].set(cache["vp"][:, src])
        return out

    return jax.jit(cow, donate_argnums=(0,))


def _build_suffix_fn(cfg: ModelConfig, use_kernel: bool, max_len: int,
                     page: int):
    """Radix-hit install: the row's longest indexed prefix (`start` tokens,
    static — ``start // page`` retained pool pages) is GATHERED into a
    width-1 dense scratch, only the suffix runs through
    ``forward_prefill_chunk`` at offset `start` (attending over the gathered
    prefix — the same chunked-prefill decomposition the async workers use,
    exact for pure-attention stacks at any offset), and only the suffix
    chunks scatter back into fresh pool pages (`dest_pages` names the
    matched chunks as sentinel). First token sampling/forcing is identical
    to the whole-prompt refill: same final-position logits, same
    fold_in(key, init_counter) — so a radix hit is bit-equal to a full
    prefill, minus ``start`` tokens of compute."""

    def suffix(start, params, adapters, row_id, prefix_pages, tokens,
               seq_len, init_counter, key, temp, forced, forced_mask,
               cache, dest_pages, slot, cur, counters, keys, temps,
               row_ids):
        pcache = init_cache(cfg, 1, max_len)
        pk = jnp.take(cache["kp"], prefix_pages, axis=1)
        pv = jnp.take(cache["vp"], prefix_pages, axis=1)
        L, _, _, KVH, hd = pk.shape
        pcache = dict(
            pcache,
            k=pcache["k"].at[:, :, :start].set(
                pk.reshape(L, 1, start, KVH, hd).astype(pcache["k"].dtype)),
            v=pcache["v"].at[:, :, :start].set(
                pv.reshape(L, 1, start, KVH, hd).astype(pcache["v"].dtype)))
        lora = batched_ctx(adapters, row_id, cfg, use_kernel)
        h, pcache = forward_prefill_chunk(params, tokens, cfg, lora,
                                          pcache, start=start,
                                          seq_lens=seq_len - start)
        last = jnp.take_along_axis(
            h, (seq_len - 1 - start)[:, None, None].astype(jnp.int32),
            axis=1)[:, 0]
        logits = lm_logits(last, params, cfg)
        sampled = _sample_rows(logits, key, init_counter, temp)
        first = jnp.where(forced_mask > 0, forced, sampled).astype(jnp.int32)
        lp = jnp.take_along_axis(jax.nn.log_softmax(logits, -1),
                                 first[:, None], axis=-1)[:, 0]
        out = dict(cache)
        out["kp"], out["vp"] = _paged_scatter(
            cfg, cache, pcache["k"], pcache["v"], dest_pages[None], page)
        out["pos"] = cache["pos"].at[slot].set(seq_len[0])
        state = (cur.at[slot].set(first[0]),
                 counters.at[slot].set(init_counter[0] + 1),
                 keys.at[slot].set(key[0]),
                 temps.at[slot].set(temp[0]),
                 row_ids.at[slot].set(row_id[0]))
        return first, lp, out, state

    return jax.jit(suffix, static_argnums=(0,),
                   donate_argnums=(12, 15, 16, 17, 18, 19))


class _Row:
    """Host-side per-episode state machine (one slot / one batch lane when
    resident; parked rows hold no slot at all)."""
    __slots__ = ("req", "prompt_len", "gen", "lps", "lmask", "sampled",
                 "forced", "status", "forced_q", "finish_reason", "key",
                 "submit_index", "meta", "submitted_at", "started_at",
                 "replays", "session", "turns", "snap", "dev_pages",
                 "dev_pos", "tool_retries", "resumed_at")

    def __init__(self, req: RolloutRequest, key, submit_index: int,
                 meta=None, submitted_at: float = 0.0):
        self.req = req
        self.prompt_len = len(req.prompt)
        self.gen: List[int] = []
        self.lps: List[float] = []
        self.lmask: List[float] = []
        self.sampled = 0
        self.forced = 0
        self.status = "active"            # active|calling|done
        self.forced_q: List[int] = []
        self.finish_reason = ""
        self.key = key                    # [2] uint32 base key
        self.submit_index = submit_index
        self.meta = meta or {}
        self.submitted_at = submitted_at
        self.started_at = 0.0
        self.replays = 0              # times preempted and re-queued
        self.tool_retries = 0         # transient tool-error retries spent
                                      # (per-episode retry cap accounting)
        self.session = None           # per-episode ToolSession (lazy; kept
                                      # across park/preempt/replay)
        self.turns = 0                # tool calls dispatched this episode
        self.snap = None              # host KVSnapshot while parked/queued
                                      # (paged engine, resume_restore mode);
                                      # None -> the row replays from tokens
        self.dev_pages = None         # KV pages kept IN-POOL while parked
                                      # (prefix cache: zero-copy park; the
                                      # row owns one refcount per page)
        self.dev_pos = 0              # cache entries those pages hold
        self.resumed_at = None        # arrival of the tool response this
                                      # row waits to resume with

    def turn_limit(self) -> int:
        """Effective tool-turn budget (0 = unlimited)."""
        if self.req.max_turns is not None:
            return self.req.max_turns
        return getattr(self.req.env, "max_turns", 0)

    def ensure_session(self):
        if self.session is None:
            self.session = self.req.env.open_session(self.req.truth)
        return self.session

    def accept(self, token: int, lp: float, mask: float, max_total: int) -> str:
        """Record one token; returns "continue" | "done" | "call".

        Only sampled tokens (mask==1) are charged to max_new_tokens; the
        length cap is the KV-cache capacity, not the sampling budget. A
        CALL sampled with the turn budget spent ends the episode instead
        of dispatching (finish_reason "turn_limit").
        """
        self.gen.append(token)
        self.lps.append(lp)
        self.lmask.append(mask)
        if mask == 1.0:
            self.sampled += 1
        else:
            self.forced += 1
        if token == tok.EOS:
            self.status, self.finish_reason = "done", "eos"
            return "done"
        if self.prompt_len + len(self.gen) >= max_total:
            self.status, self.finish_reason = "done", "capacity"
            return "done"
        if token == tok.CALL and self.req.env.is_agentic and mask == 1.0:
            limit = self.turn_limit()
            if limit and self.turns >= limit:
                self.status, self.finish_reason = "done", "turn_limit"
                return "done"
            self.turns += 1
            self.status = "calling"
            return "call"
        if self.sampled >= self.req.max_new_tokens and not self.forced_q:
            self.status, self.finish_reason = "done", "budget"
            return "done"
        return "continue"

    def result(self, prompt_tokens) -> Dict:
        return {
            "task_id": self.req.task_id,
            "prompt_len": self.prompt_len,
            "tokens": list(prompt_tokens) + self.gen,
            "gen_logprobs": self.lps,
            "gen_loss_mask": self.lmask,
            "truth": self.req.truth,
            "env": self.req.env,
            "finish_reason": self.finish_reason,
        }


def _submit_tool_call(row: "_Row", prompt_tokens, pool, rng,
                      sim_latency: bool) -> Tuple[Future, CancelToken]:
    """Dispatch a row's agentic tool call on the shared pool (freeze-in-slot
    path of both engines): sample the env-interaction latency, then run the
    episode's stateful session call while the rest of the batch decodes.

    Returns (future, cancel token). Cancelling the token makes an
    already-RUNNING call return early — ``Future.cancel()`` alone only
    helps before the pool picks the job up; the token interrupts the
    latency sleep and is passed into ``ToolSession.call`` for cooperative
    mid-call checks, so a timed-out/evicted call frees its pool thread
    immediately instead of running to completion discarded."""
    query = list(prompt_tokens) + row.gen
    latency = row.req.env.sample_env_latency(
        _RandomShim(rng)) if not sim_latency else 0.0
    session = row.ensure_session()
    token = CancelToken()

    def run_tool(q=query, sess=session, lat=latency):
        if lat > 0 and token.wait(lat):
            return []                    # cancelled during the latency sleep
        if token.cancelled:
            return []
        return call_session(sess, q, token)

    return pool.submit(run_tool), token


class RolloutEngine:
    """Round-fused baseline: one fixed batch, barrier until the last row."""

    def __init__(self, cfg: ModelConfig, base_params, *, max_len: int = 128,
                 use_kernel: bool = False, seed: int = 0):
        self.cfg = cfg
        self.base_params = base_params
        self.max_len = max_len
        self.use_kernel = use_kernel
        self._master = jax.random.PRNGKey(seed)
        self._n_issued = 0        # cumulative rows served (key freshness
                                  # across rounds; mirrors the continuous
                                  # engine's submission counter)
        self._step_fn = None
        self._first_fn = None
        self._prefill_fn = None

    # -- jitted kernels --------------------------------------------------
    def _build(self, num_adapters: int):
        self._prefill_fn, self._first_fn, self._step_fn = _build_fns(
            self.cfg, self.use_kernel)

    def _row_keys(self, requests: Sequence[RolloutRequest]) -> np.ndarray:
        """Per-row base keys: explicit request.seed, else the engine-global
        issue counter — consecutive generate() rounds get fresh keys (and
        match a continuous engine fed the same requests in the same order)."""
        keys = [jax.random.fold_in(
                    self._master,
                    r.seed if r.seed is not None else self._n_issued + i)
                for i, r in enumerate(requests)]
        self._n_issued += len(requests)
        return np.stack([np.asarray(k, np.uint32) for k in keys])

    # -- main API ---------------------------------------------------------
    def generate(self, requests: Sequence[RolloutRequest], adapter_trees,
                 *, tool_executor: Optional[ThreadPoolExecutor] = None,
                 sim_latency: bool = False,
                 deadline_s: float = 120.0) -> Tuple[List[Dict], RolloutStats]:
        """Run a batch of cross-task requests to completion (one round).

        adapter_trees: list of per-task adapter trees; request.adapter_index
        selects. Returns per-request dicts (tokens/logprobs/loss_mask/...)
        and engine stats.
        """
        t_start = time.monotonic()
        cfg = self.cfg
        B = len(requests)
        if self._step_fn is None:
            self._build(len(adapter_trees))
        stacked = stack_adapters(adapter_trees)
        row_ids = jnp.asarray([r.adapter_index for r in requests], jnp.int32)
        temps = jnp.asarray([r.temperature for r in requests], jnp.float32)
        keys = jnp.asarray(self._row_keys(requests))

        prompt_lens = np.array([len(r.prompt) for r in requests], np.int32)
        S_p = _bucket_len(prompt_lens.max())
        tokens = np.zeros((B, S_p), np.int32)
        for i, r in enumerate(requests):
            tokens[i, :len(r.prompt)] = r.prompt

        cache = init_cache(cfg, B, self.max_len,
                           enc_len=8 if cfg.family == "encdec" else 0)
        stats = RolloutStats(prefill_tokens=int(prompt_lens.sum()),
                             prefills=B)
        t0 = time.monotonic()
        logits, cache = self._prefill_fn(self.base_params, stacked, row_ids,
                                         jnp.asarray(tokens),
                                         jnp.asarray(prompt_lens), cache)
        jax.block_until_ready(logits)
        stats.prefill_seconds += time.monotonic() - t0

        rows = [_Row(r, keys[i], i) for i, r in enumerate(requests)]
        pending: Dict[int, Future] = {}
        pending_t0: Dict[int, float] = {}
        pending_tok: Dict[int, CancelToken] = {}
        own_pool = tool_executor is None
        pool = tool_executor or ThreadPoolExecutor(max_workers=4)
        rng = np.random.RandomState(
            (int(np.asarray(self._master)[1]) + self._n_issued) % (2**31))

        # sample the first token from prefill logits (counter = 0 per row)
        counters = np.zeros((B,), np.int32)
        first, first_lp = self._first_fn(logits, keys, jnp.asarray(counters),
                                         temps)
        first = np.asarray(first)
        first_lp = np.asarray(first_lp)
        cur = np.zeros((B,), np.int32)
        for i in range(B):
            action = rows[i].accept(int(first[i]), float(first_lp[i]), 1.0,
                                    self.max_len)
            stats.tokens_generated += 1
            stats.sampled_tokens += 1
            if action == "call":
                self._dispatch_tool(i, rows[i], tokens[i], pending,
                                    pending_t0, pending_tok, pool, rng,
                                    sim_latency)
            cur[i] = int(first[i])

        # forced feeds are budget-exempt, so the step bound must cover
        # budget + worst-case tool-response lengths (one response per tool
        # turn; an unlimited turn budget gets a 4-turn allowance — the wall
        # deadline is the actual straggler guard, and rows it cuts short
        # are tagged "straggler" below).
        worst_turns = max(
            (r.max_turns if r.max_turns is not None
             else getattr(r.env, "max_turns", 0)) or 4
            for r in requests)
        max_steps = (max(r.max_new_tokens for r in requests)
                     + 96 * max(1, worst_turns))
        steps_done = 0
        wall_deadline = time.monotonic() + deadline_s
        while steps_done < max_steps and time.monotonic() < wall_deadline:
            if all(r.status == "done" for r in rows):
                break
            # resolve finished tool calls
            for i in list(pending):
                if pending[i].done():
                    resp = pending[i].result()
                    stats.add_env_wait(rows[i].req.task_id,
                                       time.monotonic() - pending_t0[i])
                    rows[i].forced_q = [tok.RESP] + list(resp) + [tok.ENDRESP]
                    rows[i].status = "active"
                    del pending[i], pending_t0[i], pending_tok[i]
            advance = np.array([1 if rows[i].status == "active" else 0
                                for i in range(B)], np.int32)
            if advance.sum() == 0:
                # waiting only on external tools — does not consume the
                # decode-step budget (straggler guard is the wall deadline)
                time.sleep(0.001)
                continue
            steps_done += 1
            forced = np.zeros((B,), np.int32)
            fmask = np.zeros((B,), np.int32)
            for i in range(B):
                if rows[i].status == "active" and rows[i].forced_q:
                    forced[i] = rows[i].forced_q[0]
                    fmask[i] = 1
                counters[i] = len(rows[i].gen)
            t0 = time.monotonic()
            nxt, lp, cache = self._step_fn(
                self.base_params, stacked, row_ids, jnp.asarray(cur), cache,
                keys, jnp.asarray(counters), temps, jnp.asarray(forced),
                jnp.asarray(fmask), jnp.asarray(advance))
            nxt = np.asarray(nxt)
            lp = np.asarray(lp)
            stats.decode_seconds += time.monotonic() - t0
            stats.decode_steps += 1
            for i in range(B):
                if rows[i].status != "active" or advance[i] == 0:
                    continue
                was_forced = fmask[i] == 1
                if was_forced:
                    rows[i].forced_q.pop(0)
                action = rows[i].accept(int(nxt[i]), float(lp[i]),
                                        0.0 if was_forced else 1.0,
                                        self.max_len)
                if action == "call":
                    self._dispatch_tool(i, rows[i], tokens[i], pending,
                                        pending_t0, pending_tok, pool, rng,
                                        sim_latency)
                cur[i] = int(nxt[i])
                stats.tokens_generated += 1
                if not was_forced:
                    stats.sampled_tokens += 1

        # timed-out tool calls: cancel the Future (drops jobs still queued
        # on the SHARED pool) AND the cooperative token (makes an
        # already-executing call return early instead of running to
        # completion discarded — satellite, ISSUE 5)
        for i in pending:
            pending[i].cancel()
            pending_tok[i].cancel()
            rows[i].status = "done"
            rows[i].finish_reason = rows[i].finish_reason or "tool_timeout"
        for row in rows:
            # rows the step bound / wall deadline cut short return partial
            # (graded reward on what exists) with an explicit reason
            if row.status != "done":
                row.status = "done"
                row.finish_reason = row.finish_reason or "straggler"
        if own_pool:
            pool.shutdown(wait=False)

        results = [rows[i].result(tokens[i, :prompt_lens[i]])
                   for i in range(B)]
        stats.wall_seconds = time.monotonic() - t_start
        return results, stats

    # ------------------------------------------------------------------
    def _dispatch_tool(self, i, row: _Row, token_row, pending, pending_t0,
                       pending_tok, pool, rng, sim_latency):
        pending[i], pending_tok[i] = _submit_tool_call(
            row, token_row[:row.prompt_len], pool, rng, sim_latency)
        pending_t0[i] = time.monotonic()


class ContinuousRolloutEngine:
    """Persistent slot-pool engine: decode never drains between tenants.

    Usage: ``set_adapters(slot, tree)`` to (re)install a tenant's LoRA in
    the fixed-capacity stacked buffer, ``submit(request)`` any number of
    requests (request.adapter_index names the adapter slot), then call
    ``step()`` from the scheduler loop — or ``drain()`` to run to empty.
    Finished rows stream out of ``drain_completions()`` the moment they
    evict.

    The request queue pops in ``scheduler`` order ("srpt": priority tiers,
    then shortest predicted remaining budget via a per-tenant EMA length
    predictor, with a ``starvation_k``-refill progress bound; "fifo":
    PR-1 arrival order). ``preempt_tenant``/``preempt_slots`` implement the
    admission-driven preemption protocol documented in the module
    docstring; preempted rows replay token-for-token — under
    ``disagg_prefill=True`` the replay prefill runs asynchronously on the
    prefill workers and splices back with the row's original per-row
    counter, so replay parity is preserved across both fill paths.

    ``env_stage=True`` activates the disaggregated environment-interaction
    stage (rollout/env_stage.py, ``env_workers`` threads,
    ``env_inflight_per_tenant`` fairness cap): rows that sample CALL are
    parked instead of freezing in their slot, and resume through the
    prefill path once their tool response lands — works with either fill
    path, preserving token-for-token parity with the freeze-in-slot
    baseline.
    """

    def __init__(self, cfg: ModelConfig, base_params, *, max_slots: int = 8,
                 max_adapters: int = 8, max_len: int = 128,
                 use_kernel: bool = False, seed: int = 0,
                 tool_executor: Optional[ThreadPoolExecutor] = None,
                 sim_latency: bool = False, tool_timeout_s: float = 60.0,
                 scheduler: str = "srpt", starvation_k: int = 8,
                 predictor: Optional[LengthPredictor] = None,
                 disagg_prefill: bool = False, prefill_chunk: int = 0,
                 prefill_workers: int = 1, env_stage: bool = False,
                 env_workers: int = 2, env_inflight_per_tenant: int = 0,
                 paged_kv: bool = False, kv_page_size: int = 16,
                 kv_pool_pages: int = 0, resume_restore: bool = True,
                 snapshot_budget_bytes: int = 0, prefix_cache: bool = True,
                 on_stage=None, tracer=None, chaos=None,
                 tool_retry_max: int = 3, tool_retry_base_s: float = 0.05,
                 tool_retry_max_s: float = 2.0,
                 tool_retry_episode_cap: int = 0,
                 supervise_wedge_s: float = 0.0):
        self.cfg = cfg
        self.base_params = base_params
        self.max_slots = max_slots
        self.max_adapters = max_adapters
        self.max_len = max_len
        self.use_kernel = use_kernel
        self.tool_timeout_s = tool_timeout_s
        # -- paged KV-cache block pool (ISSUE 5) ---------------------------
        self.paged_kv = paged_kv
        self.kv_page_size = kv_page_size
        self.resume_restore = paged_kv and resume_restore
        if paged_kv:
            if cfg.family == "encdec":
                raise ValueError("paged_kv unsupported for encdec")
            if max_len % kv_page_size != 0:
                raise ValueError(f"max_len {max_len} must be a multiple of "
                                 f"kv_page_size {kv_page_size}")
            self._max_pg = max_len // kv_page_size
            # default pool: dense-equivalent capacity (every slot could run
            # to max_len); size it DOWN to realize the HBM saving, at the
            # cost of cache-capacity evictions if every row runs long
            self.kv_pool_pages = kv_pool_pages or max_slots * self._max_pg
            self._pages = PagePool(self.kv_pool_pages, kv_page_size)
            self._slot_pages: List[List[int]] = [[] for _ in range(max_slots)]
            self._slot_pos = [0] * max_slots      # device cache["pos"] mirror
            self._tbl_host = np.full((max_slots, self._max_pg),
                                     self._pages.sentinel, np.int32)
            self._tbl_dirty = False
            self._snap_store = SnapshotStore(snapshot_budget_bytes)
        else:
            self.kv_pool_pages = 0
            self._pages = None
            self._snap_store = None
        # -- global COW prefix cache (ISSUE 8) -----------------------------
        # three sharing levels over the page pool: GRPO-group prompt pages
        # (siblings radix-hit the representative's pages), device-resident
        # park/preempt (pages stay in-pool; host snapshot demoted to a
        # spill tier), and cross-request radix reuse of common prefixes.
        # prefix_cache=False reproduces the PR-5 private-pages engine.
        self.prefix_cache = bool(paged_kv and prefix_cache)
        self._prefix_idx = (PrefixIndex(kv_page_size)
                            if self.prefix_cache else None)
        self._dev_parked: List[_Row] = []   # rows whose dev_pages are live
                                            # (engine-thread-only registry:
                                            # spill victims + invariants)
        self._cow_fn = None
        self._suffix_fn = None
        self._snap_fn = None
        self._restore_fn = None
        self.sim_latency = sim_latency
        self.disagg_prefill = disagg_prefill
        self.prefill_workers = max(1, prefill_workers)
        self.env_stage = env_stage
        self._chaos = chaos          # ChaosInjector or None (fault drills)
        self._env: Optional[EnvStage] = EnvStage(
            max(1, env_workers),
            max_inflight_per_tenant=env_inflight_per_tenant,
            sim_latency=sim_latency, retry_max=tool_retry_max,
            retry_episode_cap=tool_retry_episode_cap,
            retry_base_s=tool_retry_base_s, retry_max_s=tool_retry_max_s,
            seed=seed, chaos=chaos) if env_stage else None
        self._prefill_chunk_eff = effective_chunk(cfg, prefill_chunk)
        self.on_stage = on_stage    # optional (phase, task_id, t0, t1) hook
                                    # (called from worker threads too)
        # episode tracer (repro.obs): None by default — every hook site
        # below guards on it, so an untraced run pays one pointer compare
        # per episode EVENT (install/park/evict), never per token
        self._tracer = tracer
        self._slot_tr_t0 = [0.0] * max_slots    # residency span starts
        self._slot_tr_flow = [0] * max_slots    # incoming hand-off arrows
        self._t_read: Optional[float] = None    # token read of the last
                                                # step, while steps decode
        self._master = jax.random.PRNGKey(seed)
        self._rng = np.random.RandomState(seed + 7919)
        self._own_pool = tool_executor is None
        self._pool = tool_executor or ThreadPoolExecutor(max_workers=4)

        self._step_fn = None
        self._refill_fn = None
        self._write_adapter_fn = None
        self._stacked = None                     # [L, T, ...] LoRA buffer
        self._cache = None                       # batch = max_slots

        N = max_slots
        self._rows: List[Optional[_Row]] = [None] * N
        self._prompts: List[Optional[List[int]]] = [None] * N
        # device-resident row state (updated inside the jitted calls; the
        # host only uploads the advance/forced masks, and only when they
        # change — see _masks())
        self._d_cur = None          # [N] int32   current token per lane
        self._d_counters = None     # [N] int32   == len(gen) per lane
        self._d_keys = None         # [N,2] uint32 per-row base PRNG keys
        self._d_temps = None        # [N] float32
        self._d_row_ids = None      # [N] int32   adapter slot per lane
        self._mask_sig = None       # last uploaded (advance,forced,fmask)
        self._d_masks = None
        self._pending: Dict[int, Future] = {}
        self._pending_t0: Dict[int, float] = {}
        self._pending_tok: Dict[int, CancelToken] = {}
        self.predictor = predictor or LengthPredictor()
        self._sched = SlotScheduler(policy=scheduler,
                                    predictor=self.predictor,
                                    starvation_k=starvation_k)
        self._completed: Deque[RolloutCompletion] = deque()
        self._n_submitted = 0
        self.stats = RolloutStats()
        # -- disaggregated prefill stage (workers <-> decode thread) -------
        self._stage_lock = threading.Lock()   # guards: _sched/_ready/
                                              # _stage_inflight
        self._ready: Deque[ReadyRow] = deque()
        self._stage_inflight: List[_Row] = []  # popped by a worker, not yet
                                               # ready (host refs only)
        self._stage_stop = threading.Event()
        self._stage_error: Optional[BaseException] = None
        self._workers: List[PrefillWorker] = []
        self._next_pwid = 0     # unique prefill-worker ids across respawns
        self._pkernels: Optional[PrefillKernels] = None
        self._splice_fn = None
        # -- stage supervision (ISSUE 10) ----------------------------------
        # dead/wedged workers are detected on the step() tick, their
        # stranded work recovered, and the pool restarted to complement
        # under bounded exponential backoff; past the restart budget the
        # supervisor raises on the engine thread (-> runtime.error ->
        # checkpoint-restart)
        self.supervise_wedge_s = supervise_wedge_s   # 0 = liveness only
        self.supervisor = StageSupervisor(tracer=tracer)
        if env_stage:
            self.supervisor.register(
                "env_worker", healthy=self._env_stage_healthy,
                recover=self._env.recover_dead,
                restart=self._env._ensure_workers)
        if disagg_prefill:
            self.supervisor.register(
                "prefill_worker", healthy=self._prefill_stage_healthy,
                recover=self._recover_prefill_claims,
                restart=self._ensure_stage)

    # -- build ----------------------------------------------------------
    def _ensure_built(self):
        if self._step_fn is None:
            self._step_fn = _build_cont_step_fn(self.cfg, self.use_kernel)
            if self.paged_kv:
                self._refill_fn = _build_refill_fn_paged(
                    self.cfg, self.use_kernel, self.max_len,
                    self.kv_page_size)
                self._snap_fn = _build_snap_fn(self.cfg)
                self._restore_fn = _build_restore_fn(self.cfg)
                if self.prefix_cache:
                    self._cow_fn = _build_cow_fn(self.cfg)
                    self._suffix_fn = _build_suffix_fn(
                        self.cfg, self.use_kernel, self.max_len,
                        self.kv_page_size)
            else:
                self._refill_fn = _build_refill_fn(self.cfg, self.use_kernel,
                                                   self.max_len)
            # disaggregated mode: the write must NOT donate the old buffer —
            # a prefill worker's in-flight call may still be reading it (the
            # old immutable tree stays valid until its last reader drops it)
            self._write_adapter_fn = jax.jit(
                lambda buf, tree, i: jax.tree.map(
                    lambda b, l: b.at[:, i].set(l), buf, tree),
                donate_argnums=() if self.disagg_prefill else (0,))
            if self.disagg_prefill:
                self._splice_fn = (_build_splice_fn_paged(self.cfg,
                                                          self.kv_page_size)
                                   if self.paged_kv else
                                   _build_splice_fn(self.cfg))
                self._pkernels = PrefillKernels(self.cfg, self.use_kernel,
                                                self.max_len)
        if self._cache is None:
            N = self.max_slots
            if self.paged_kv:
                self._cache = init_paged_cache(
                    self.cfg, N, pool_pages=self.kv_pool_pages,
                    page_size=self.kv_page_size,
                    max_pages_per_row=self._max_pg)
            else:
                self._cache = init_cache(
                    self.cfg, N, self.max_len,
                    enc_len=8 if self.cfg.family == "encdec" else 0)
            self._d_cur = jnp.zeros((N,), jnp.int32)
            self._d_counters = jnp.zeros((N,), jnp.int32)
            self._d_keys = jnp.zeros((N, 2), jnp.uint32)
            self._d_temps = jnp.ones((N,), jnp.float32)
            self._d_row_ids = jnp.zeros((N,), jnp.int32)

    # -- adapters --------------------------------------------------------
    def set_adapters(self, index: int, tree):
        """Install/replace the LoRA tree at adapter slot `index` in the
        fixed-capacity stacked buffer (shape-stable: no recompiles)."""
        if not 0 <= index < self.max_adapters:
            raise ValueError(f"adapter slot {index} out of range "
                             f"[0, {self.max_adapters})")
        self._ensure_built()
        if self._stacked is None:
            self._stacked = init_stacked_buffer(tree, self.max_adapters)
        self._stacked = self._write_adapter_fn(self._stacked, tree,
                                               jnp.int32(index))
        if self._prefix_idx is not None:
            # cached K/V was produced under the OLD adapter weights — a
            # match against it would be silently wrong for the new ones
            stale = self._prefix_idx.invalidate(index)
            if stale:
                self._pages.release(stale)

    # -- submission ------------------------------------------------------
    def submit(self, req: RolloutRequest, meta=None):
        if len(req.prompt) + 1 >= self.max_len:
            raise ValueError("prompt does not fit decode cache")
        key = np.asarray(jax.random.fold_in(
            self._master,
            req.seed if req.seed is not None else self._n_submitted),
            np.uint32)
        row = _Row(req, key, self._n_submitted, meta=meta,
                   submitted_at=time.monotonic())
        self._n_submitted += 1
        if self._tracer is not None:
            # the episode's trace id rides row.meta — the one piece of
            # host state that provably survives park, preemption and
            # snapshot/replay resume (it already carries the behaviour
            # version for the same reason)
            if not isinstance(row.meta, dict):
                row.meta = {}           # engine-direct callers pass no meta
            trace = row.meta.get("trace_id")
            if trace is None:
                trace = self._tracer.new_trace(req.task_id)
                row.meta["trace_id"] = trace
            self._tracer.mark(trace, "queued", row.submitted_at)
        with self._stage_lock:
            self._sched.push(row, self.stats.refills)
        return row.submit_index

    # -- wait counters ------------------------------------------------------
    def _book_queue_wait(self, pushed_at: float):  # held: _stage_lock
        """A row popped off the scheduler queue goes to an install path:
        book the time since its push (prefill workers book here too, hence
        the lock)."""
        self.stats.queue_wait_seconds += max(0.0, time.monotonic()
                                             - pushed_at)
        self.stats.queue_pops += 1

    # -- episode tracing helpers (no-ops when tracer is None) -------------
    def _trace_of(self, row: _Row):
        m = row.meta
        return m.get("trace_id") if isinstance(m, dict) else None

    def _installed(self, slot: int, row: _Row, t_now: float,
                   t_pre: float = None, pre_state: str = None):
        """Row entered a decode slot: book a resumed row's wait since its
        tool response arrived; with a tracer, open its residency span,
        consume any pending hand-off arrow (env resume / preempt
        reinstall), and mark the lifecycle transition(s)."""
        if row.resumed_at is not None:
            self.stats.resume_wait_seconds += max(0.0,
                                                  t_now - row.resumed_at)
            self.stats.resumes_installed += 1
            row.resumed_at = None
        tr = self._tracer
        if tr is None:
            return
        trace = self._trace_of(row)
        if t_pre is not None and pre_state is not None:
            tr.mark(trace, pre_state, t_pre)
        tr.mark(trace, "decode", t_now)
        self._slot_tr_t0[slot] = t_now
        m = row.meta
        self._slot_tr_flow[slot] = (m.pop("_flow_in", 0)
                                    if isinstance(m, dict) else 0)

    def _tr_vacate(self, slot: int, row: _Row, t_now: float,
                   flow_out: int = 0):
        """Row left its slot (evict/park/preempt): emit the residency span
        on the slot's track, with flow arrows binding it to the hand-off
        source/destination across threads."""
        tr = self._tracer
        if tr is None:
            return
        tr.span(("rollout", f"slot-{slot}"), row.req.task_id,
                self._slot_tr_t0[slot], t_now, trace=self._trace_of(row),
                flow_in=self._slot_tr_flow[slot], flow_out=flow_out)
        self._slot_tr_flow[slot] = 0

    # -- prefill stage lifecycle ------------------------------------------
    def _ensure_stage(self):
        """Spawn the async prefill workers — the full complement after a
        halt, or just replacements for workers that died on an error
        (survivors keep running; total parallelism stays at
        `prefill_workers`). A no-op until the first adapter install —
        workers have nothing to prefill against before then (requests may
        already be queued; they keep until the buffer exists)."""
        if not self.disagg_prefill or self._stacked is None:
            return
        self._ensure_built()
        alive = [w for w in self._workers if w.is_alive()]
        if len(alive) >= self.prefill_workers:
            return
        self._stage_stop.clear()
        fresh = []
        for _ in range(self.prefill_workers - len(alive)):
            # unique ids across respawns: a replacement must not shadow a
            # dead worker's claimed-row ownership (supervisor recovery)
            fresh.append(PrefillWorker(self, self._next_pwid))
            self._next_pwid += 1
        self._workers = alive + fresh
        for w in fresh:
            w.start()

    def _halt_stage(self):
        """Stop the prefill workers; their unfinished rows return to the
        queue (worker teardown pushes them back under the stage lock)."""
        self._stage_stop.set()
        for w in self._workers:
            w.join(timeout=30)
        self._recover_prefill_claims()   # chaos-killed workers strand rows
        self._workers = []

    # -- stage supervision callables (engine thread only) -----------------
    def _env_stage_healthy(self) -> bool:
        if self.supervise_wedge_s > 0:
            self._env.mark_wedged(self.supervise_wedge_s)
        return self._env.healthy()

    def _prefill_stage_healthy(self) -> bool:
        if self._stacked is None or not self._workers:
            return True          # stage not started (or halted): nothing
                                 # to supervise — step() does first start
        if self._stage_error is not None:
            return True          # a REAL worker error is about to raise on
                                 # the engine thread (fatal) — restarting
                                 # first would just mask the cause
        alive = [w for w in self._workers if w.is_alive()]
        return len(alive) >= self.prefill_workers

    def _recover_prefill_claims(self) -> int:
        """Requeue rows a dead prefill worker stranded mid-prefill: still
        in ``_stage_inflight`` (so not aborted) but never emitted and with
        no live owner — they re-enter the scheduler queue and prefill
        again from scratch (prefill is deterministic; the re-run is
        token-identical)."""
        n = 0
        with self._stage_lock:
            for w in self._workers:
                if w.is_alive():
                    continue
                for row in list(w.claimed):
                    w.claimed.remove(row)
                    if row in self._stage_inflight:
                        self._stage_inflight.remove(row)
                        self._sched.push(row, self.stats.refills)
                        n += 1
        return n

    def _raise_stage_error(self):
        if self._stage_error is not None:
            err, self._stage_error = self._stage_error, None
            raise err

    # -- introspection ---------------------------------------------------
    def occupancy(self) -> Tuple[int, int]:
        return sum(r is not None for r in self._rows), self.max_slots

    def occupant_tasks(self) -> frozenset:
        return frozenset(r.req.task_id for r in self._rows if r is not None)

    def queued(self) -> int:
        with self._stage_lock:
            n = (len(self._sched) + len(self._stage_inflight)
                 + len(self._ready))
        if self._env is not None:
            n += self._env.count()      # parked rows hold no slot but are
                                        # still in flight (env stage)
        return n

    def queue_depths(self) -> Tuple[int, int]:
        """(prefill queue + in-prefill, ready-to-splice) — the two stage
        queues of the disaggregated layout (Fig 5)."""
        with self._stage_lock:
            return (len(self._sched) + len(self._stage_inflight),
                    len(self._ready))

    def env_depths(self) -> Tuple[int, int]:
        """(queued, executing) depths of the env-interaction stage."""
        return self._env.depths() if self._env is not None else (0, 0)

    def idle(self) -> bool:
        return self.queued() == 0 and all(r is None for r in self._rows)

    def active_tenants(self) -> frozenset:
        """Tenants with rows resident in slots OR anywhere in the pipeline
        (queued, mid-prefill, ready-to-splice, parked in the env stage,
        incl. preempted rows awaiting replay) — i.e. whose adapter slot
        must stay resident."""
        with self._stage_lock:
            stage = (frozenset(r.req.task_id for r in self._stage_inflight)
                     | frozenset(rr.row.req.task_id for rr in self._ready)
                     | self._sched.tenants())
        if self._env is not None:
            stage = stage | self._env.tenants()
        return self.occupant_tasks() | stage

    def queued_progress(self, task_id: str) -> Tuple[int, float]:
        """(row count, mean sampled tokens) over a tenant's not-yet-resident
        rows (queued / mid-prefill / ready / parked). Preempted rows carry
        their generated prefix, so this feeds the admission controller's
        remaining-budget re-estimate (readmission packs tighter)."""
        with self._stage_lock:
            rows = self._sched.rows_for(task_id)
            rows += [r for r in self._stage_inflight
                     if r.req.task_id == task_id]
            rows += [rr.row for rr in self._ready
                     if rr.row.req.task_id == task_id]
        if self._env is not None:
            rows += self._env.rows_for(task_id)
        if not rows:
            return 0, 0.0
        return len(rows), float(sum(r.sampled for r in rows)) / len(rows)

    def drain_completions(self) -> List[RolloutCompletion]:
        out = []
        while self._completed:
            out.append(self._completed.popleft())
        return out

    # -- slot lifecycle --------------------------------------------------
    def _completion(self, row: _Row, prompt, slot: int) -> RolloutCompletion:
        """One finished episode → completion record (shared by slot
        eviction, parked-row timeout, and the drain abort paths). The
        behaviour version is stamped per-row from the submit meta — which
        lives on the row object itself, so the stamp survives park,
        preemption, and snapshot/replay resume."""
        meta = row.meta if isinstance(row.meta, dict) else {}
        finished_at = time.monotonic()
        if self._tracer is not None:
            self._tracer.mark(self._trace_of(row), "completed", finished_at)
        return RolloutCompletion(
            task_id=row.req.task_id, prompt_len=row.prompt_len,
            tokens=list(prompt) + row.gen, gen_logprobs=row.lps,
            gen_loss_mask=row.lmask, truth=row.req.truth, env=row.req.env,
            finish_reason=row.finish_reason, slot=slot,
            version=int(meta.get("version", -1)),
            sampled_tokens=row.sampled, forced_tokens=row.forced,
            submit_index=row.submit_index, submitted_at=row.submitted_at,
            started_at=row.started_at, finished_at=finished_at,
            finished_step=self.stats.decode_steps, meta=row.meta)

    def _evict(self, slot: int):
        row = self._rows[slot]
        if self._tracer is not None:
            self._tr_vacate(slot, row, time.monotonic())
        self._completed.append(self._completion(row, self._prompts[slot],
                                                slot))
        self.stats.completions += 1
        if row.finish_reason in ("eos", "budget", "capacity", "turn_limit"):
            # natural finishes only: a tool_timeout/aborted row's partial
            # sampled count would bias the tenant's length EMA low
            self.predictor.observe(row.req.task_id, row.sampled)
        self._rows[slot] = None
        self._prompts[slot] = None
        if self.paged_kv:
            self._free_slot_pages(slot)
        # cancel, don't just drop, a pending tool Future: abandoned
        # env.tool_call work left queued would keep burning the shared
        # thread-pool and starve other tenants' tool calls — and a late
        # response must never reach the slot's next occupant. The token
        # additionally makes an already-RUNNING call return early, freeing
        # its pool thread immediately (cooperative cancellation).
        fut = self._pending.pop(slot, None)
        if fut is not None:
            fut.cancel()
        tok_ = self._pending_tok.pop(slot, None)
        if tok_ is not None:
            tok_.cancel()
        self._pending_t0.pop(slot, None)

    def _complete_parked(self, row: _Row):
        """Finish an episode that holds NO slot (parked in the env stage:
        tool timeout or abort)."""
        self._drop_snap(row)          # a dead row's snapshot frees its arena
        self._release_dev(row)        # ... and its in-pool parked pages
        self._completed.append(self._completion(row, row.req.prompt, -1))
        self.stats.completions += 1

    # -- paged KV page + snapshot lifecycle (rollout/kvcache.py) ----------
    def _row_pages_needed(self, tokens: int) -> int:
        """Pool pages holding `tokens` cache entries for one row (0 for
        pure-SSM models: recurrent state is fixed-size and never paged)."""
        if self.cfg.family == "ssm":
            return 0
        return pages_for(tokens, self.kv_page_size)

    def _assign_slot_pages(self, slot: int, pages: List[int], pos: int):
        """Install a slot's host-side page list + block-table mirror."""
        self._slot_pages[slot] = list(pages)
        self._slot_pos[slot] = pos
        self._tbl_host[slot, :] = self._pages.sentinel
        self._tbl_host[slot, :len(pages)] = pages
        self._tbl_dirty = True

    def _free_slot_pages(self, slot: int):
        """Vacating a slot returns its pages to the pool and neutralizes
        its block-table row — stale entries would let the empty lane's
        (garbage) decode writes corrupt pages re-allocated to other rows."""
        if self._slot_pages[slot]:
            self._pages.release(self._slot_pages[slot])
        self._slot_pages[slot] = []
        self._tbl_host[slot, :] = self._pages.sentinel
        self._tbl_dirty = True

    def _padded_pages(self, pages: List[int]) -> np.ndarray:
        out = np.full((self._max_pg,), self._pages.sentinel, np.int32)
        out[:len(pages)] = pages
        return out

    def _snapshot_row(self, slot: int, row: _Row):
        """Copy a row's cache state to HOST before vacating its slot (park
        or preemption): only the ``ceil(pos/page)`` live pages plus the
        fixed SSM/conv rows — never the max_len worst case. Under host
        memory pressure the snapshot is dropped and the row replays from
        tokens instead (identical output, recomputed)."""
        if not self.resume_restore:
            return
        if self._chaos is not None and self._chaos.fire("snapshot_drop"):
            # simulated host-memory pressure: the row falls back to token
            # replay — identical output, recomputed prefix
            row.snap = None
            self.stats.snapshot_drops += 1
            return
        pos = self._slot_pos[slot]
        n_pg = self._row_pages_needed(pos)
        # the slot may hold one extra pre-allocated page for the pending
        # write (pos % page == 0); it contains no valid entries — skip it
        outs = self._snap_fn(self._cache,
                             jnp.asarray(self._padded_pages(
                                 self._slot_pages[slot][:n_pg])),
                             jnp.int32(slot))
        # device-side slice BEFORE the host transfer: the jitted gather is
        # shape-stable at _max_pg pages, but only the n_pg live ones cross
        # the host boundary — the snapshot copy is O(live), not O(max_len)
        snap = KVSnapshot(
            pos=pos, cur=row.gen[-1],
            kpages=(np.asarray(outs["kp"][:, :n_pg])
                    if "kp" in outs else None),
            vpages=(np.asarray(outs["vp"][:, :n_pg])
                    if "vp" in outs else None),
            ssm=np.asarray(outs["ssm"]).copy() if "ssm" in outs else None,
            conv=np.asarray(outs["conv"]).copy() if "conv" in outs else None)
        if self._snap_store.try_add(snap):
            row.snap = snap
            self.stats.snapshots += 1
        else:
            row.snap = None
            self.stats.snapshot_drops += 1

    def _drop_snap(self, row: _Row):
        if getattr(row, "snap", None) is not None:
            self._snap_store.remove(row.snap)
            row.snap = None

    def _release_dev(self, row: _Row):
        """Drop a row's device-resident parked pages (death paths: abort,
        timeout, capacity finish) — the counterpart of ``_drop_snap`` for
        the in-pool tier."""
        if getattr(row, "dev_pages", None) is not None:
            self._pages.release(row.dev_pages)
            row.dev_pages, row.dev_pos = None, 0
            if row in self._dev_parked:
                self._dev_parked.remove(row)

    # -- prefix cache: allocation relief + device-resident parking ---------
    def _alloc_pages(self, n: int, *, spill: bool = True
                     ) -> Optional[List[int]]:
        """Pool allocation with prefix-cache pressure relief: on failure,
        evict cold radix entries (LRU leaves), then spill the oldest
        device-parked row's pages to the host snapshot tier, then retry.
        ``spill=False`` keeps the call host-only (no device gather) — use
        it under ``_stage_lock``."""
        if n == 0:
            return []
        pages = self._pages.alloc(n)
        while pages is None:
            if self._prefix_idx is not None:
                dropped = self._prefix_idx.pop_lru(
                    max(1, n - self._pages.free_pages))
                if dropped:
                    self._pages.release(dropped)
                    pages = self._pages.alloc(n)
                    continue
            if not (spill and self._spill_dev_parked()):
                return None
            pages = self._pages.alloc(n)
        return pages

    def _spill_dev_parked(self) -> bool:
        """Spill tier: demote the oldest device-parked row to a host
        snapshot (gather its pages off-device, merge with its recurrent
        -state snapshot if any) so the pool pages free up. If the snapshot
        store rejects the bytes, the row falls back to token replay —
        either way its pages return to the pool. Returns True if a row was
        spilled."""
        if not self._dev_parked:
            return False
        row = self._dev_parked.pop(0)
        n_pg = len(row.dev_pages)
        outs = self._snap_fn(self._cache,
                             jnp.asarray(self._padded_pages(row.dev_pages)),
                             jnp.int32(0))
        old = row.snap            # hybrid park: recurrent-only snapshot
        snap = KVSnapshot(
            pos=row.dev_pos, cur=row.gen[-1],
            kpages=np.asarray(outs["kp"][:, :n_pg]),
            vpages=np.asarray(outs["vp"][:, :n_pg]),
            ssm=old.ssm if old is not None else None,
            conv=old.conv if old is not None else None)
        if old is not None:
            self._snap_store.remove(old)
            row.snap = None
        self._pages.release(row.dev_pages)
        row.dev_pages, row.dev_pos = None, 0
        if self._snap_store.try_add(snap):
            row.snap = snap
            self.stats.snapshots += 1
        else:
            self.stats.snapshot_drops += 1   # token-replay fallback
        return True

    def _dev_park_row(self, slot: int, row: _Row) -> bool:
        """Device-resident park/preempt (prefix cache): the row KEEPS its
        pool pages — ownership moves from the slot to the row, resume is a
        block-table splice, and ZERO bytes cross the host boundary for the
        attention family. Recurrent state (hybrid) has no paged
        representation and still snapshots to host; if the store rejects
        it the row falls back to token replay (pages released). Returns
        True when the slot was vacated (device-resident or replay)."""
        if not (self.prefix_cache and self.resume_restore):
            return False
        if self.cfg.family == "ssm" or not self._slot_pages[slot]:
            return False                 # no attention pages to keep
        pos = self._slot_pos[slot]
        n_pg = self._row_pages_needed(pos)
        pages = self._slot_pages[slot]
        if "ssm" in self._cache:         # hybrid: recurrent part to host
            outs = self._snap_fn(self._cache,
                                 jnp.asarray(self._padded_pages([])),
                                 jnp.int32(slot))
            snap = KVSnapshot(pos=pos, cur=row.gen[-1],
                              ssm=np.asarray(outs["ssm"]).copy(),
                              conv=np.asarray(outs["conv"]).copy())
            if not self._snap_store.try_add(snap):
                self.stats.snapshot_drops += 1
                self._free_slot_pages(slot)      # replay fallback
                return True
            row.snap = snap
            self.stats.snapshots += 1
        # the slot may hold one slack page pre-allocated for the pending
        # write (pos % page == 0): it has no valid entries — drop it
        if n_pg < len(pages):
            self._pages.release(pages[n_pg:])
        row.dev_pages = pages[:n_pg]
        row.dev_pos = pos
        self._dev_parked.append(row)
        # hand-off WITHOUT release: the row now owns the refcounts
        self._slot_pages[slot] = []
        self._tbl_host[slot, :] = self._pages.sentinel
        self._tbl_dirty = True
        return True

    def _park_or_snap(self, slot: int, row: _Row):
        """Vacate a slot preserving resume state: device-resident when the
        prefix cache is on (pure retain, no host round-trip), host
        snapshot otherwise; both fall back to token replay under memory
        pressure."""
        if self._dev_park_row(slot, row):
            return
        self._snapshot_row(slot, row)
        self._free_slot_pages(slot)

    def _finish_capacity(self, row: _Row):
        """Cache-capacity eviction: the page pool cannot serve this row
        even when otherwise idle, so the episode finishes with what it has
        instead of deadlocking the queue."""
        self._drop_snap(row)
        row.status, row.finish_reason = "done", "capacity"
        self.stats.pool_exhausted += 1
        self._complete_parked(row)

    def _restore_rows(self) -> bool:
        """Decode-thread install of snapshot-carrying queued rows (the
        resume path that kills O(prefix) replay): splice the saved KV
        pages into freshly allocated pool pages, the SSM/conv rows into
        the slot, and resume with the pending token — the next decode step
        produces the exact logits an uninterrupted run would have. No
        token is accepted at install (the pending one was accepted before
        the park/preempt), so bookkeeping differs from refill: only the
        device state moves."""
        if not self.resume_restore or self._cache is None:
            return False
        free = [s for s in range(self.max_slots) if self._rows[s] is None]
        did = False
        while free:
            with self._stage_lock:
                # pop_if, not pop(where=): a snapshot row restores only
                # when it is genuinely next in scheduler order — it must
                # not jump a higher-priority tenant's fresh rows (e.g. the
                # newcomer its own preemption just made room for)
                row = self._sched.pop_if(
                    self.stats.refills,
                    lambda r: r.snap is not None or r.dev_pages is not None)
                pushed_at = self._sched.last_pushed_at
                if row is not None and row.dev_pages is not None:
                    self._book_queue_wait(pushed_at)
            if row is None:
                break
            if row.dev_pages is not None:
                # device-resident resume: the pages never left the pool —
                # reattach them to the slot's block table and reset the
                # device row state. Zero KV bytes cross the host boundary
                # (the restore call's page writes land on the scratch
                # page); only the hybrid recurrent rows come back up.
                slot = free.pop(0)
                t0 = time.monotonic()
                kz = vz = jnp.zeros(
                    (self._cache["kp"].shape[0], self._max_pg,
                     self.kv_page_size, self.cfg.num_kv_heads,
                     self.cfg.head_dim), self._cache["kp"].dtype)
                zssm = self._cache.get("ssm")
                ssm_row = (jnp.asarray(row.snap.ssm)
                           if row.snap is not None and row.snap.ssm is not None
                           else (zssm[:, 0] if zssm is not None
                                 else jnp.zeros((1,))))
                zconv = self._cache.get("conv")
                conv_row = (jnp.asarray(row.snap.conv)
                            if row.snap is not None and row.snap.conv is not None
                            else (zconv[:, 0] if zconv is not None
                                  else jnp.zeros((1,))))
                self._cache, state = self._restore_fn(
                    self._cache, kz, vz,
                    jnp.asarray(self._padded_pages([])), jnp.int32(slot),
                    jnp.int32(row.dev_pos), ssm_row, conv_row,
                    jnp.int32(row.gen[-1]), jnp.int32(len(row.gen)),
                    jnp.asarray(row.key, jnp.uint32),
                    jnp.float32(row.req.temperature),
                    jnp.int32(row.req.adapter_index), self._d_cur,
                    self._d_counters, self._d_keys, self._d_temps,
                    self._d_row_ids)
                (self._d_cur, self._d_counters, self._d_keys,
                 self._d_temps, self._d_row_ids) = state
                self._mask_sig = None
                now = time.monotonic()
                self._rows[slot] = row
                self._prompts[slot] = list(row.req.prompt)
                self._installed(slot, row, now, t0, "restore")
                # ownership transfer back: slot adopts the row's refcounts
                self._assign_slot_pages(slot, row.dev_pages, row.dev_pos)
                self._dev_parked.remove(row)
                row.dev_pages, row.dev_pos = None, 0
                self._drop_snap(row)
                self.stats.restores += 1
                self.stats.device_resident_resumes += 1
                self.stats.replay_tokens_saved += (row.prompt_len
                                                   + len(row.gen))
                self.stats.splice_seconds += now - t0
                if self.on_stage is not None:
                    self.on_stage("splice", row.req.task_id, t0, now)
                did = True
                continue
            snap = row.snap
            pages = self._alloc_pages(snap.n_pages)
            if pages is None:
                if (self._pages.used_pages == 0
                        and snap.n_pages > self._pages.n_pages):
                    self._finish_capacity(row)      # can never fit
                    continue
                with self._stage_lock:              # pool pressure: retry
                    self._sched.push(row, self.stats.refills, pushed_at)
                break
            with self._stage_lock:
                self._book_queue_wait(pushed_at)
            slot = free.pop(0)
            t0 = time.monotonic()
            L_attn = 1 if snap.kpages is None else snap.kpages.shape[0]
            pad = self._max_pg - snap.n_pages
            kpages = vpages = jnp.zeros(
                (L_attn, self._max_pg, self.kv_page_size,
                 self.cfg.num_kv_heads, self.cfg.head_dim), jnp.float32)
            if snap.kpages is not None:
                kpages = jnp.asarray(np.pad(
                    snap.kpages, ((0, 0), (0, pad), (0, 0), (0, 0), (0, 0))))
                vpages = jnp.asarray(np.pad(
                    snap.vpages, ((0, 0), (0, pad), (0, 0), (0, 0), (0, 0))))
            zssm = self._cache.get("ssm")
            ssm_row = (jnp.asarray(snap.ssm) if snap.ssm is not None
                       else (zssm[:, 0] if zssm is not None else jnp.zeros((1,))))
            zconv = self._cache.get("conv")
            conv_row = (jnp.asarray(snap.conv) if snap.conv is not None
                        else (zconv[:, 0] if zconv is not None else jnp.zeros((1,))))
            self._cache, state = self._restore_fn(
                self._cache, kpages, vpages,
                jnp.asarray(self._padded_pages(pages)), jnp.int32(slot),
                jnp.int32(snap.pos), ssm_row, conv_row,
                jnp.int32(snap.cur), jnp.int32(len(row.gen)),
                jnp.asarray(row.key, jnp.uint32),
                jnp.float32(row.req.temperature),
                jnp.int32(row.req.adapter_index), self._d_cur,
                self._d_counters, self._d_keys, self._d_temps,
                self._d_row_ids)
            (self._d_cur, self._d_counters, self._d_keys, self._d_temps,
             self._d_row_ids) = state
            self._mask_sig = None
            now = time.monotonic()
            self._rows[slot] = row
            self._prompts[slot] = list(row.req.prompt)
            self._installed(slot, row, now, t0, "restore")
            self._assign_slot_pages(slot, pages, snap.pos)
            self._drop_snap(row)
            self.stats.restores += 1
            self.stats.replay_tokens_saved += row.prompt_len + len(row.gen)
            self.stats.splice_seconds += now - t0
            if self.on_stage is not None:
                self.on_stage("splice", row.req.task_id, t0, now)
            did = True
        if did:
            self.stats.refills += 1     # one refill event (starvation aging)
        return did

    def _ensure_decode_pages(self):
        """Pre-step growth: every resident ACTIVE row is about to write
        its K/V at cache position ``_slot_pos`` — allocate the covering
        page when the row crosses a page boundary. A row the pool cannot
        serve finishes via cache-capacity eviction (pool exhaustion is a
        scheduling condition, not a crash)."""
        for slot, r in enumerate(self._rows):
            if r is None or r.status != "active":
                continue
            if self.cfg.family == "ssm":
                continue
            need_idx = self._slot_pos[slot] // self.kv_page_size
            if need_idx >= self._max_pg:
                continue            # accept() finishes the row at max_len
            if need_idx < len(self._slot_pages[slot]):
                page = self._slot_pages[slot][need_idx]
                if (self.prefix_cache
                        and self._pages.refcount(page) > 1):
                    # copy-on-write fork: the row is about to decode-write
                    # into a SHARED page — privatize just this page (alloc
                    # + one-page copy); every earlier shared page stays
                    # shared. The last sibling standing sees rc==1 and
                    # writes in place.
                    pg = self._alloc_pages(1)
                    if pg is None:
                        r.status, r.finish_reason = "done", "capacity"
                        self.stats.pool_exhausted += 1
                        self._evict(slot)
                        continue
                    self._cache = self._cow_fn(self._cache, jnp.int32(page),
                                               jnp.int32(pg[0]))
                    self._pages.release([page])
                    self._slot_pages[slot][need_idx] = pg[0]
                    self._tbl_host[slot, need_idx] = pg[0]
                    self._tbl_dirty = True
                    self.stats.cow_forks += 1
                continue
            pg = (self._alloc_pages(1) if self.prefix_cache
                  else self._pages.alloc(1))
            if pg is None:
                r.status, r.finish_reason = "done", "capacity"
                self.stats.pool_exhausted += 1
                self._evict(slot)
                continue
            self._slot_pages[slot].extend(pg)
            self._tbl_host[slot, need_idx] = pg[0]
            self._tbl_dirty = True

    def page_stats(self) -> Dict[str, float]:
        """Pool occupancy/fragmentation gauges: used/total pages, the
        high-water mark, internal fragmentation (allocated page slack
        beyond the live cache entries), and the prefix-cache sharing
        gauges (shared pages, index-held pages, HBM bytes per resident
        row)."""
        if self._pages is None:
            return {}
        used = self._pages.used_pages
        cap_tokens = used * self.kv_page_size
        live = sum(min(self._slot_pos[s],
                       len(self._slot_pages[s]) * self.kv_page_size)
                   for s in range(self.max_slots)
                   if self._rows[s] is not None)
        frag = 1.0 - live / cap_tokens if cap_tokens else 0.0
        resident = sum(1 for r in self._rows if r is not None)
        resident += len(self._dev_parked)      # in-pool parked rows count:
                                               # their pages are HBM too
        dtype_bytes = (self._cache["kp"].dtype.itemsize
                       if self._cache is not None and "kp" in self._cache
                       else 2)
        hbm = cap_tokens * self.cfg.state_bytes_per_token(dtype_bytes)
        return {"kv_pages_used": float(used),
                "kv_pages_total": float(self._pages.n_pages),
                "kv_pages_peak": float(self._pages.peak_used),
                "kv_page_frag": float(frag),
                "kv_shared_pages": float(self._pages.shared_pages),
                "kv_prefix_pages": float(
                    self._prefix_idx.held_pages
                    if self._prefix_idx is not None else 0),
                "kv_hbm_bytes_per_row": float(hbm / max(1, resident)),
                "snapshot_bytes": float(
                    self._snap_store.bytes_used if self._snap_store else 0)}

    def check_page_invariants(self):
        """Debug assertion the test suite runs after every drive loop:
        allocator-level conservation (``PagePool.check_invariants``) PLUS
        exact refcount accounting — every page's rc must equal its owner
        count across resident slots, device-parked rows, and radix-index
        nodes, and the host block-table mirror must name exactly the
        slots' pages. Catches COW leaks and double-frees at the step they
        happen instead of as end-of-run drift."""
        if self._pages is None:
            return
        self._pages.check_invariants()
        owners = np.zeros((self._pages.n_pages,), np.int64)
        for s in range(self.max_slots):
            for p in self._slot_pages[s]:
                owners[p] += 1
            want = np.full((self._max_pg,), self._pages.sentinel, np.int32)
            want[:len(self._slot_pages[s])] = self._slot_pages[s]
            assert (self._tbl_host[s] == want).all(), \
                f"slot {s}: block-table mirror out of sync"
        for row in self._dev_parked:
            assert row.dev_pages is not None
            for p in row.dev_pages:
                owners[p] += 1
        if self._prefix_idx is not None:
            for p, n in self._prefix_idx.refcounts().items():
                owners[p] += n
        for p in range(self._pages.n_pages):
            assert self._pages.refcount(p) == owners[p], (
                f"page {p}: rc={self._pages.refcount(p)} but "
                f"{owners[p]} owners (slots+parked+index)")

    def queued_state_bytes(self, task_id: str,
                           dtype_bytes: int = 2) -> Optional[int]:
        """ACTUAL byte need of a tenant's queued/parked rows (paged mode):
        snapshot page counts for restore rows (exact — what restore will
        allocate), page-rounded prompt+prefix for replay rows, plus the
        fixed recurrent state. Feeds the admission controller's
        readmission re-estimate, replacing the worst-case ``max_len``
        charge. None in dense mode (caller falls back to the estimator)."""
        if not self.paged_kv:
            return None
        with self._stage_lock:
            rows = self._sched.rows_for(task_id)
            rows += [r for r in self._stage_inflight
                     if r.req.task_id == task_id]
            rows += [rr.row for rr in self._ready
                     if rr.row.req.task_id == task_id]
        if self._env is not None:
            rows += self._env.rows_for(task_id)
        per_tok = self.cfg.state_bytes_per_token(dtype_bytes)
        fixed = self.cfg.state_bytes_fixed(dtype_bytes)
        total = 0
        for r in rows:
            if getattr(r, "dev_pages", None) is not None:
                # device-parked: its pages are ALREADY in the pool — the
                # resume allocates nothing, only the fixed state returns
                total += fixed
                continue
            n_pg = (r.snap.n_pages if getattr(r, "snap", None) is not None
                    else self._row_pages_needed(r.prompt_len + len(r.gen)))
            total += n_pg * self.kv_page_size * per_tok + fixed
        return int(total)

    # -- preemption -------------------------------------------------------
    def _preemptible(self, slot: int, protect=()) -> bool:
        r = self._rows[slot]
        return (r is not None and r.status == "active" and not r.forced_q
                and slot not in self._pending
                and r.req.task_id not in protect)

    def _preempt_slot(self, slot: int):
        """Free one slot: snapshot is implicit (the generated prefix already
        lives host-side in the _Row), so just vacate and re-queue. The
        re-queued row flows through the SAME path as a fresh one — in
        disaggregated mode a prefill worker replays prompt+prefix
        asynchronously and the row splices back with its original per-row
        counter, preserving token-for-token replay parity.

        Paged engine with ``resume_restore``: the row's KV pages + SSM
        state snapshot to host first, so the later resume SPLICES state
        back instead of re-prefilling — unless the snapshot was dropped
        under memory pressure, in which case the retained replay path
        runs (identical output either way)."""
        row = self._rows[slot]
        row.replays += 1
        if self.paged_kv:
            self._park_or_snap(slot, row)
        if self._tracer is not None:
            fid = self._tracer.next_flow("preempt")
            now = time.monotonic()
            self._tr_vacate(slot, row, now, flow_out=fid)
            self._tracer.mark(self._trace_of(row), "preempted", now)
            if isinstance(row.meta, dict):
                row.meta["_flow_in"] = fid    # consumed at reinstall
        self._rows[slot] = None
        self._prompts[slot] = None
        self.stats.preemptions += 1
        with self._stage_lock:
            self._sched.push(row, self.stats.refills)

    def preempt_tenant(self, task_id: str, max_rows: Optional[int] = None
                       ) -> int:
        """Preempt up to `max_rows` (default: all) of a tenant's resident
        rows; returns the number preempted. Rows mid tool-call or mid
        force-feed keep their slots (replay always samples its first
        token). The freed KV needs no save: replay re-prefills the prefix."""
        n = 0
        for slot in range(self.max_slots):
            if max_rows is not None and n >= max_rows:
                break
            r = self._rows[slot]
            if (r is not None and r.req.task_id == task_id
                    and self._preemptible(slot)):
                self._preempt_slot(slot)
                n += 1
        return n

    def preempt_slots(self, n: int, protect=()) -> int:
        """Free up to `n` slots for an incoming tenant by preempting the
        lowest-priority / longest-remaining-budget resident rows (tenants in
        `protect` are never victims). Returns the number actually freed."""
        victims = [s for s in range(self.max_slots)
                   if self._preemptible(s, protect)]
        victims.sort(key=lambda s: (self._rows[s].req.priority,
                                    -(self._rows[s].req.max_new_tokens
                                      - self._rows[s].sampled),
                                    -self._rows[s].submit_index))
        freed = 0
        for slot in victims[:n]:
            self._preempt_slot(slot)
            freed += 1
        return freed

    def abort_tenant(self, task_id: str, reason: str = "quarantined") -> int:
        """Abort EVERY in-flight episode of one tenant — resident rows,
        queued / mid-prefill / ready-to-splice rows, and env-parked jobs —
        each yielding exactly one completion with ``reason`` as its
        finish_reason (the runtime counts them as quarantine drops).
        Other tenants' rows and scheduling order are untouched."""
        n = 0
        if self._env is not None:
            for job in self._env.cancel_tenant(task_id):
                row = job.row
                if row.status == "done":
                    continue     # expired earlier; already completed
                row.status, row.finish_reason = "done", reason
                self._complete_parked(row)
                n += 1
        for slot, r in enumerate(self._rows):
            if r is not None and r.req.task_id == task_id:
                r.status, r.finish_reason = "done", reason
                self._evict(slot)    # cancels a pending tool future too
                n += 1
        with self._stage_lock:
            drained: List[_Row] = []
            while True:
                row = self._sched.pop(
                    self.stats.refills,
                    where=lambda r: r.req.task_id == task_id)
                if row is None:
                    break
                drained.append(row)
            # mid-prefill rows: removing them from _stage_inflight makes
            # the owning worker's eventual _emit a no-op (abort idiom the
            # drain() path established)
            for row in list(self._stage_inflight):
                if row.req.task_id == task_id:
                    self._stage_inflight.remove(row)
                    drained.append(row)
            keep: Deque[ReadyRow] = deque()
            for rr in self._ready:
                if rr.row.req.task_id == task_id:
                    drained.append(rr.row)
                else:
                    keep.append(rr)
            self._ready = keep
        for row in drained:
            row.status, row.finish_reason = "done", reason
            self._complete_parked(row)
            n += 1
        return n

    # -- radix prefix reuse + GRPO-group sharing ---------------------------
    def _radix_on(self) -> bool:
        """Radix/group page sharing applies to pure-attention stacks only:
        suffix prefill at an arbitrary page offset is exact for attention
        (the same chunked-prefill decomposition the async workers use) but
        not for SSD recurrences mid-chunk, and SSM/hybrid rows carry
        recurrent state that has no shareable paged form."""
        return (self._prefix_idx is not None and self._cache is not None
                and "kp" in self._cache and "ssm" not in self._cache)

    def _group_key(self, r: _Row):
        return (r.req.adapter_index, tuple(r.req.prompt))

    def _radix_candidate(self, r: _Row):
        """Shared-install plan for a queued row whose prefix is in-pool:
        ``(shared_pages, start, L)`` or None. ``shared_pages`` are the
        pool pages the row will reference (NOT yet retained) and ``start``
        the page-aligned offset its suffix prefill resumes from. An exact
        whole-sequence hit (a GRPO-group sibling, or an unmodified
        re-submit) shares EVERY page including the partial tail: nothing
        is written at install — the final chunk recomputes only for the
        first-token logits — and the first decode write past the shared
        boundary COW-forks the tail page."""
        if r.snap is not None or r.dev_pages is not None:
            return None
        seq = list(r.req.prompt) + r.gen
        L = len(seq)
        adapter = r.req.adapter_index
        hit = self._prefix_idx.match_full(adapter, seq)
        if hit is not None:
            pages, tail = hit
            shared = pages + ([tail] if tail is not None else [])
            start = (len(pages) - (1 if tail is None else 0)) \
                * self.kv_page_size
            if start + _bucket_len(L - start) <= self.max_len:
                return (shared, start, L)
        pages = self._prefix_idx.match(adapter, seq, max_tokens=L - 1)
        if not pages:
            return None
        start = len(pages) * self.kv_page_size
        if start + _bucket_len(L - start) > self.max_len:
            return None                  # suffix bucket would overflow
        return (pages, start, L)

    def _index_prompt(self, row: _Row, row_pages: List[int]):
        """Publish a freshly installed row's prompt pages (full pages +
        partial tail) to the per-tenant radix index so later same-prefix
        rows share them. The index holds its own refcount per page
        (retained here); entries outlive the row and drop via LRU eviction
        under pool pressure or adapter-swap invalidation. Valid on EVERY
        install path — prompt-position K/V depends only on prompt tokens,
        so even a replayed row's pages hold the exact prompt prefix."""
        if not self._radix_on():
            return
        n_full = row.prompt_len // self.kv_page_size
        if n_full < 1:
            return
        rem = row.prompt_len % self.kv_page_size
        tail = (int(row_pages[n_full])
                if rem and len(row_pages) > n_full else None)
        newly = self._prefix_idx.insert(
            row.req.adapter_index, row.req.prompt,
            [int(p) for p in row_pages[:n_full]], tail_page=tail)
        if newly:
            self._pages.retain(newly)

    def _radix_fill_rows(self) -> bool:
        """Decode-thread install of queued rows whose prefix is already
        in-pool (radix hit / GRPO sibling): retain the shared pages,
        prefill ONLY the suffix (`_suffix_fn`, one width-1 call per row;
        jit caches one variant per (start, suffix-bucket) pair), and book
        only the suffix as prefill work. Runs before the private fill
        paths each step, and pops with ``pop_if`` so a sharable row never
        jumps a higher-priority tenant."""
        if not self._radix_on():
            return False
        free = [s for s in range(self.max_slots) if self._rows[s] is None]
        if not free:
            return False
        self._ensure_built()
        if self._stacked is None:
            return False          # the fill paths raise the proper error
        installed = 0
        while free:
            with self._stage_lock:
                row = self._sched.pop_if(
                    self.stats.refills,
                    lambda r: self._radix_candidate(r) is not None)
                pushed_at = self._sched.last_pushed_at
            if row is None:
                break
            plan = self._radix_candidate(row)
            if plan is None:      # index mutated between pop and here
                with self._stage_lock:
                    self._sched.push(row, self.stats.refills, pushed_at)
                break
            shared, start, L = plan
            self._pages.retain(shared)
            fresh = self._alloc_pages(self._row_pages_needed(L)
                                      - len(shared))
            if fresh is None:     # pool pressure: retry next step
                self._pages.release(shared)
                with self._stage_lock:
                    self._sched.push(row, self.stats.refills, pushed_at)
                break
            with self._stage_lock:
                self._book_queue_wait(pushed_at)
            slot = free.pop(0)
            t0 = time.monotonic()
            seq = list(row.req.prompt) + row.gen
            S_b = _bucket_len(L - start)
            toks = np.zeros((1, S_b), np.int32)
            toks[0, :L - start] = seq[start:]
            n_chunks = self.max_len // self.kv_page_size
            dest = np.full((n_chunks,), self._pages.sentinel, np.int32)
            dest[len(shared):len(shared) + len(fresh)] = fresh
            was_forced = bool(row.forced_q)
            first, lp, self._cache, state = self._suffix_fn(
                start, self.base_params, self._stacked,
                jnp.asarray([row.req.adapter_index], jnp.int32),
                jnp.asarray(shared[:start // self.kv_page_size], jnp.int32),
                jnp.asarray(toks), jnp.asarray([L], jnp.int32),
                jnp.asarray([len(row.gen)], jnp.int32),
                jnp.asarray(row.key[None], jnp.uint32),
                jnp.asarray([row.req.temperature], jnp.float32),
                jnp.asarray([row.forced_q[0] if was_forced else 0],
                            jnp.int32),
                jnp.asarray([1 if was_forced else 0], jnp.int32),
                self._cache, jnp.asarray(dest), jnp.int32(slot),
                self._d_cur, self._d_counters, self._d_keys, self._d_temps,
                self._d_row_ids)
            (self._d_cur, self._d_counters, self._d_keys, self._d_temps,
             self._d_row_ids) = state
            self._mask_sig = None
            now = time.monotonic()
            installed += 1
            self._rows[slot] = row
            self._prompts[slot] = list(row.req.prompt)
            self._installed(slot, row, now, t0, "prefill")
            self._assign_slot_pages(slot, shared + fresh, L)
            self._index_prompt(row, shared + fresh)
            self.stats.prefix_hits += 1
            self.stats.prefix_hit_tokens += start
            self.stats.prefill_tokens += L - start      # suffix only
            self.stats.prefill_seconds += now - t0
            self.stats.decode_stall_seconds += now - t0
            if was_forced:                    # env-stage resume splice
                row.forced_q.pop(0)
                if row.gen:
                    self.stats.replays += 1
                    self.stats.replay_tokens += L - start
            elif row.gen:                     # preemption replay
                self.stats.replays += 1
                self.stats.replay_tokens += L - start
            else:                             # fresh row (GRPO sibling)
                self.stats.prefills += 1
                row.started_at = now
            self.stats.tokens_generated += 1
            if not was_forced:
                self.stats.sampled_tokens += 1
            if self.on_stage is not None:
                self.on_stage("prefill", row.req.task_id, t0, now)
            action = row.accept(int(np.asarray(first)[0]),
                                float(np.asarray(lp)[0]),
                                0.0 if was_forced else 1.0, self.max_len)
            if action == "call":
                self._on_call(slot)
            elif action == "done":
                self._evict(slot)
        if installed:
            self.stats.refills += 1    # one refill event (starvation aging)
        return installed > 0

    def _fusable_forced(self, row: _Row) -> bool:
        """Response-prefill fusion guard: a forced RESP…ENDRESP block can
        fold into the row's (re)prefill call only when replaying it
        step-wise would provably not terminate or branch mid-block —
        forced tokens never dispatch CALLs (mask 0), so the only early
        exits are a forced EOS, the max_len capacity trip, or the
        sampling budget firing at the block's last token."""
        q = row.forced_q
        if len(q) <= 1:
            return False            # single opener: already one call
        if tok.EOS in q:
            return False
        if row.prompt_len + len(row.gen) + len(q) >= self.max_len:
            return False
        if row.sampled >= row.req.max_new_tokens:
            return False
        return True

    def _refill_free_slots(self) -> bool:
        """Fill every freed slot from the queue with ONE fused jitted call:
        batch-prefill the incoming rows, splice their KV/SSM state into the
        pool, and sample their first tokens. Ghost lanes (fewer queued rows
        than the padded width) scatter out of bounds and are dropped, so the
        call shape depends only on (width, prompt bucket).

        The queue pops in scheduler order (priority / predicted-remaining /
        starvation tier). A preemption-replayed row prefills its prompt +
        generated prefix in one sequence and samples token `len(gen)` with
        counter `len(gen)` — bit-identical continuation.

        Prefix cache interplay: snapshot/device-parked rows restore on the
        decode thread and radix-sharable rows install via
        ``_radix_fill_rows`` — neither pops here. A GRPO sibling of a row
        popped THIS round defers one step (``seen_keys``) so the leader's
        pages reach the index first and the sibling lands as an
        exact-match share instead of a private prefill."""
        free = [s for s in range(self.max_slots) if self._rows[s] is None]
        with self._stage_lock:
            has_queued = bool(self._sched)
        if not free or not has_queued:
            return False
        self._ensure_built()
        if self._stacked is None:
            raise RuntimeError("no adapters installed — call set_adapters()")
        incoming: List[Tuple[int, _Row]] = []
        pages_of: List[List[int]] = []
        seen_keys = set()
        radix = self._radix_on()
        where = None
        if self.resume_restore or radix:
            def where(r):
                # snapshot/device-parked rows restore on the decode
                # thread (no prefill at all); radix candidates install
                # through the suffix-only path
                if r.snap is not None or r.dev_pages is not None:
                    return False
                if radix and self._radix_candidate(r) is not None:
                    return False
                if radix and len(r.req.prompt) >= self.kv_page_size \
                        and self._group_key(r) in seen_keys:
                    return False        # sibling: wait for the leader
                return True
        pressure = False
        with self._stage_lock:
            while free and self._sched:
                row = self._sched.pop(self.stats.refills, where=where)
                if row is None:
                    break
                pushed_at = self._sched.last_pushed_at
                if self.paged_kv:
                    n_pg = self._row_pages_needed(
                        len(row.req.prompt) + len(row.gen))
                    # spill=False: dev-parked spilling gathers device
                    # state (host sync) — never under _stage_lock; cold
                    # radix entries still evict (pure host bookkeeping)
                    pages = self._alloc_pages(n_pg, spill=False)
                    if pages is None:
                        if self._pages.used_pages == 0:
                            self._finish_capacity(row)   # can never fit
                            continue
                        # pool pressure: resident rows will free pages
                        self._sched.push(row, self.stats.refills, pushed_at)
                        pressure = True
                        break
                    pages_of.append(pages)
                self._book_queue_wait(pushed_at)
                incoming.append((free.pop(0), row))
                if radix:
                    seen_keys.add(self._group_key(row))
        if not incoming:
            if pressure and self._dev_parked:
                # nothing installable and nothing resident to free pages:
                # demote the oldest device-parked row to the host tier
                # (outside the lock) so the next step's alloc succeeds
                self._spill_dev_parked()
            return False
        with phase("prefill.fused", self._tracer, ("prefill", "fused")) as ph:
            seqs, fused, fmask, first, lp, flp = self._fused_prefill(
                incoming, pages_of)
        t0, now = ph.t0, ph.t1
        self.stats.refills += 1
        # stage attribution (pre-existing bug: this was booked as decode
        # time): the fused refill is PREFILL-stage work, and because it runs
        # on the decode stream it is also decode-stall time — the quantity
        # the disaggregated path drives to zero.
        self.stats.prefill_seconds += now - t0
        self.stats.decode_stall_seconds += now - t0
        if self.on_stage is not None:
            self.on_stage("prefill",
                          "+".join(sorted({r.req.task_id
                                           for _, r in incoming})), t0, now)
        for j, (slot, row) in enumerate(incoming):
            self._rows[slot] = row
            self._prompts[slot] = list(row.req.prompt)
            self._installed(slot, row, now, t0, "prefill")
            if self.paged_kv:
                self._assign_slot_pages(slot, pages_of[j], len(seqs[j]))
                self._index_prompt(row, pages_of[j])
            was_forced = fmask[j] == 1
            L_replay = len(row.req.prompt) + len(row.gen)
            if was_forced or fused[j]:            # env-stage resume splice
                if row.gen:   # the resume re-prefilled prompt+prefix: the
                    self.stats.replays += 1       # per-turn recomputation
                    self.stats.replay_tokens += L_replay  # restore kills
            elif row.gen:                         # preemption replay
                self.stats.replays += 1
                self.stats.replay_tokens += L_replay
            else:                                 # fresh row
                self.stats.prefills += 1
                row.started_at = now
            self.stats.prefill_tokens += len(seqs[j])
            if fused[j]:
                Fj = len(row.forced_q)
                self.stats.fused_forced_tokens += Fj
                self.stats.tokens_generated += Fj
                for t in range(Fj):
                    tk = row.forced_q.pop(0)   # pop BEFORE accept, like the
                    # step-wise path: accept's budget check reads forced_q
                    action = row.accept(tk, float(flp[j, t]), 0.0,
                                        self.max_len)
                    assert action == "continue", \
                        "fusion guard admitted a terminating forced block"
            elif was_forced:
                row.forced_q.pop(0)
            self.stats.tokens_generated += 1
            if not was_forced:
                self.stats.sampled_tokens += 1
            action = row.accept(int(first[j]), float(lp[j]),
                                0.0 if was_forced else 1.0, self.max_len)
            if action == "call":
                self._on_call(slot)
            elif action == "done":
                self._evict(slot)
        return True

    def _fused_prefill(self, incoming: List[Tuple[int, "_Row"]],
                       pages_of: List[List[int]]):
        """The fused refill's one jitted call over the popped rows: their
        prefill, KV splice and first tokens. Returns ``(seqs, fused,
        fmask, first, lp, flp)`` on the host (``flp`` None off the paged
        path)."""
        flp = None
        k = len(incoming)
        W = 1                                    # next-pow2 width bucket
        while W < k:
            W *= 2
        # response-prefill fusion (paged path): a resume's whole forced
        # RESP…ENDRESP block joins the prefilled sequence — its tokens are
        # known — instead of force-feeding one decode step each
        fused = [self.paged_kv and self._fusable_forced(row)
                 for _, row in incoming]
        seqs = [list(row.req.prompt) + row.gen
                + (row.forced_q if fused[j] else [])
                for j, (_, row) in enumerate(incoming)]
        F_B = max([1] + [_bucket_len(len(r.forced_q))
                         for j, (_, r) in enumerate(incoming) if fused[j]])
        S_p = _bucket_len(max(len(s) for s in seqs))
        tokens = np.zeros((W, S_p), np.int32)
        prompt_lens = np.ones((W,), np.int32)    # ghosts: len-1 dummy prompt
        init_counters = np.zeros((W,), np.int32)
        row_ids = np.zeros((W,), np.int32)
        slots = np.full((W,), self.max_slots, np.int32)   # ghosts: OOB → drop
        keys = np.zeros((W, 2), np.uint32)
        temps = np.ones((W,), np.float32)
        forced = np.zeros((W,), np.int32)        # env-stage resumes install
        fmask = np.zeros((W,), np.int32)         # a forced RESP opener
        fpos = np.zeros((W, F_B), np.int32)      # fusion: positions whose
        ftoks = np.zeros((W, F_B), np.int32)     # logits predict each
                                                 # forced token
        for j, (slot, row) in enumerate(incoming):
            tokens[j, :len(seqs[j])] = seqs[j]
            prompt_lens[j] = len(seqs[j])
            init_counters[j] = len(row.gen)
            row_ids[j] = row.req.adapter_index
            slots[j] = slot
            keys[j] = row.key
            temps[j] = row.req.temperature
            if fused[j]:
                L0 = len(row.req.prompt) + len(row.gen)
                Fj = len(row.forced_q)
                init_counters[j] = len(row.gen) + Fj
                fpos[j, :Fj] = np.arange(L0 - 1, L0 - 1 + Fj)
                ftoks[j, :Fj] = row.forced_q
            elif row.forced_q:
                forced[j] = row.forced_q[0]
                fmask[j] = 1
        if self.paged_kv:
            # physical destination pages per (row, chunk); ghost rows and
            # chunks past a row's page count point at the scratch page
            n_chunks = self.max_len // self.kv_page_size
            dest = np.full((W, n_chunks), self._pages.sentinel, np.int32)
            for j, pages in enumerate(pages_of):
                dest[j, :len(pages)] = pages
            first, lp, flp, self._cache, state = self._refill_fn(
                self.base_params, self._stacked, jnp.asarray(tokens),
                jnp.asarray(prompt_lens), jnp.asarray(init_counters),
                jnp.asarray(slots), jnp.asarray(dest), jnp.asarray(row_ids),
                jnp.asarray(keys), jnp.asarray(temps), jnp.asarray(forced),
                jnp.asarray(fmask), jnp.asarray(fpos), jnp.asarray(ftoks),
                self._cache, self._d_cur, self._d_counters, self._d_keys,
                self._d_temps, self._d_row_ids)
            flp = np.asarray(flp)
        else:
            first, lp, self._cache, state = self._refill_fn(
                self.base_params, self._stacked, jnp.asarray(tokens),
                jnp.asarray(prompt_lens), jnp.asarray(init_counters),
                jnp.asarray(slots), jnp.asarray(row_ids), jnp.asarray(keys),
                jnp.asarray(temps), jnp.asarray(forced), jnp.asarray(fmask),
                self._cache, self._d_cur, self._d_counters,
                self._d_keys, self._d_temps, self._d_row_ids)
        (self._d_cur, self._d_counters, self._d_keys, self._d_temps,
         self._d_row_ids) = state
        first = np.asarray(first)
        lp = np.asarray(lp)
        return seqs, fused, fmask, first, lp, flp

    def _splice_ready_rows(self) -> str:
        """Decode-side half of the disaggregated split: install rows the
        async prefill stage finished into freed slots with one scatter-only
        jitted call each. No prefill graph runs on the decode stream — the
        splice is O(cache row copy), so decode never stalls on a prompt.
        Returns the installed rows' tenants joined by "+" ("" for none);
        ``step`` books the splice time from its ``engine.splice`` phase."""
        free = [s for s in range(self.max_slots) if self._rows[s] is None]
        if not free:
            return ""
        ready: List[ReadyRow] = []
        with self._stage_lock:
            while free and self._ready:
                ready.append(self._ready.popleft())
                free.pop(0)
        if not ready:
            return ""
        free = [s for s in range(self.max_slots) if self._rows[s] is None]
        installed = 0
        for i_rr, rr in enumerate(ready):
            row = rr.row
            pages: List[int] = []
            if self.paged_kv:
                alloc = self._alloc_pages(self._row_pages_needed(rr.seq_len))
                if alloc is None:
                    if self._pages.used_pages == 0:
                        self._finish_capacity(row)      # can never fit
                        continue
                    with self._stage_lock:    # pool pressure: retry later
                        for back in reversed(ready[i_rr:]):
                            self._ready.appendleft(back)
                    break
                pages = alloc
            slot = free.pop(0)
            if self.paged_kv:
                self._cache, state = self._splice_fn(
                    self._cache, rr.pcache, jnp.int32(slot),
                    jnp.asarray(self._padded_pages(pages)),
                    jnp.int32(rr.seq_len), jnp.int32(rr.first),
                    jnp.int32(rr.init_counter),
                    jnp.asarray(row.key, jnp.uint32),
                    jnp.float32(row.req.temperature),
                    jnp.int32(row.req.adapter_index), self._d_cur,
                    self._d_counters, self._d_keys, self._d_temps,
                    self._d_row_ids)
            else:
                self._cache, state = self._splice_fn(
                    self._cache, rr.pcache, jnp.int32(slot),
                    jnp.int32(rr.seq_len), jnp.int32(rr.first),
                    jnp.int32(rr.init_counter),
                    jnp.asarray(row.key, jnp.uint32),
                    jnp.float32(row.req.temperature),
                    jnp.int32(row.req.adapter_index), self._d_cur,
                    self._d_counters, self._d_keys, self._d_temps,
                    self._d_row_ids)
            (self._d_cur, self._d_counters, self._d_keys, self._d_temps,
             self._d_row_ids) = state
            self._mask_sig = None      # slot contents changed
            now = time.monotonic()
            installed += 1
            self._rows[slot] = row
            self._prompts[slot] = list(row.req.prompt)
            self._installed(slot, row, now)
            if self.paged_kv:
                self._assign_slot_pages(slot, pages, rr.seq_len)
                self._index_prompt(row, pages)
            n_fused = len(rr.forced_lps)
            if rr.forced_first or n_fused:        # env-stage resume splice
                if rr.forced_first:
                    row.forced_q.pop(0)
                if row.gen:                       # resume re-prefilled the
                    self.stats.replays += 1       # whole prefix async
                    self.stats.replay_tokens += rr.seq_len - n_fused
            elif row.gen:                         # preemption replay
                self.stats.replays += 1
                self.stats.replay_tokens += rr.seq_len
            else:                                 # fresh row
                self.stats.prefills += 1
                row.started_at = now
            self.stats.splices += 1
            self.stats.splice_wait_seconds += max(0.0, now - rr.ready_at)
            if n_fused:
                # response-prefill fusion: the worker prefilled the whole
                # forced block — book its tokens here with the prefill
                # logprobs (bit-equal to the step-wise force-feed)
                self.stats.fused_forced_tokens += n_fused
                self.stats.tokens_generated += n_fused
                for t in range(n_fused):
                    tk = row.forced_q.pop(0)
                    action = row.accept(tk, rr.forced_lps[t], 0.0,
                                        self.max_len)
                    assert action == "continue", \
                        "fusion guard admitted a terminating forced block"
            self.stats.tokens_generated += 1
            if not rr.forced_first:
                self.stats.sampled_tokens += 1
            action = row.accept(rr.first, rr.lp,
                                0.0 if rr.forced_first else 1.0,
                                self.max_len)
            if action == "call":
                self._on_call(slot)
            elif action == "done":
                self._evict(slot)
        if installed == 0:
            return ""
        self.stats.refills += 1        # one refill event (starvation aging)
        return "+".join(sorted({rr.row.req.task_id for rr in ready}))

    def _on_call(self, slot: int):
        """Route a freshly sampled CALL: park the row in the env stage
        (env_stage mode) or freeze it in its slot (baseline)."""
        if self._env is not None:
            self._park(slot)
        else:
            self._dispatch_tool(slot)

    def _dispatch_tool(self, slot: int):
        self._pending[slot], self._pending_tok[slot] = _submit_tool_call(
            self._rows[slot], self._prompts[slot], self._pool, self._rng,
            self.sim_latency)
        self._pending_t0[slot] = time.monotonic()
        if self._tracer is not None:
            # freeze-in-slot baseline: the row stays resident, so the
            # env window is a lifecycle state only (no park hand-off)
            self._tracer.mark(self._trace_of(self._rows[slot]), "env",
                              self._pending_t0[slot])

    def _park(self, slot: int):
        """Env-stage path: vacate the slot the moment the row samples CALL.
        The generated prefix already lives host-side (the same snapshot
        preemption relies on), so parking is free of device copies — the
        slot is immediately refillable from the scheduler queue while an
        EnvWorker runs the tool call."""
        row = self._rows[slot]
        row.ensure_session()
        query = list(self._prompts[slot]) + row.gen
        latency = row.req.env.sample_env_latency(
            _RandomShim(self._rng)) if not self.sim_latency else 0.0
        if self.paged_kv:
            # resume_restore: the row's resume state is preserved — pages
            # stay IN-POOL under the prefix cache (pure retain, zero host
            # bytes) or snapshot to host otherwise; the tool-response
            # resume splices them back instead of replaying prompt+prefix
            self._park_or_snap(slot, row)
        fid = 0
        if self._tracer is not None:
            fid = self._tracer.next_flow("park")
            now = time.monotonic()
            self._tr_vacate(slot, row, now, flow_out=fid)
            self._tracer.mark(self._trace_of(row), "parked", now)
        self._rows[slot] = None
        self._prompts[slot] = None
        self.stats.parks += 1
        job = self._env.submit(row, query, row.req.task_id, latency)
        job.flow = fid

    def _pump_env_stage(self):
        """Resolve the env stage's response queue: expire timed-out jobs
        (their rows finish with tool_timeout — they hold no slot), and turn
        each response into a resume job — the row re-enters the scheduler
        queue with its force-feed queue pre-loaded; the (fused or
        disaggregated) prefill path replays prompt+prefix and installs the
        forced RESP opener."""
        now = time.monotonic()
        for job in self._env.expire(now, self.tool_timeout_s):
            row = job.row
            row.status, row.finish_reason = "done", "tool_timeout"
            self._complete_parked(row)
        # drain_resolved() pops the WHOLE resolved batch: process every job
        # before surfacing an error, else the siblings' rows would vanish
        # from all engine accounting (no slot, no queue, no completion)
        first_error: Optional[BaseException] = None
        for job in self._env.drain_resolved():
            row = job.row
            if job.error is not None:
                # ToolError (permanent / retries exhausted) is an expected
                # EPISODE outcome: the row finishes with finish_reason
                # tool_error — counted, never trained, feeding the tenant
                # breaker. Anything else is a bug in our stack and stays
                # fatal, so chaos-off behaviour is unchanged.
                row.status, row.finish_reason = "done", "tool_error"
                self.stats.tool_errors += 1
                if self._tracer is not None:
                    self._tracer.mark(self._trace_of(row), "tool_error")
                self._complete_parked(row)
                if not isinstance(job.error, ToolError):
                    first_error = first_error or job.error
                continue
            tid = row.req.task_id
            self.stats.add_env_wait(tid, job.resolved_at - job.submitted_at)
            if self.on_stage is not None:
                self.on_stage("env", tid, job.submitted_at, job.resolved_at)
            if self._tracer is not None:
                # env worker span + the two hand-off arrows: park→env
                # (job.flow, opened at _park) and env→resume (opened
                # here, consumed when the row reinstalls into a slot)
                trace = self._trace_of(row)
                fid = self._tracer.next_flow("resume")
                self._tracer.span(("env", f"worker-{job.worker}"), tid,
                                  job.started_at, job.resolved_at,
                                  trace=trace, flow_in=job.flow,
                                  flow_out=fid)
                self._tracer.mark(trace, "env", job.started_at)
                self._tracer.mark(trace, "resume_queued", job.resolved_at)
                if isinstance(row.meta, dict):
                    row.meta["_flow_in"] = fid
            row.forced_q = [tok.RESP] + list(job.response) + [tok.ENDRESP]
            row.status = "active"
            row.resumed_at = job.resolved_at
            self.stats.resumes += 1
            with self._stage_lock:
                self._sched.push(row, self.stats.refills)
        if first_error is not None:      # surface like fut.result() does
            raise first_error

    # -- scheduler interface ---------------------------------------------
    def step(self) -> bool:
        """One engine iteration: resolve tools, fill freed slots (fused
        refill, or splice of async-prefilled rows in disaggregated mode),
        one decode step over the pool, evict finished rows. Returns True if
        any device work happened (refill/splice or decode).

        Each part runs in a named phase (``repro.obs.phase``: a
        ``marlaas.engine.*`` span on the profiler's trace, and a span on
        the tracer's ``STEP_TRACK`` when a tracer is attached), in this
        order: supervise, env_pump, restore, radix_fill, splice (refill on
        the fused path), pages, masks, dispatch, wait, accept."""
        tr = self._tracer
        progressed = False
        # stage supervision: detect dead/wedged workers, recover their
        # stranded work, respawn to complement under backoff (no-op while
        # every pool is at complement — one healthy() call per stage)
        with phase("engine.supervise", tr, STEP_TRACK) as ph:
            now = ph.t0
            self.supervisor.tick(now)
        with phase("engine.env_pump", tr, STEP_TRACK):
            # env-interaction stage: expire + resume parked rows (env_stage
            # mode); the baseline freeze-in-slot path resolves futures below
            if self._env is not None:
                self._pump_env_stage()
            # resolve / time out pending tool calls (freeze-in-slot baseline)
            for slot in list(self._pending):
                fut = self._pending[slot]
                row = self._rows[slot]
                if fut.done():
                    resp = fut.result()
                    t0w = self._pending_t0[slot]
                    tid = row.req.task_id
                    self.stats.add_env_wait(tid, now - t0w)
                    if self.on_stage is not None:
                        self.on_stage("env", tid, t0w, now)
                    if tr is not None:
                        trace = self._trace_of(row)
                        tr.span(("env", "pool"), tid, t0w, now, trace=trace)
                        tr.mark(trace, "decode", now)
                    row.forced_q = [tok.RESP] + list(resp) + [tok.ENDRESP]
                    row.status = "active"
                    del self._pending[slot], self._pending_t0[slot]
                elif now - self._pending_t0[slot] > self.tool_timeout_s:
                    row.status, row.finish_reason = "done", "tool_timeout"
                    self._evict(slot)
        # snapshot-restore resume (paged engine): queued rows carrying a
        # host snapshot splice their saved pages back on the decode thread
        # — no prefill graph, no replay — before the fill paths run
        if self.resume_restore:
            with phase("engine.restore", tr, STEP_TRACK):
                if self._restore_rows():
                    progressed = True
        # radix/GRPO shared installs (prefix cache): rows whose prefix is
        # already in-pool retain it and prefill only their suffix — runs
        # on the decode thread before the private fill paths in BOTH
        # fused and disaggregated modes
        if self.paged_kv and self._stacked is not None:
            with phase("engine.radix_fill", tr, STEP_TRACK):
                if self._radix_fill_rows():
                    progressed = True
        # fill freed slots from the cross-task queue: disaggregated mode
        # splices asynchronously-prefilled rows (decode never runs a prefill
        # graph); fused mode runs the baseline one-call refill
        if self.disagg_prefill:
            self._raise_stage_error()
            if self._stacked is None:
                with self._stage_lock:
                    has_queued = len(self._sched) > 0
                if has_queued:      # same fail-fast as the fused refill
                    raise RuntimeError(
                        "no adapters installed — call set_adapters()")
            else:
                if not self._workers:
                    self._ensure_stage()  # first start / post-halt only;
                                          # replacements are the
                                          # supervisor's (backoff-gated)
                with phase("engine.splice", tr, STEP_TRACK) as ph:
                    spliced = self._splice_ready_rows()
                if spliced:
                    progressed = True
                    self.stats.splice_seconds += ph.t1 - ph.t0
                    if self.on_stage is not None:
                        self.on_stage("splice", spliced, ph.t0, ph.t1)
        else:
            with phase("engine.refill", tr, STEP_TRACK):
                if self._refill_free_slots():
                    progressed = True
        if self._env is not None:
            # the engine invariant of the disaggregated env stage: a
            # tool-waiting row NEVER occupies a decode slot (it parks)
            assert all(r is None or r.status != "calling"
                       for r in self._rows), \
                "env-stage invariant violated: tool-waiting row resident"
        if self.paged_kv:
            # pre-step growth: allocate the page each active row's next
            # K/V write lands in (cache-capacity eviction on exhaustion),
            # then upload the block table if the topology changed
            with phase("engine.pages", tr, STEP_TRACK):
                self._ensure_decode_pages()
                if self._tbl_dirty and "tbl" in self._cache:
                    self._cache = dict(self._cache,
                                       tbl=jnp.asarray(self._tbl_host))
                    self._tbl_dirty = False
        with phase("engine.masks", tr, STEP_TRACK):
            advance = np.array(
                [1 if (r is not None and r.status == "active") else 0
                 for r in self._rows], np.int32)
            forced = np.zeros((self.max_slots,), np.int32)
            fmask = np.zeros((self.max_slots,), np.int32)
            for i, r in enumerate(self._rows):
                if r is not None and r.status == "active" and r.forced_q:
                    forced[i] = r.forced_q[0]
                    fmask[i] = 1
            # upload the masks only when they changed (steady decode
            # between evictions re-uses the device copies — zero uploads
            # per step)
            sig = advance.tobytes() + forced.tobytes() + fmask.tobytes()
            if advance.any() and sig != self._mask_sig:
                self._d_masks = (jnp.asarray(forced), jnp.asarray(fmask),
                                 jnp.asarray(advance))
                self._mask_sig = sig
        if not advance.any():
            self._t_read = None     # no decode: the next one opens no gap
            return progressed
        d_forced, d_fmask, d_advance = self._d_masks
        with phase("engine.dispatch", tr, STEP_TRACK) as ph:
            nxt, lp, self._cache, self._d_counters = self._step_fn(
                self.base_params, self._stacked, self._d_row_ids,
                self._d_cur, self._cache, self._d_keys, self._d_counters,
                self._d_temps, d_forced, d_fmask, d_advance)
        self._d_cur = nxt
        with phase("engine.wait", tr, STEP_TRACK) as ph_wait:
            nxt = np.asarray(nxt)
            lp = np.asarray(lp)
        if self._t_read is not None:
            self.stats.host_gap_seconds += ph.t0 - self._t_read
            self.stats.host_gaps += 1
        self._t_read = ph_wait.t1
        self.stats.decode_seconds += ph_wait.t1 - ph.t0
        with phase("engine.accept", tr, STEP_TRACK):
            self.stats.decode_steps += 1
            self.stats.occupied_row_steps += int(advance.sum())
            self.stats.capacity_row_steps += self.max_slots
            if self.paged_kv:
                # this step's token lands at _slot_pos: ceil((pos+1)/page)
                pos = np.asarray(self._slot_pos)[np.asarray(advance) > 0]
                self.stats.kv_live_pages += int(
                    (pos // self.kv_page_size + 1).sum())
                self.stats.kv_table_pages += pos.size * self._max_pg
            # slot dead weight of the freeze-in-slot baseline: resident
            # rows that spent this decode step waiting on a tool (0 by
            # construction under env_stage — the invariant above)
            self.stats.tool_wait_slot_steps += sum(
                1 for r in self._rows
                if r is not None and r.status == "calling")
            for slot, r in enumerate(self._rows):
                if r is None or r.status != "active" or advance[slot] == 0:
                    continue
                if self.paged_kv:
                    self._slot_pos[slot] += 1   # device cache["pos"] mirror
                was_forced = fmask[slot] == 1
                if was_forced:
                    r.forced_q.pop(0)
                action = r.accept(int(nxt[slot]), float(lp[slot]),
                                  0.0 if was_forced else 1.0, self.max_len)
                self.stats.tokens_generated += 1
                if not was_forced:
                    self.stats.sampled_tokens += 1
                if action == "call":
                    self._on_call(slot)
                elif action == "done":
                    self._evict(slot)
        return True

    def drain(self, deadline_s: float = 300.0,
              stop: Optional[Callable[[], bool]] = None
              ) -> List[RolloutCompletion]:
        """Run until queue and pool are empty (or deadline); returns all
        completions produced during the drain."""
        out: List[RolloutCompletion] = []
        deadline = time.monotonic() + deadline_s
        while not self.idle() and time.monotonic() < deadline:
            if stop is not None and stop():
                break
            progressed = self.step()
            out.extend(self.drain_completions())
            if not progressed:
                time.sleep(0.001)     # waiting only on external tools
        # deadline: abort whatever is still resident OR anywhere in the
        # prefill pipeline, so every submitted request yields exactly one
        # completion. Workers are halted first: their unfinished rows return
        # to the queue, ready-but-unspliced rows abort like queued ones. A
        # worker stuck past the join timeout (e.g. mid cold-compile) still
        # can't lose rows: its in-flight rows are swept into the queue here,
        # and the worker's late emit/teardown drops rows it no longer owns.
        if self.queued() > 0 and self.disagg_prefill:
            self._halt_stage()
            with self._stage_lock:
                for rr in self._ready:
                    self._sched.push(rr.row, self.stats.refills)
                self._ready.clear()
                for row in self._stage_inflight:
                    self._sched.push(row, self.stats.refills)
                self._stage_inflight.clear()
        if self._env is not None and self._env.count() > 0:
            # parked episodes abort like queued ones; late worker results
            # are dropped by the cancelled flag. cancel_all also returns
            # already-cancelled executing jobs whose rows expire() finished
            # earlier — those must not complete twice.
            for job in self._env.cancel_all():
                row = job.row
                if row.status == "done":
                    continue
                row.status = "done"
                row.finish_reason = row.finish_reason or "aborted"
                self._complete_parked(row)
        for slot, r in enumerate(self._rows):
            if r is not None:
                r.status = "done"
                r.finish_reason = r.finish_reason or "aborted"
                self._evict(slot)
        with self._stage_lock:
            leftovers = self._sched.pop_all()
        for row in leftovers:
            # a preempted/resumed-then-aborted row keeps its generated prefix
            row.status, row.finish_reason = "done", "aborted"
            self._complete_parked(row)
        out.extend(self.drain_completions())
        return out

    def run_requests(self, requests: Sequence[RolloutRequest], adapter_trees,
                     deadline_s: float = 300.0
                     ) -> Tuple[List[Dict], RolloutStats]:
        """Convenience: submit a request list, drain, return results in
        submission order — drop-in comparable with `generate()`."""
        t0 = time.monotonic()
        for i, tree in enumerate(adapter_trees):
            self.set_adapters(i, tree)
        idx = {}
        for i, r in enumerate(requests):
            # unseeded requests default to the advancing submission counter
            # inside submit() — matching generate()'s _n_issued behaviour
            idx[self.submit(r)] = i
        comps = self.drain(deadline_s)
        results: List[Optional[Dict]] = [None] * len(requests)
        for c in comps:
            if c.submit_index in idx:     # skip strays from an earlier call
                results[idx[c.submit_index]] = c.to_result()
        self.stats.wall_seconds += time.monotonic() - t0
        return results, self.stats

    def shutdown(self):
        if self._workers:
            self._halt_stage()
        if self._env is not None:
            self._env.halt()
        if self._own_pool:
            self._pool.shutdown(wait=False)


class _RandomShim:
    """random.Random-compatible gauss() over a numpy RandomState."""
    def __init__(self, rs):
        self.rs = rs

    def gauss(self, mu, sigma):
        return float(self.rs.normal(mu, sigma))


def to_trajectory_batch(results: List, task_id: str, version: int,
                        group_size: int, pad_to: int = None) -> TrajectoryBatch:
    """Pack engine results for ONE task into a padded TrajectoryBatch and
    verify rewards. Accepts `generate()` result dicts or
    `RolloutCompletion`s (continuous engine)."""
    results = [r.to_result() if isinstance(r, RolloutCompletion) else r
               for r in results]
    rows = [r for r in results if r["task_id"] == task_id]
    S = max(len(r["tokens"]) for r in rows)
    if pad_to:
        S = max(S, pad_to)
    S = -(-S // 8) * 8
    R = len(rows)
    tokens = np.zeros((R, S), np.int32)
    loss_mask = np.ones((R, S), np.float32)
    behavior = np.zeros((R, S), np.float32)
    p_lens = np.zeros((R,), np.int32)
    t_lens = np.zeros((R,), np.int32)
    rewards = np.zeros((R,), np.float32)
    for j, r in enumerate(rows):
        n = len(r["tokens"])
        tokens[j, :n] = r["tokens"]
        p_lens[j] = r["prompt_len"]
        t_lens[j] = n
        gen_len = n - r["prompt_len"]
        # behavior logprobs/losses sit at positions predicting each gen token
        for k in range(gen_len):
            pos = r["prompt_len"] - 1 + k
            behavior[j, pos] = r["gen_logprobs"][k]
            loss_mask[j, pos] = r["gen_loss_mask"][k]
        comp = r["tokens"][r["prompt_len"]:]
        rewards[j] = r["env"].verify(r["truth"], comp)
    meta = {"loss_mask": loss_mask}
    if any("finish_reason" in r for r in rows):
        meta["finish_reasons"] = [r.get("finish_reason", "") for r in rows]
    return TrajectoryBatch(task_id=task_id, version=version, tokens=tokens,
                           prompt_lens=p_lens, total_lens=t_lens,
                           rewards=rewards, group_size=group_size,
                           behavior_logprobs=behavior[:, :S - 1],
                           meta=meta)
