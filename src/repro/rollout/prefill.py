"""Disaggregated async prefill stage (paper §4.1, Fig 5).

The continuous engine's fused refill ran every incoming prompt's prefill as
one jitted call ON THE DECODE STREAM: a long prompt stalled decode for all
resident tenants — exactly the cross-task interference MARLaaS's
disaggregated layout eliminates. This module is the prefill side of the
split:

``PrefillWorker`` — a daemon thread (the engine spawns ``prefill_workers``
of them when ``disagg_prefill=True``). Each worker pops scheduler-ordered
rows from the engine's cross-task queue (the same ``SlotScheduler`` that
used to order the fused refill pop), prefills them on its OWN cache — never
touching the decode pool — and emits a ``ReadyRow`` (spliceable KV/SSM
state + first sampled token + logprob) into the engine's ready queue. The
decode side then installs ready rows with a scatter-only jitted splice
(see ``engine._build_splice_fn``), so decode literally never waits on a
prefill graph.

Chunked prefill: prompts longer than ``prefill_chunk`` are processed in
fixed-size chunks through ``forward_prefill_chunk`` and each worker
round-robins its in-flight jobs chunk by chunk, so one huge prompt cannot
monopolize the stage — short prompts admitted later still come out first.
The chunk size is rounded up to a multiple of 8 (shape buckets) and of
``cfg.ssm.chunk_size`` (recurrent families: external chunk boundaries then
coincide with the SSD scan's internal ones, making the chunked state
bit-equal to the whole-prompt state). Only the last chunk is padded; its
pad positions are masked out of the recurrent state (``seq_lens``) and sit
beyond ``pos`` in the KV cache, where decode overwrites them.

Determinism: the first token is sampled from the final-position prefill
logits with ``fold_in(row key, init_counter)`` — counter 0 for fresh rows,
``len(gen)`` for preemption-replayed rows — the identical rule the fused
refill applies, so disaggregated output is token-for-token equal to the
fused path (and to one-shot ``generate()``).

Env-stage resume jobs (rollout/env_stage.py) ride the same path with ONE
difference: their first token is FORCED (the tool response's ``RESP``
opener) instead of sampled — ``forced``/``forced_mask`` select it, and its
logprob is read off the same final-position logits, exactly what the
freeze-in-slot baseline records when it feeds ``CALL`` through a decode
step. Everything downstream (force-feed of the rest of the response,
budget exemption) is the ordinary decode path.
"""
from __future__ import annotations

import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Deque, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ModelConfig
from repro.lora.adapters import batched_ctx
from repro.models import (forward_prefill_chunk, forward_seq, init_cache,
                          lm_logits)
from repro.obs.phase import phase


def _bucket_len(n: int) -> int:
    return int(max(8, -(-int(n) // 8) * 8))


def _sample_rows(logits, keys, counters, temps):
    """Per-row categorical: row i uses fold_in(keys[i], counters[i]).

    The sample depends only on the row's own (key, count, logits) — not on
    batch width or slot position — which is what makes continuous batching
    (and the disaggregated prefill stage) bit-reproduce one-shot generation.
    """
    scaled = logits / jnp.maximum(temps[:, None], 1e-4)

    def one(k, c, row):
        return jax.random.categorical(jax.random.fold_in(k, c), row)

    return jax.vmap(one)(keys, counters, scaled)


def effective_chunk(cfg: ModelConfig, chunk: int) -> int:
    """Round a requested prefill chunk up so chunked == whole-prompt
    bit-for-bit: multiple of 8 (shape buckets) and, for recurrent families,
    of the SSD scan chunk (aligned boundaries decompose exactly). 0 keeps
    chunking off (whole-prompt prefill calls)."""
    if chunk <= 0:
        return 0
    c = _bucket_len(chunk)
    if cfg.ssm is not None:
        s = cfg.ssm.chunk_size
        c = -(-c // s) * s
    return c


class PrefillKernels:
    """The jitted kernels of the prefill stage (shared by all workers).

    ``whole``  — one-call prefill of a full (bucketed) sequence on a fresh
                 width-1 cache; returns (first token, logprob, cache). Same
                 forward + sampling math as the fused refill, minus the
                 splice.
    ``chunk``  — one fixed-size chunk at static offset `start` through
                 ``forward_prefill_chunk`` (jit caches one variant per
                 offset); returns (hidden, cache).
    ``finish`` — final-position logits + first-token sample off the last
                 chunk's hidden states.
    """

    def __init__(self, cfg: ModelConfig, use_kernel: bool, max_len: int):
        self.cfg = cfg
        self.max_len = max_len
        enc = 8 if cfg.family == "encdec" else 0

        def whole(params, adapters, row_ids, tokens, seq_lens, init_counters,
                  keys, temps, forced, forced_mask, fpos, ftoks):
            pcache = init_cache(cfg, tokens.shape[0], max_len, enc_len=enc)
            lora = batched_ctx(adapters, row_ids, cfg, use_kernel)
            h, pcache, _ = forward_seq(params, tokens, cfg, lora, pcache,
                                       seq_lens=seq_lens)
            last = jnp.take_along_axis(
                h, (seq_lens - 1)[:, None, None].astype(jnp.int32),
                axis=1)[:, 0]
            logits = lm_logits(last, params, cfg)
            sampled = _sample_rows(logits, keys, init_counters, temps)
            first = jnp.where(forced_mask > 0, forced,
                              sampled).astype(jnp.int32)
            lp = jnp.take_along_axis(jax.nn.log_softmax(logits, -1),
                                     first[:, None], axis=-1)[:, 0]
            # response-prefill fusion: logprob of each forced token off
            # the logits at the position that predicts it (fpos) — the
            # same values the step-wise force-feed would record
            fh = jnp.take_along_axis(
                h, fpos[:, :, None].astype(jnp.int32), axis=1)
            flogits = lm_logits(fh, params, cfg)
            flp = jnp.take_along_axis(jax.nn.log_softmax(flogits, -1),
                                      ftoks[:, :, None], axis=-1)[:, :, 0]
            return first, lp, flp, pcache

        def chunk(start, params, adapters, row_ids, tokens, seq_lens, pcache):
            lora = batched_ctx(adapters, row_ids, cfg, use_kernel)
            return forward_prefill_chunk(params, tokens, cfg, lora, pcache,
                                         start=start, seq_lens=seq_lens)

        def finish(params, h, last_idx, keys, init_counters, temps, forced,
                   forced_mask):
            last = jnp.take_along_axis(
                h, last_idx[:, None, None].astype(jnp.int32), axis=1)[:, 0]
            logits = lm_logits(last, params, cfg)
            sampled = _sample_rows(logits, keys, init_counters, temps)
            first = jnp.where(forced_mask > 0, forced,
                              sampled).astype(jnp.int32)
            lp = jnp.take_along_axis(jax.nn.log_softmax(logits, -1),
                                     first[:, None], axis=-1)[:, 0]
            return first, lp

        self.whole = jax.jit(whole)
        self.chunk = jax.jit(chunk, static_argnums=(0,),
                             donate_argnums=(6,))
        self.finish = jax.jit(finish)

    def fresh_cache(self):
        return init_cache(self.cfg, 1, self.max_len,
                          enc_len=8 if self.cfg.family == "encdec" else 0)


@dataclass
class ReadyRow:
    """A prefilled row awaiting its scatter-only splice into the pool."""
    row: object              # engine _Row (host-side state)
    seq_len: int             # prompt (+ replayed prefix) length == cache pos
    first: int               # first sampled token (counter = init_counter)
    lp: float                # its logprob
    init_counter: int        # len(gen) at prefill time (0 for fresh rows)
    pcache: dict             # width-1 device cache to splice
    ready_at: float          # queue timestamp: splice latency = now - this
    forced_first: bool = False   # env-stage resume: `first` is the forced
                                 # RESP opener (loss_mask 0), not a sample
    forced_lps: List[float] = field(default_factory=list)
                             # response-prefill fusion: logprobs of the
                             # whole forced RESP…ENDRESP block, prefilled
                             # in the same call (seq_len includes them and
                             # `first` samples AFTER the block)


class _Job:
    """One in-flight prefill: host progress of a chunked row."""
    __slots__ = ("row", "seq", "L", "pcache", "done", "chunks", "spent",
                 "fused")

    def __init__(self, row, fused: int = 0):
        self.row = row
        self.seq = list(row.req.prompt) + row.gen
        self.fused = fused           # forced tokens folded into the prefill
        if fused:
            self.seq += row.forced_q[:fused]
        self.L = len(self.seq)
        self.pcache = None
        self.done = 0
        self.chunks = 0
        self.spent = 0.0


class PrefillWorker(threading.Thread):
    """Async prefill worker: pops scheduler-ordered rows from the engine's
    queue, runs (chunked) prefill on its own caches, emits ReadyRows.

    Backpressure: workers only pop while ready + in-flight rows stay under
    ``max_slots + prefill_workers`` — bounded lookahead keeps device memory
    at O(max_slots) extra caches and bounds priority inversion (a
    higher-priority late arrival waits at most the lookahead window).
    Workers round-robin their jobs one chunk at a time, so the stage stays
    responsive under a single huge prompt.
    """

    def __init__(self, engine, worker_id: int = 0):
        super().__init__(daemon=True,
                         name=f"prefill-worker-{worker_id}")
        self.eng = engine
        self.worker_id = worker_id
        self.last_beat = time.monotonic()  # liveness heartbeat (supervisor)
        self.claimed: List = []  # rows popped but not yet emitted (mutated
                                 # under eng._stage_lock) — the supervisor
                                 # requeues these if this worker dies
        self.chaos_killed = False

    # -- queue interaction (under the engine's stage lock) -----------------
    def _try_pop(self):
        eng = self.eng
        if eng._stacked is None:     # no adapter buffer yet: nothing to
            return None              # prefill against (rows keep queued)
        with eng._stage_lock:
            backlog = len(eng._ready) + len(eng._stage_inflight)
            if backlog >= eng.max_slots + eng.prefill_workers:
                return None
            if not eng._sched:
                return None
            # snapshot-carrying and device-parked rows (paged engine,
            # resume_restore / prefix cache) never prefill: the decode
            # thread splices their saved state back. Radix candidates and
            # GRPO siblings of rows already in this stage also stay queued
            # — the decode thread installs them as shared-page suffix
            # prefills (a sibling popped here would pay a full private
            # prefill the index was about to save).
            where = None
            if (getattr(eng, "resume_restore", False)
                    or getattr(eng, "prefix_cache", False)):
                radix = eng._radix_on()
                seen = set()
                if radix:
                    seen = {eng._group_key(r) for r in eng._stage_inflight}
                    seen |= {eng._group_key(rr.row) for rr in eng._ready}

                def where(r):
                    if r.snap is not None or r.dev_pages is not None:
                        return False
                    if radix and eng._radix_candidate(r) is not None:
                        return False
                    if radix and len(r.req.prompt) >= eng.kv_page_size \
                            and eng._group_key(r) in seen:
                        return False
                    return True
            row = eng._sched.pop(eng.stats.refills, where=where)
            if row is not None:
                eng._book_queue_wait(eng._sched.last_pushed_at)
                eng._stage_inflight.append(row)
                self.claimed.append(row)
        if row is not None and eng._tracer is not None:
            eng._tracer.mark(eng._trace_of(row), "prefill")
        return row

    def _emit(self, job: _Job, first: int, lp: float,
              forced_lps: Optional[List[float]] = None):
        eng = self.eng
        ready = ReadyRow(row=job.row, seq_len=job.L, first=first, lp=lp,
                         init_counter=len(job.row.gen) + job.fused,
                         pcache=job.pcache,
                         ready_at=time.monotonic(),
                         forced_first=bool(job.row.forced_q)
                         and not job.fused,
                         forced_lps=forced_lps or [])
        if eng._tracer is not None:
            eng._tracer.mark(eng._trace_of(job.row), "ready", ready.ready_at)
        with eng._stage_lock:
            if job.row in self.claimed:
                self.claimed.remove(job.row)
            if job.row not in eng._stage_inflight:
                return    # aborted by drain() while we were prefilling
            eng._stage_inflight.remove(job.row)
            eng._ready.append(ready)
            eng.stats.prefill_seconds += job.spent
            eng.stats.prefill_tokens += job.L
            eng.stats.prefill_chunks += job.chunks

    # -- device calls ------------------------------------------------------
    def _advance(self, job: _Job) -> bool:
        """Run ONE prefill call for `job` (whole prompt, or the next chunk);
        returns True when the job is complete."""
        eng = self.eng
        ker = eng._pkernels
        cfg = eng.cfg
        params = eng.base_params
        stacked = eng._stacked           # immutable jax tree; non-donating
                                         # writes keep in-flight readers safe
        row = job.row
        row_id = jnp.asarray([row.req.adapter_index], jnp.int32)
        key = jnp.asarray(row.key[None], jnp.uint32)
        temp = jnp.asarray([row.req.temperature], jnp.float32)
        counter = jnp.asarray([len(row.gen) + job.fused], jnp.int32)
        # env-stage resume: the first spliced token is the forced RESP
        # opener (the response follows via the ordinary force-feed path) —
        # unless the job FUSED the whole forced block into its sequence,
        # in which case `first` is a true sample past the block
        forced = jnp.asarray(
            [row.forced_q[0] if row.forced_q and not job.fused else 0],
            jnp.int32)
        fmask = jnp.asarray([1 if row.forced_q and not job.fused else 0],
                            jnp.int32)
        C = eng._prefill_chunk_eff
        t0 = time.monotonic()

        def booked(done: bool) -> bool:
            now = time.monotonic()
            job.spent += now - t0
            if eng.on_stage is not None:
                eng.on_stage("prefill", row.req.task_id, t0, now)
            if eng._tracer is not None:
                # one span per (chunk or whole-prompt) device call, on
                # this worker's own track
                eng._tracer.span(
                    ("prefill", f"worker-{self.worker_id}"),
                    row.req.task_id, t0, now, trace=eng._trace_of(row))
            return done

        if C == 0 or job.L <= C or cfg.family == "encdec":
            toks = np.zeros((1, _bucket_len(job.L)), np.int32)
            toks[0, :job.L] = job.seq
            F = job.fused
            fpos = np.zeros((1, _bucket_len(F) if F else 1), np.int32)
            ftoks = np.zeros_like(fpos)
            if F:
                L0 = job.L - F
                fpos[0, :F] = np.arange(L0 - 1, L0 - 1 + F)
                ftoks[0, :F] = job.seq[L0:]
            first, lp, flp, job.pcache = ker.whole(
                params, stacked, row_id, jnp.asarray(toks),
                jnp.asarray([job.L], jnp.int32), counter, key, temp,
                forced, fmask, jnp.asarray(fpos), jnp.asarray(ftoks))
            job.chunks += 1
            first = int(np.asarray(first)[0])
            lp = float(np.asarray(lp)[0])
            flps = [float(x) for x in np.asarray(flp)[0, :F]] if F else None
            booked(True)
            self._emit(job, first, lp, flps)
            return True
        if job.pcache is None:
            job.pcache = ker.fresh_cache()
        start = job.done
        end = min(start + C, job.L)
        toks = np.zeros((1, C), np.int32)
        toks[0, :end - start] = job.seq[start:end]
        h, job.pcache = ker.chunk(start, params, stacked, row_id,
                                  jnp.asarray(toks),
                                  jnp.asarray([end - start], jnp.int32),
                                  job.pcache)
        job.done = end
        job.chunks += 1
        if end < job.L:
            return booked(False)
        first, lp = ker.finish(params, h,
                               jnp.asarray([job.L - 1 - start], jnp.int32),
                               key, counter, temp, forced, fmask)
        first = int(np.asarray(first)[0])
        lp = float(np.asarray(lp)[0])
        booked(True)
        self._emit(job, first, lp)
        return True

    # -- main loop ---------------------------------------------------------
    def run(self):
        eng = self.eng
        jobs: Deque[_Job] = deque()
        try:
            while not eng._stage_stop.is_set():
                self.last_beat = time.monotonic()
                # profiler-only phases: the tracer already has a span per
                # device call, with its episode's trace id (_advance)
                with phase("prefill.pop"):
                    row = self._try_pop()
                if row is not None and eng._chaos is not None \
                        and eng._chaos.fire("prefill_worker_kill"):
                    # simulated abrupt death: skip the finally requeue —
                    # the claimed rows stay stranded in _stage_inflight
                    # until the supervisor's recovery requeues them
                    self.chaos_killed = True
                    return
                if row is not None:
                    # response-prefill fusion: fold a resume's whole forced
                    # block into the prefill when the job will run as ONE
                    # whole-sequence call (per-token logprobs come off the
                    # same hidden states; chunked jobs keep the step-wise
                    # force-feed)
                    C = eng._prefill_chunk_eff
                    L_f = row.prompt_len + len(row.gen) + len(row.forced_q)
                    fuse = (getattr(eng, "paged_kv", False)
                            and eng._fusable_forced(row)
                            and (C == 0 or L_f <= C
                                 or eng.cfg.family == "encdec"))
                    jobs.append(_Job(row,
                                     fused=len(row.forced_q) if fuse else 0))
                if not jobs:
                    time.sleep(0.0005)
                    continue
                job = jobs.popleft()         # round-robin: one chunk each
                try:
                    with phase("prefill.chunk"):
                        done = self._advance(job)
                    if not done:
                        jobs.append(job)
                except BaseException as e:   # surface to the engine thread
                    eng._stage_error = e
                    jobs.append(job)         # keep the row accounted for
                    break
        finally:
            # hand unfinished rows back so abort/drain accounting sees them
            # (rows drain() already swept out of _stage_inflight were
            # aborted there — dropping them keeps one completion each);
            # a chaos-killed worker deliberately strands its rows — the
            # supervisor's recovery path is what's under test
            if not self.chaos_killed:
                with eng._stage_lock:
                    for job in jobs:
                        if job.row in eng._stage_inflight:
                            eng._stage_inflight.remove(job.row)
                            eng._sched.push(job.row, eng.stats.refills)
                        if job.row in self.claimed:
                            self.claimed.remove(job.row)
