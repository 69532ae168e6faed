"""Slot-scheduling policies for the continuous engine (paper §4.3/§4.5).

The continuous engine's cross-task request queue was FIFO in PR 1; at high
tenant counts a few long rollouts head-of-line block everyone else (the
skew "RL in the Wild" characterizes). This module provides the ordered pop
that replaces it:

``LengthPredictor`` — per-tenant EMA of *sampled* completion length, fed by
every evicted row. Until a tenant has history its prediction is its request
budget (``max_new_tokens``), so cold tenants are scheduled pessimistically
and converge as rows complete.

``SlotScheduler`` — the queue. Pop order under policy ``"srpt"``:

  1. starvation tier: any entry that has waited ``starvation_k`` refill
     events pops first, FIFO among the starved — every queued tenant is
     guaranteed progress within K refills no matter how many short rows
     keep arriving;
  2. priority tier: higher ``RolloutRequest.priority`` first;
  3. resume tier: env-stage resume jobs (rows re-queued with a pre-loaded
     force-feed queue, see rollout/env_stage.py) pop before fresh rows of
     the same priority — they carry live episode/session state and their
     force-fed response tokens are budget-exempt, so finishing them first
     drains in-flight episodes instead of opening new ones;
  4. shortest-predicted-remaining-budget first (predicted length minus
     tokens already sampled — replayed rows get credit for their prefix);
  5. deterministic tie-break on ``submit_index`` (unique per row).

Policy ``"fifo"`` preserves PR-1 arrival order (the benchmark baseline).
Token streams are unaffected by pop order: sampling is per-row
(key, counter), so any schedule yields the same tokens per request.

With the disaggregated prefill stage (``rollout/prefill.py``) this queue
IS the prefill queue: workers pop in the same scheduler order the fused
refill used, so SRPT/priority/starvation semantics carry over unchanged —
the pop just happens on a prefill worker instead of the decode stream.
The queue itself is not thread-safe; the engine serializes access under
its stage lock.
"""
from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

POLICIES = ("fifo", "srpt")


class LengthPredictor:
    """EMA per-tenant predictor of sampled completion length.

    Thread contract: ``observe`` runs on the rollout thread (every evicted
    row) while the driver thread calls ``predict`` from the admission
    tick's expected-generation estimate — the EMA dict is the one piece of
    scheduler state crossing threads, hence its own lock."""

    def __init__(self, alpha: float = 0.25):
        if not 0.0 < alpha <= 1.0:
            raise ValueError(f"alpha {alpha} outside (0, 1]")
        self.alpha = alpha
        self._lock = threading.Lock()   # guards: _ema
        self._ema: Dict[str, float] = {}

    def observe(self, tenant: str, sampled_tokens: int):
        """Feed one completed row's sampled-token count."""
        x = float(sampled_tokens)
        with self._lock:
            prev = self._ema.get(tenant)
            self._ema[tenant] = x if prev is None else (
                self.alpha * x + (1.0 - self.alpha) * prev)

    def predict(self, tenant: str, budget: int) -> float:
        """Expected sampled length for a row of `tenant` with this budget.

        No history -> the full budget (pessimistic prior); with history the
        EMA, still capped by the budget (a row can never exceed it)."""
        with self._lock:
            e = self._ema.get(tenant)
        return float(budget) if e is None else min(float(budget), e)

    def remaining(self, tenant: str, budget: int, sampled: int) -> float:
        """Predicted sampled tokens still to come for a (possibly replayed)
        row that has already sampled `sampled` of its `budget`."""
        return max(1.0, self.predict(tenant, budget) - float(sampled))


@dataclass
class _Entry:
    row: object          # duck-typed: .req.{task_id,priority,max_new_tokens},
                         # .sampled, .submit_index
    seq: int             # push order (FIFO key)
    enq_refill: int      # engine refill counter at push time (starvation age)
    pushed_at: float     # host clock at push (the row's queue wait starts)


class SlotScheduler:
    """Ordered request queue for the continuous engine's free-slot refill."""

    def __init__(self, policy: str = "srpt",
                 predictor: Optional[LengthPredictor] = None,
                 starvation_k: int = 8):
        if policy not in POLICIES:
            raise ValueError(f"unknown scheduler policy {policy!r}; "
                             f"one of {POLICIES}")
        if starvation_k < 1:
            raise ValueError("starvation_k must be >= 1")
        self.policy = policy
        self.predictor = predictor or LengthPredictor()
        self.starvation_k = starvation_k
        self._entries: List[_Entry] = []
        self._seq = 0
        self.last_pushed_at = 0.0   # push time of the row the last pop
                                    # returned (its queue wait starts there)

    def __len__(self) -> int:
        return len(self._entries)

    def push(self, row, refill_count: int = 0,
             pushed_at: Optional[float] = None):
        """Queue a row. ``pushed_at`` keeps the push time of a row handed
        back unused (pool pressure) so its queue wait runs on."""
        self._entries.append(_Entry(
            row, self._seq, refill_count,
            time.monotonic() if pushed_at is None else pushed_at))
        self._seq += 1

    def _take(self, i: int):
        e = self._entries.pop(i)
        self.last_pushed_at = e.pushed_at
        return e.row

    def _key(self, e: _Entry, refill_count: int):
        if self.policy == "fifo":
            return (e.seq,)
        starved = (refill_count - e.enq_refill) >= self.starvation_k
        if starved:
            # starvation tier wins outright; FIFO among the starved
            return (0, e.seq, 0, 0, 0.0, 0)
        req = e.row.req
        rem = self.predictor.remaining(req.task_id, req.max_new_tokens,
                                       e.row.sampled)
        resume = 0 if getattr(e.row, "forced_q", None) else 1
        return (1, 0, -req.priority, resume, rem, e.row.submit_index)

    def pop(self, refill_count: int = 0, where=None):
        """Remove and return the highest-ranked row, or None if empty.

        `where` (optional row predicate) restricts the pop to matching
        rows — the paged engine uses it to keep snapshot-carrying rows out
        of the prefill/replay path (they restore on the decode thread) and
        vice versa; scheduling order among eligible rows is unchanged."""
        if not self._entries:
            return None
        idxs = (range(len(self._entries)) if where is None else
                [i for i in range(len(self._entries))
                 if where(self._entries[i].row)])
        if not idxs:
            return None
        best = min(idxs,
                   key=lambda i: self._key(self._entries[i], refill_count))
        return self._take(best)

    def pop_if(self, refill_count: int = 0, pred=None):
        """Pop the highest-ranked row ONLY if it satisfies `pred`; returns
        None otherwise (queue untouched). Unlike ``pop(where=)`` this never
        jumps a matching row over better-ranked non-matching ones — the
        paged engine's restore path uses it so a snapshot-carrying row
        resumes when (and only when) it is genuinely next in line, never
        ahead of a higher-priority tenant's fresh rows."""
        if not self._entries:
            return None
        best = min(range(len(self._entries)),
                   key=lambda i: self._key(self._entries[i], refill_count))
        if pred is not None and not pred(self._entries[best].row):
            return None
        return self._take(best)

    def pop_all(self) -> List:
        """Drain every queued row in current pop order (abort path)."""
        out = []
        while self._entries:
            out.append(self.pop())
        return out

    def tenants(self) -> frozenset:
        return frozenset(e.row.req.task_id for e in self._entries)

    def rows_for(self, task_id: str) -> List:
        """A tenant's queued rows (admission re-estimates read `.sampled`
        off preempted rows awaiting replay)."""
        return [e.row for e in self._entries if e.row.req.task_id == task_id]
