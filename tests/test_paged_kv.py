"""Paged KV-cache block pool + snapshot/restore resume (ISSUE 5).

1. Allocator property (hypothesis): any alloc/retain/release interleaving
   preserves the pool invariants — no page owned by two live rows unless
   explicitly retained, free-list conservation, no leaks.
2. Restore-resume parity: the paged engine with snapshot/restore produces
   token-for-token identical output to the dense-cache engine and the
   one-shot oracle, across attention/SSM/hybrid, preempt-at-any-step
   (hypothesis) and through park/resume agentic turns (both fill paths).
3. Snapshot dropped under memory pressure falls back to token replay with
   identical output.
4. Pool exhaustion mid-decode finishes rows via cache-capacity eviction
   (never a crash, never a leak).
5. Cooperative tool-call cancellation frees workers immediately.
6. Page-granular admission packs more rows than max_len reservation.
"""
import random
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:        # property tests skip without hypothesis; the rest still run
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False
requires_hypothesis = pytest.mark.skipif(not HAVE_HYPOTHESIS,
                                         reason="hypothesis not installed")

from conftest import tiny_lm
from repro.data import tokenizer as tok
from repro.envs.base import CancelToken, ToolSession
from repro.envs.tasks import make_env
from repro.lora.adapters import init_lora
from repro.models import init_params
from repro.rollout.engine import (ContinuousRolloutEngine, RolloutEngine,
                                  RolloutRequest, _submit_tool_call)
from repro.rollout.env_stage import EnvStage
from repro.rollout.kvcache import KVSnapshot, PagePool, SnapshotStore, pages_for

FAMILIES = {"attention": "granite-3-2b", "ssm": "mamba2-780m",
            "hybrid": "zamba2-1.2b"}
_CACHE = {}


# ===========================================================================
# 1. allocator invariants
# ===========================================================================

@requires_hypothesis
def test_page_pool_property():
    """Model-based allocator check: a host-side mirror of owner->pages
    tracks every alloc/retain/release; after every op the pool invariants
    hold and no page is owned by two live owners (unless one retained it,
    which models snapshot sharing)."""

    @given(ops=st.lists(st.tuples(st.sampled_from(["alloc", "release",
                                                   "retain"]),
                                  st.integers(0, 5)),
                        min_size=1, max_size=60))
    @settings(max_examples=60, deadline=None)
    def check(ops):
        pool = PagePool(n_pages=12, page_size=8)
        owners = {}             # owner id -> list of pages (rc 1 each)
        shared = []             # pages given an extra rc via retain
        next_id = 0
        for kind, n in ops:
            if kind == "alloc":
                pages = pool.alloc(n)
                if pages is not None:
                    # freshly allocated pages are exclusively owned
                    live = {p for ps in owners.values() for p in ps}
                    assert not (set(pages) & live), "page aliased"
                    assert len(pages) == n      # all-or-nothing
                    owners[next_id] = pages
                    next_id += 1
            elif kind == "release" and owners:
                key = sorted(owners)[n % len(owners)]
                pool.release(owners.pop(key))
            elif kind == "retain" and owners:
                key = sorted(owners)[n % len(owners)]
                pool.retain(owners[key])
                shared.append(list(owners[key]))
            pool.check_invariants()
        for ps in shared:       # drop the snapshot-style extra refs
            pool.release(ps)
        for ps in owners.values():
            pool.release(ps)
        pool.check_invariants()
        assert pool.used_pages == 0 and pool.free_pages == pool.n_pages

    check()


def test_page_pool_basics():
    pool = PagePool(n_pages=4, page_size=16)
    a = pool.alloc(3)
    assert len(a) == 3 and pool.free_pages == 1
    assert pool.alloc(2) is None            # all-or-nothing
    assert pool.free_pages == 1             # refused alloc left no debris
    pool.retain(a)
    pool.release(a)
    assert pool.used_pages == 3             # still held by the retain
    pool.release(a)
    assert pool.used_pages == 0
    with pytest.raises(ValueError):
        pool.release([a[0]])                # double free
    assert pages_for(0, 16) == 0 and pages_for(1, 16) == 1
    assert pages_for(16, 16) == 1 and pages_for(17, 16) == 2


def test_snapshot_store_budget():
    store = SnapshotStore(budget_bytes=100)
    small = KVSnapshot(pos=4, cur=1, ssm=np.zeros(10, np.float32))  # 40 B
    big = KVSnapshot(pos=4, cur=1, ssm=np.zeros(32, np.float32))    # 128 B
    assert store.try_add(small) and store.bytes_used == 40
    assert not store.try_add(big) and store.drops == 1
    store.remove(small)
    assert store.bytes_used == 0


# ===========================================================================
# 2. restore-resume parity (preempt-at-any-step, all families)
# ===========================================================================

def _family(fam: str):
    """(reqs, one-shot reference, reusable PAGED engine) per family."""
    if fam not in _CACHE:
        cfg = tiny_lm(FAMILIES[fam])
        params = init_params(jax.random.PRNGKey(0), cfg)
        trees = [init_lora(jax.random.PRNGKey(1), cfg),
                 init_lora(jax.random.PRNGKey(2), cfg)]
        env = make_env("gsm8k")
        rng = random.Random(7)
        reqs = []
        for i in range(3):
            prompt, truth = env.sample_prompt(rng)
            reqs.append(RolloutRequest(
                f"t{i % 2}", i % 2, prompt, truth, env,
                max_new_tokens=5 + 2 * i, seed=i))
        ref_eng = RolloutEngine(cfg, params, max_len=64, seed=0)
        ref, _ = ref_eng.generate(reqs, trees)       # uninterrupted oracle
        eng = ContinuousRolloutEngine(cfg, params, max_slots=2,
                                      max_adapters=2, max_len=64, seed=0,
                                      paged_kv=True, kv_page_size=16)
        for i, tree in enumerate(trees):
            eng.set_adapters(i, tree)
        _CACHE[fam] = (reqs, ref, eng)
    return _CACHE[fam]


def _drive(eng, reqs, preempt_step, victims):
    pos_of = {eng.submit(r): i for i, r in enumerate(reqs)}
    comps, preempted, iters = {}, 0, 0
    while not eng.idle() and iters < 400:
        eng.step()
        iters += 1
        if iters == preempt_step:
            for v in victims:
                preempted += eng.preempt_tenant(v)
        for c in eng.drain_completions():
            comps[pos_of[c.submit_index]] = c
    assert len(comps) == len(reqs), "engine failed to drain"
    eng.check_page_invariants()
    return comps, preempted


@requires_hypothesis
@pytest.mark.parametrize("fam", sorted(FAMILIES))
def test_restore_resume_parity_property(fam):
    """Preempting at ANY step and restoring the snapshotted pages+state
    yields bit-identical tokens/logprobs to an uninterrupted one-shot run
    — with ZERO prefill replays (restore mode never re-prefills)."""
    reqs, ref, eng = _family(fam)
    observed = {"n": 0}

    @given(preempt_step=st.integers(1, 14),
           victims=st.sampled_from([("t0",), ("t1",), ("t0", "t1")]))
    @settings(max_examples=8, deadline=None)
    def check(preempt_step, victims):
        comps, preempted = _drive(eng, reqs, preempt_step, victims)
        observed["n"] += preempted
        for i, r in enumerate(ref):
            c = comps[i]
            assert list(c.tokens) == r["tokens"], (
                f"{fam}: token mismatch, preempt@{preempt_step}")
            assert list(c.gen_loss_mask) == r["gen_loss_mask"]
            np.testing.assert_allclose(c.gen_logprobs, r["gen_logprobs"],
                                       atol=1e-5)

    check()
    assert observed["n"] > 0
    assert eng.stats.restores > 0
    # attention rows park DEVICE-RESIDENT under the prefix cache (pure
    # retain, zero host bytes); recurrent state still snapshots to host
    assert (eng.stats.snapshots > 0
            or eng.stats.device_resident_resumes > 0)
    assert eng.stats.replays == 0           # restore NEVER replays
    assert eng.stats.replay_tokens == 0
    # no leak: only radix-index retained prompt pages may remain at idle,
    # the snapshot arena is empty
    held = eng._prefix_idx.held_pages if eng._prefix_idx else 0
    assert eng._pages.used_pages == held
    assert eng._snap_store.bytes_used == 0
    eng.check_page_invariants()


# ===========================================================================
# 2b. agentic park/resume restore across both fill paths
# ===========================================================================

@pytest.fixture
def biased_sampler():
    """Deterministic CALL pattern at fixed per-row counters (the
    bench_env_stage trick), restored after the test."""
    import repro.rollout.engine as eng_mod
    import repro.rollout.prefill as pf_mod
    orig = pf_mod._sample_rows

    def biased(logits, keys, counters, temps):
        s = orig(logits, keys, counters, temps)
        s = jnp.where(s == tok.EOS, 10, s)
        hit = (counters == 1) | (counters == 6)
        return jnp.where(hit, tok.CALL, s)

    pf_mod._sample_rows = biased
    eng_mod._sample_rows = biased
    yield
    pf_mod._sample_rows = orig
    eng_mod._sample_rows = orig


def _run_engine(eng, reqs, preempt_at=()):
    pos_of = {eng.submit(r): i for i, r in enumerate(reqs)}
    comps, it = {}, 0
    deadline = time.monotonic() + 120
    while not eng.idle() and time.monotonic() < deadline:
        progressed = eng.step()
        it += 1
        if it in preempt_at:
            eng.preempt_tenant("t0")
            eng.preempt_tenant("t1")
        for c in eng.drain_completions():
            comps[pos_of[c.submit_index]] = c
        if not progressed:
            time.sleep(0.0005)
    assert len(comps) == len(reqs), "engine failed to drain"
    if getattr(eng, "paged_kv", False):
        eng.check_page_invariants()
    return comps


def _agentic_reqs(hops=2):
    env = make_env("hopsearch", kb_size=8, hops=hops, seed=0)
    env.env_latency_mean = 0.0
    rng = random.Random(7)
    reqs = []
    for i in range(4):
        prompt, truth = env.sample_prompt(rng)
        reqs.append(RolloutRequest(f"t{i % 2}", i % 2, prompt, truth, env,
                                   max_new_tokens=10, seed=i))
    return reqs


@pytest.mark.parametrize("fam", ["hybrid", "attention"])
@pytest.mark.parametrize("disagg", [False, True])
def test_park_restore_parity_agentic(fam, disagg, biased_sampler):
    """Multi-turn episodes (park on CALL, resume on response) restore
    token-for-token identically to the dense-cache engine on the SAME
    schedule — fused and disaggregated fill paths, preempt mid-episode
    included (preempt-during-parked rows keep their snapshots)."""
    cfg = tiny_lm(FAMILIES[fam])
    params = init_params(jax.random.PRNGKey(0), cfg)
    trees = [init_lora(jax.random.PRNGKey(1), cfg),
             init_lora(jax.random.PRNGKey(2), cfg)]
    reqs = _agentic_reqs()

    outs, stats = {}, {}
    for mode in ("dense", "paged"):
        eng = ContinuousRolloutEngine(
            cfg, params, max_slots=2, max_adapters=2, max_len=96, seed=0,
            paged_kv=(mode == "paged"), kv_page_size=16,
            env_stage=True, env_workers=2, disagg_prefill=disagg)
        for i, tree in enumerate(trees):
            eng.set_adapters(i, tree)
        outs[mode] = _run_engine(eng, reqs, preempt_at=(6, 14))
        stats[mode] = eng.stats
        if mode == "paged":
            assert eng._pages.used_pages == 0       # no leak at idle
            assert eng._snap_store.bytes_used == 0
        eng.shutdown()
    for i in range(len(reqs)):
        d, p = outs["dense"][i], outs["paged"][i]
        assert list(d.tokens) == list(p.tokens), (fam, disagg, i)
        assert list(d.gen_loss_mask) == list(p.gen_loss_mask)
        np.testing.assert_allclose(d.gen_logprobs, p.gen_logprobs,
                                   atol=1e-5)
    assert stats["paged"].parks > 0 and stats["paged"].resumes > 0
    assert stats["paged"].restores > 0
    assert stats["paged"].replay_tokens == 0        # the tentpole claim
    assert stats["dense"].replay_tokens > 0         # baseline recomputes


def test_snapshot_drop_falls_back_to_replay(biased_sampler):
    """snapshot_budget_bytes=1 rejects every snapshot: all resumes fall
    back to token replay, with output identical to restore mode."""
    cfg = tiny_lm("zamba2-1.2b")
    params = init_params(jax.random.PRNGKey(0), cfg)
    trees = [init_lora(jax.random.PRNGKey(1), cfg),
             init_lora(jax.random.PRNGKey(2), cfg)]
    reqs = _agentic_reqs()
    outs, stats = {}, {}
    for mode, budget in (("restore", 0), ("dropped", 1)):
        eng = ContinuousRolloutEngine(
            cfg, params, max_slots=2, max_adapters=2, max_len=96, seed=0,
            paged_kv=True, kv_page_size=16, env_stage=True, env_workers=2,
            snapshot_budget_bytes=budget)
        for i, tree in enumerate(trees):
            eng.set_adapters(i, tree)
        outs[mode] = _run_engine(eng, reqs)
        stats[mode] = eng.stats
        eng.shutdown()
    for i in range(len(reqs)):
        a, b = outs["restore"][i], outs["dropped"][i]
        assert list(a.tokens) == list(b.tokens)
        np.testing.assert_allclose(a.gen_logprobs, b.gen_logprobs,
                                   atol=1e-5)
    assert stats["restore"].restores > 0 and stats["restore"].replays == 0
    assert stats["dropped"].restores == 0 and stats["dropped"].replays > 0
    assert stats["dropped"].snapshot_drops > 0


# ===========================================================================
# 4. pool exhaustion mid-decode
# ===========================================================================

def test_pool_exhaustion_finishes_rows():
    """A pool too small for every row's growth finishes rows via
    cache-capacity eviction: every submitted row completes, nothing
    crashes, and the free list is conserved."""
    cfg = tiny_lm("granite-3-2b")
    params = init_params(jax.random.PRNGKey(0), cfg)
    tree = init_lora(jax.random.PRNGKey(1), cfg)
    env = make_env("gsm8k")
    rng = random.Random(3)
    reqs = []
    for i in range(6):
        prompt, truth = env.sample_prompt(rng)
        reqs.append(RolloutRequest("t0", 0, prompt, truth, env,
                                   max_new_tokens=40, seed=i))
    # 3 pages x 8 tokens for 2 slots: prompts fit (1-2 pages) but growth
    # past the page boundary starves the pool mid-decode
    eng = ContinuousRolloutEngine(cfg, params, max_slots=2, max_adapters=1,
                                  max_len=64, seed=0, paged_kv=True,
                                  kv_page_size=8, kv_pool_pages=3)
    eng.set_adapters(0, tree)
    comps = _run_engine(eng, reqs)
    assert len(comps) == len(reqs)
    reasons = {c.finish_reason for c in comps.values()}
    assert eng.stats.pool_exhausted > 0 and "capacity" in reasons
    assert eng._pages.used_pages == 0       # everything released
    eng._pages.check_invariants()


def test_row_larger_than_pool_finishes_capacity():
    """A row whose prompt alone exceeds the whole pool can never fit: it
    must finish (capacity), not deadlock the queue."""
    cfg = tiny_lm("granite-3-2b")
    params = init_params(jax.random.PRNGKey(0), cfg)
    tree = init_lora(jax.random.PRNGKey(1), cfg)
    env = make_env("gsm8k")
    rng = random.Random(3)
    prompt, truth = env.sample_prompt(rng)
    prompt = prompt + [5] * (20 - len(prompt))       # 20 tokens > 2 pages
    eng = ContinuousRolloutEngine(cfg, params, max_slots=2, max_adapters=1,
                                  max_len=64, seed=0, paged_kv=True,
                                  kv_page_size=8, kv_pool_pages=2)
    eng.set_adapters(0, tree)
    comps = _run_engine(eng, [RolloutRequest("t0", 0, prompt, truth, env,
                                             max_new_tokens=4, seed=0)])
    assert comps[0].finish_reason == "capacity"


def test_kv_page_counters_count_what_the_decode_kernel_reads():
    """Over every decode step, ``kv_live_pages`` sums each decoded row's
    live pages, ceil(pos / page) with pos counting the step's own token,
    and ``kv_table_pages`` sums decoded rows × block-table width: checked
    against the pos and advance mask the device step is handed."""
    cfg = tiny_lm("granite-3-2b")
    params = init_params(jax.random.PRNGKey(0), cfg)
    env = make_env("gsm8k")
    rng = random.Random(5)
    reqs = []
    for i, n in enumerate([3, 9, 17, 26]):       # prompts across 1-4 pages
        prompt, truth = env.sample_prompt(rng)
        prompt = (prompt + [5] * n)[:n]
        reqs.append(RolloutRequest("t0", 0, prompt, truth, env,
                                   max_new_tokens=20, seed=i))
    eng = ContinuousRolloutEngine(cfg, params, max_slots=4, max_adapters=1,
                                  max_len=64, seed=0, paged_kv=True,
                                  kv_page_size=8)
    eng.set_adapters(0, init_lora(jax.random.PRNGKey(1), cfg))
    seen = {"live": 0, "table": 0}
    step_fn = eng._step_fn

    def spy(*args):
        cache, advance = args[4], np.asarray(args[10]) > 0
        pos = np.asarray(cache["pos"])[advance] + 1
        seen["live"] += int(np.sum(-(-pos // 8)))
        seen["table"] += int(advance.sum()) * cache["tbl"].shape[1]
        return step_fn(*args)

    eng._step_fn = spy
    _run_engine(eng, reqs)
    st = eng.stats
    assert st.decode_steps > 0 and 0 < seen["live"] < seen["table"]
    assert st.kv_live_pages == seen["live"]
    assert st.kv_table_pages == seen["table"] == st.occupied_row_steps * 8


# ===========================================================================
# 5. cooperative tool-call cancellation
# ===========================================================================

class _SlowSession(ToolSession):
    def __init__(self):
        self.calls = 0

    def call(self, query_ids, cancel=None):
        self.calls += 1
        return [1, 2]


def test_env_stage_cancel_frees_worker_immediately():
    """A timed-out job mid latency-sleep releases its worker NOW: a
    second job completes far sooner than the first job's latency."""
    class _Row:
        session = _SlowSession()
    stage = EnvStage(1)                      # ONE worker: job B must wait
    t0 = time.monotonic()                    # for job A's worker
    stage.submit(_Row(), [1], "a", latency=30.0)
    time.sleep(0.05)                         # let the worker pick A up
    stage.expire(time.monotonic() + 100.0, 1.0)   # time A out -> cancel
    rb = _Row()
    stage.submit(rb, [2], "b", latency=0.0)
    deadline = time.monotonic() + 5.0
    done = []
    while not done and time.monotonic() < deadline:
        done = stage.drain_resolved()
        time.sleep(0.005)
    elapsed = time.monotonic() - t0
    stage.halt()
    assert done and done[0].row is rb
    assert elapsed < 5.0, f"worker stayed pinned for {elapsed:.1f}s"


def test_submit_tool_call_token_interrupts_latency():
    """Cancelling the freeze-in-slot path's token interrupts the latency
    sleep and skips the session call."""
    from concurrent.futures import ThreadPoolExecutor

    class _Req:
        class env:
            @staticmethod
            def sample_env_latency(rng):
                return 30.0
        task_id = "t"

    class _FakeRow:
        req = _Req()
        gen = [1]
        session = _SlowSession()

        def ensure_session(self):
            return self.session

    pool = ThreadPoolExecutor(max_workers=1)
    rng = np.random.RandomState(0)
    t0 = time.monotonic()
    fut, token = _submit_tool_call(_FakeRow(), [1, 2], pool, rng, False)
    time.sleep(0.05)
    token.cancel()
    assert fut.result(timeout=5.0) == []
    assert time.monotonic() - t0 < 5.0
    assert _FakeRow.session.calls == 0       # never reached the session
    pool.shutdown(wait=False)


def test_cancel_token_forwarding_legacy_session():
    """call_session forwards the token to sessions that accept it and
    still works with legacy sessions that don't."""
    from repro.envs.base import call_session

    class Legacy:
        def call(self, q):
            return [7]

    class Modern:
        def __init__(self):
            self.got = None

        def call(self, q, cancel=None):
            self.got = cancel
            return [8]

    tok_ = CancelToken()
    assert call_session(Legacy(), [1], tok_) == [7]
    m = Modern()
    assert call_session(m, [1], tok_) == [8]
    assert m.got is tok_


# ===========================================================================
# 6. page-granular admission
# ===========================================================================

def test_paged_admission_packs_tighter():
    from repro.configs import REGISTRY
    from repro.core.admission import (AdmissionConfig, AdmissionController,
                                      task_state_bytes,
                                      task_state_bytes_paged)
    from repro.core.manager import TaskSpec
    cfg = REGISTRY["granite-3-2b"]
    spec = TaskSpec("t", "gsm8k", group_size=8, num_groups=2,
                    max_new_tokens=512)
    dense = task_state_bytes(cfg, spec, 64)
    cold = task_state_bytes_paged(cfg, spec, 64, page_size=16)
    warm = task_state_bytes_paged(cfg, spec, 64, page_size=16,
                                  expected_new_tokens=48.0)
    # cold (no history) stays pessimistic; warm packs far tighter
    assert abs(cold - dense) / dense < 0.05
    assert warm < 0.3 * dense
    # the controller admits more tasks under the same budget when paged
    budget = 4 * dense
    dense_ctl = AdmissionController(cfg, AdmissionConfig(
        memory_budget_bytes=budget))
    paged_ctl = AdmissionController(cfg, AdmissionConfig(
        memory_budget_bytes=budget, paged=True, page_size=16))
    n_dense = n_paged = 0
    for i in range(64):
        s = TaskSpec(f"d{i}", "gsm8k", group_size=8, num_groups=2,
                     max_new_tokens=512)
        if dense_ctl.try_admit(s, 64):
            n_dense += 1
    for i in range(64):
        s = TaskSpec(f"p{i}", "gsm8k", group_size=8, num_groups=2,
                     max_new_tokens=512)
        if paged_ctl.try_admit(s, 64, expected_new_tokens=48.0):
            n_paged += 1
    assert n_paged >= 1.5 * n_dense
    # actual-bytes readmission re-estimate only ever tightens
    paged_ctl.preempt("p0")
    first = paged_ctl.reestimate_preempted_bytes("p0", 10_000)
    assert first == 10_000
    assert paged_ctl.reestimate_preempted_bytes("p0", 50_000) == 10_000
