"""Fault-tolerant stage supervision under deterministic chaos (ISSUE 10).

Unit-level: the seeded per-site chaos streams (replayable fault scripts,
per-site caps), the tenant circuit breaker's full state machine on an
injectable clock, checkpoint publish safety (replace-aside, retention,
LATEST scan fallback, torn-publish recovery, async episode round-trip),
env-stage tool-call retry semantics (transient backoff, budgets,
permanent errors, injected faults, worker-death recovery), the RA106
swallowed-exception checker, and the fault sections of the metrics
recorder / trace report.

Runtime-level (slow): the chaos matrix on the real engine — stage-worker
kills recovered by the supervisor, transient tool errors retried to a
bit-identical token stream, permanent tool errors tripping quarantine
through recovery or abandonment — each asserting the extended row
conservation invariant EXACTLY:

    completed == trained + stale_dropped + discarded_tails
                 + failed + quarantine_dropped + orphaned
"""
import os
import time
from types import SimpleNamespace

import numpy as np
import pytest

from repro.core.chaos import ChaosConfig, ChaosError, ChaosInjector
from repro.core.manager import MultiTaskManager, TaskSpec
from repro.core.supervisor import (ABANDONED, CLOSED, HALF_OPEN, OPEN,
                                   TenantBreaker)
from repro.envs.base import PermanentToolError, TransientToolError


def _assert_accounting(rt):
    """The extended PR-7 conservation invariant, exactly."""
    acc = rt.row_accounting()
    assert acc["completed"] == (
        acc["trained"] + acc["stale_dropped"] + acc["discarded_tails"]
        + acc["failed"] + acc["quarantine_dropped"] + acc["orphaned"]), acc


# -- chaos injector -------------------------------------------------------

def test_chaos_streams_are_deterministic_and_per_site():
    cfg = ChaosConfig(seed=7, env_worker_kill=0.5, tool_error_transient=0.5)
    a, b = ChaosInjector(cfg), ChaosInjector(cfg)
    for site in ("env_worker_kill", "tool_error_transient"):
        assert [a.fire(site) for _ in range(64)] \
            == [b.fire(site) for _ in range(64)]
    # interleaving one site's draws must not perturb the other's stream
    c = ChaosInjector(cfg)
    mixed = []
    for _ in range(64):
        c.fire("tool_error_transient")
        mixed.append(c.fire("env_worker_kill"))
    d = ChaosInjector(cfg)
    assert mixed == [d.fire("env_worker_kill") for _ in range(64)]
    assert a.counts() == b.counts() and sum(a.counts().values()) > 0


def test_chaos_rate_edges_and_cap():
    inj = ChaosInjector(ChaosConfig(seed=0, prefill_worker_kill=1.0,
                                    max_faults_per_site=3))
    assert [inj.fire("prefill_worker_kill") for _ in range(10)] \
        == [True] * 3 + [False] * 7          # cap is exact
    assert inj.counts() == {"prefill_worker_kill": 3}
    assert not inj.fire("snapshot_drop")     # rate 0.0 never fires
    with pytest.raises(ValueError):
        inj.fire("not_a_site")


def test_chaos_config_enabled_gate():
    assert not ChaosConfig().enabled
    assert ChaosConfig(torn_checkpoint=0.1).enabled


# -- tenant circuit breaker -----------------------------------------------

def test_breaker_trips_cools_down_and_recovers():
    now = [0.0]
    b = TenantBreaker(fail_threshold=2, cooldown_s=1.0, max_trips=3,
                      clock=lambda: now[0])
    b.record_failure("t")
    assert b.poll() == [] and b.state("t") == CLOSED
    b.record_success("t")                    # success resets the streak
    b.record_failure("t")
    b.record_failure("t")
    assert b.poll() == [("t", OPEN)] and b.state("t") == OPEN
    assert b.poll() == []                    # cooldown not elapsed
    now[0] = 1.5
    assert b.poll() == [("t", HALF_OPEN)]
    b.record_success("t")                    # clean probe: full recovery
    assert b.poll() == [("t", CLOSED)]
    assert b.snapshot() == {}                # closed tenants don't surface


def test_breaker_retrip_abandon_and_straggler_noop():
    now = [0.0]
    b = TenantBreaker(fail_threshold=1, cooldown_s=1.0, max_trips=1,
                      clock=lambda: now[0])
    b.record_failure("t")
    assert b.poll() == [("t", OPEN)]
    # stragglers of the tripped tenant land while open: must not re-trip
    b.record_failure("t")
    b.record_failure("t")
    assert b.poll() == []
    now[0] = 2.0
    assert b.poll() == [("t", HALF_OPEN)]
    b.record_failure("t")                    # probe failed: trips(2) > 1
    assert b.poll() == [("t", ABANDONED)]
    b.record_failure("t")                    # terminal: further events noop
    b.record_success("t")
    assert b.poll() == [] and b.state("t") == ABANDONED
    assert b.snapshot() == {"t": ABANDONED}


def test_breaker_abandons_immediately_with_zero_trip_budget():
    b = TenantBreaker(fail_threshold=1, cooldown_s=1.0, max_trips=0)
    b.record_failure("t")
    assert b.poll() == [("t", ABANDONED)]


# -- checkpoint store: safe publish / retention / recovery ----------------

def _ck_mgr(**kw):
    m = MultiTaskManager(async_mode=True, max_staleness=1, **kw)
    m.submit(TaskSpec("t", "gsm8k", group_size=2, num_groups=2,
                      target_steps=100))
    m.admit("t")
    return m


def _ep(version, submit_index):
    return SimpleNamespace(version=version, submit_index=submit_index,
                           env=None, meta={})


def test_checkpoint_replace_leaves_no_aside(tmp_path):
    from repro.checkpoint.store import latest_checkpoint, save_checkpoint
    d = str(tmp_path)
    save_checkpoint(d, _ck_mgr(), step_tag="s")
    p = save_checkpoint(d, _ck_mgr(), step_tag="s")   # replace same tag
    assert latest_checkpoint(d) == p
    assert not [n for n in os.listdir(d) if n.endswith(".replacing")]


def test_checkpoint_keep_last_n_prunes_oldest(tmp_path):
    from repro.checkpoint.store import latest_checkpoint, save_checkpoint
    d = str(tmp_path)
    for i in range(4):
        time.sleep(0.01)                     # distinct manifest times
        p = save_checkpoint(d, _ck_mgr(), step_tag=f"s{i}", keep_last_n=2)
    snaps = sorted(n for n in os.listdir(d)
                   if os.path.isdir(os.path.join(d, n)))
    assert snaps == ["s2", "s3"]
    assert latest_checkpoint(d) == p


def test_latest_checkpoint_scans_when_pointer_is_bad(tmp_path):
    from repro.checkpoint.store import latest_checkpoint, save_checkpoint
    d = str(tmp_path)
    save_checkpoint(d, _ck_mgr(), step_tag="old")
    time.sleep(0.01)
    newest = save_checkpoint(d, _ck_mgr(), step_tag="new")
    os.remove(os.path.join(d, "LATEST"))     # missing pointer -> scan
    assert latest_checkpoint(d) == newest
    with open(os.path.join(d, "LATEST"), "w") as f:
        f.write("no_such_snapshot")          # dangling pointer -> scan
    assert latest_checkpoint(d) == newest


def test_torn_checkpoint_falls_back_to_previous_snapshot(tmp_path):
    from repro.checkpoint.store import latest_checkpoint, save_checkpoint
    d = str(tmp_path)
    good = save_checkpoint(d, _ck_mgr(), step_tag="a")
    time.sleep(0.01)
    chaos = ChaosInjector(ChaosConfig(seed=0, torn_checkpoint=1.0,
                                      max_faults_per_site=1))
    with pytest.raises(ChaosError):
        save_checkpoint(d, _ck_mgr(), step_tag="b", chaos=chaos)
    # LATEST still points at `a`; the torn `b` dir has no manifest
    assert latest_checkpoint(d) == good
    # retry (cap exhausted -> no fault) publishes over the torn dir
    fixed = save_checkpoint(d, _ck_mgr(), step_tag="b", chaos=chaos)
    assert latest_checkpoint(d) == fixed


def test_checkpoint_async_episode_roundtrip(tmp_path):
    from repro.checkpoint.store import load_checkpoint, save_checkpoint
    m = _ck_mgr(min_train_rows=1)
    for g in range(2):                       # two complete groups...
        for i in range(2):
            m.enqueue_episode("t", 0, (0, g), _ep(0, g * 2 + i))
    popped_tid, popped = m.pop_episodes()    # ...popped but uncommitted
    m.enqueue_episode("t", 0, (0, 7), _ep(0, 8))
    m.enqueue_episode("t", 0, (0, 7), _ep(0, 9))   # one still queued
    m.tasks["t"].status = "quarantined"
    m.tasks["t"].failed_rows = 3
    m.failed_rows = 3
    m.quarantine_dropped_rows = 5
    path = save_checkpoint(str(tmp_path), m, step_tag="s")
    m2 = MultiTaskManager(async_mode=True, max_staleness=1)
    load_checkpoint(path, m2)
    # in-flight work restored at the queue head, same recover order
    assert [g.seq for g in m2.episodes["t"]] \
        == [g.seq for g in popped] + [g.seq for g in m.episodes["t"]]
    assert m2.ready_rows("t") == 6
    assert not m2._inflight_train            # restored AS queued work
    # fault counters survive the restart (invariant holds across it)
    assert m2.failed_rows == 3 and m2.tasks["t"].failed_rows == 3
    assert m2.quarantine_dropped_rows == 5
    # quarantine does not survive restart: no breaker would clear it
    assert m2.tasks["t"].status == "admitted"
    assert all(c.env is None for g in m2.episodes["t"] for c in g.rows)
    env = object()
    assert m2.rebind_episode_envs({"t": env}) == 6
    assert all(c.env is env for g in m2.episodes["t"] for c in g.rows)


def test_load_checkpoint_orphans_unserializable_completed_rows(tmp_path):
    """Rows completed before the crash whose round never assembled into a
    serialized batch/group regenerate after restart (their round
    re-issues) — load must attribute the lost copies to `orphaned_rows`
    so the conservation invariant stays exact across incarnations."""
    from repro.checkpoint.store import load_checkpoint, save_checkpoint
    m = _ck_mgr(min_train_rows=1)
    m.enqueue_episode("t", 0, (0, 0), _ep(0, 0))
    m.enqueue_episode("t", 0, (0, 0), _ep(0, 1))     # 2 rows survive
    m.rows_trained = 4                               # 2 commits survived
    m.tasks["t"].rollout_rows_total = 8              # ...but 8 completed
    path = save_checkpoint(str(tmp_path), m, step_tag="s")
    m2 = MultiTaskManager(async_mode=True, max_staleness=1)
    load_checkpoint(path, m2)
    # 8 completed = 4 trained + 2 in queues + 2 lost-to-the-crash
    assert m2.rows_trained == 4
    assert m2.orphaned_rows == 2
    # a second save/load round-trip must not re-count the same orphans
    path2 = save_checkpoint(str(tmp_path), m2, step_tag="s2")
    m3 = MultiTaskManager(async_mode=True, max_staleness=1)
    load_checkpoint(path2, m3)
    assert m3.orphaned_rows == 2


# -- env-stage tool-call retry / worker recovery --------------------------

class _FlakySession:
    """Session failing the first `fail` calls; optionally permanently."""

    def __init__(self, fail=0, permanent=False):
        self.fail = fail
        self.permanent = permanent
        self.calls = 0

    def call(self, query_ids, cancel=None):
        self.calls += 1
        if self.permanent:
            raise PermanentToolError("endpoint down")
        if self.calls <= self.fail:
            raise TransientToolError("flaky")
        return [4, 2]


def _stage(**kw):
    from repro.rollout.env_stage import EnvStage
    kw.setdefault("retry_base_s", 0.001)
    kw.setdefault("retry_max_s", 0.01)
    return EnvStage(n_workers=1, **kw)


def _drain_one(stage, deadline_s=10.0):
    t0 = time.monotonic()
    while time.monotonic() - t0 < deadline_s:
        out = stage.drain_resolved()
        if out:
            return out[0]
        time.sleep(0.002)
    raise AssertionError("env stage never resolved the job")


def test_env_stage_retries_transient_then_succeeds():
    stage = _stage(retry_max=3)
    sess = _FlakySession(fail=2)
    row = SimpleNamespace(session=sess, tool_retries=0)
    stage.submit(row, [1, 2], "t", latency=0.0)
    job = _drain_one(stage)
    assert job.error is None and job.response == [4, 2]
    assert sess.calls == 3 and stage.retries == 2
    assert row.tool_retries == 2
    stage.halt(timeout_s=10.0)


def test_env_stage_fails_row_when_retry_budget_spent():
    stage = _stage(retry_max=2)
    sess = _FlakySession(fail=99)
    stage.submit(SimpleNamespace(session=sess, tool_retries=0),
                 [1], "t", latency=0.0)
    job = _drain_one(stage)
    assert isinstance(job.error, TransientToolError)
    assert job.response == [] and sess.calls == 3     # 1 try + 2 retries
    stage.halt(timeout_s=10.0)


def test_env_stage_permanent_error_fails_without_retry():
    stage = _stage(retry_max=3)
    sess = _FlakySession(permanent=True)
    stage.submit(SimpleNamespace(session=sess, tool_retries=0),
                 [1], "t", latency=0.0)
    job = _drain_one(stage)
    assert isinstance(job.error, PermanentToolError)
    assert sess.calls == 1 and stage.retries == 0
    stage.halt(timeout_s=10.0)


def test_env_stage_episode_retry_cap_bounds_flapping_rows():
    stage = _stage(retry_max=5, retry_episode_cap=1)
    sess = _FlakySession(fail=99)
    row = SimpleNamespace(session=sess, tool_retries=0)
    stage.submit(row, [1], "t", latency=0.0)
    job = _drain_one(stage)
    assert isinstance(job.error, TransientToolError)
    assert row.tool_retries == 1                      # cap, not retry_max
    stage.halt(timeout_s=10.0)


def test_env_stage_injected_transient_fault_passes_through():
    chaos = ChaosInjector(ChaosConfig(seed=0, tool_error_transient=1.0,
                                      transient_fail_count=2,
                                      max_faults_per_site=1))
    stage = _stage(retry_max=3, chaos=chaos)
    sess = _FlakySession()
    stage.submit(SimpleNamespace(session=sess, tool_retries=0),
                 [1], "t", latency=0.0)
    job = _drain_one(stage)
    # both injected failures precede any real call; the retry then lands
    assert job.error is None and job.response == [4, 2]
    assert sess.calls == 1 and stage.retries == 2
    assert chaos.counts() == {"tool_error_transient": 1}
    stage.halt(timeout_s=10.0)


def test_env_stage_recovers_job_from_chaos_killed_worker():
    chaos = ChaosInjector(ChaosConfig(seed=0, env_worker_kill=1.0,
                                      max_faults_per_site=1))
    stage = _stage(chaos=chaos)
    sess = _FlakySession()
    stage.submit(SimpleNamespace(session=sess, tool_retries=0),
                 [1], "t", latency=0.0)
    t0 = time.monotonic()
    while stage.healthy() and time.monotonic() - t0 < 10.0:
        time.sleep(0.002)
    assert not stage.healthy()               # worker died mid-job
    assert stage.recover_dead() == 1         # stranded job re-queued
    stage._ensure_workers()                  # supervisor respawn path
    job = _drain_one(stage)
    assert job.error is None and job.response == [4, 2]
    assert stage.recovered == 1 and sess.calls == 1
    stage.halt(timeout_s=10.0)


# -- RA106: swallowed exceptions in worker run() loops --------------------

_RA106_BAD = '''
import threading

class W(threading.Thread):
    def run(self):
        while True:
            try:
                self.step()
            except:
                pass

class X(threading.Thread):
    def run(self):
        try:
            self.step()
        except Exception:
            return
'''

_RA106_GOOD = '''
import threading

class Y(threading.Thread):
    def run(self):
        try:
            self.step()
        except ValueError:
            pass                    # narrow taxonomy: never flagged
        try:
            self.step()
        except Exception as e:
            self.error = e          # recorded for the supervisor
        try:
            self.step()
        except BaseException:
            raise

class NotAWorker:
    def run(self):
        try:
            self.step()
        except Exception:
            pass                    # not a Thread subclass
'''


def test_ra106_flags_swallowed_worker_exceptions(tmp_path):
    from repro.analysis.core import collect_files
    from repro.analysis.robustness import check
    bad = tmp_path / "bad.py"
    bad.write_text(_RA106_BAD)
    good = tmp_path / "good.py"
    good.write_text(_RA106_GOOD)
    findings = check(collect_files([str(bad), str(good)]))
    assert sorted(f.rule for f in findings) == ["RA106", "RA106"]
    assert all(f.file == "bad.py" for f in findings)
    msgs = " ".join(f.message for f in findings)
    assert "W.run()" in msgs and "X.run()" in msgs


def test_ra106_runs_in_the_analysis_gate(tmp_path):
    from repro.analysis.core import analyze_paths
    bad = tmp_path / "worker.py"
    bad.write_text(_RA106_BAD)
    findings, _ = analyze_paths([str(bad)])
    assert any(f.rule == "RA106" for f in findings)


# -- observability: breaker timeline + trace fault report -----------------

def test_metrics_breaker_timeline():
    from repro.core.metrics import MetricsRecorder
    rec = MetricsRecorder({"rollout": 1})
    rec.record_breaker_sample(1.0, "a", OPEN)
    rec.record_breaker_sample(2.0, "b", OPEN)
    rec.record_breaker_sample(3.0, "a", CLOSED)
    assert rec.breaker_timeline("a") == [(1.0, "a", OPEN),
                                         (3.0, "a", CLOSED)]
    assert len(rec.breaker_timeline()) == 3


def test_report_load_faults_from_trace():
    from repro.obs.report import load_faults
    trace = {"traceEvents": [
        {"ph": "M", "name": "thread_name", "pid": 1, "tid": 5,
         "args": {"name": "supervisor:env_worker"}},
        {"ph": "i", "cat": "supervisor", "pid": 1, "tid": 5,
         "name": "restart", "ts": 0},
        {"ph": "i", "cat": "supervisor", "pid": 1, "tid": 5,
         "name": "restart", "ts": 1},
        {"ph": "i", "cat": "supervisor", "pid": 1, "tid": 9,
         "name": "hop:open", "ts": 2},
        {"ph": "i", "cat": "supervisor", "pid": 1, "tid": 9,
         "name": "hop:half_open", "ts": 3},
        {"ph": "i", "cat": "other", "pid": 1, "tid": 9,
         "name": "ignored", "ts": 4},
    ]}
    out = load_faults(trace)
    assert out["stage_restarts"] == {"supervisor:env_worker": 2}
    assert out["breaker_transitions"] == {"hop": ["open", "half_open"]}


# -- runtime chaos matrix (real engine, slow) -----------------------------

_CACHE = {}


def _force_calls(monkeypatch, call_at=2):
    """Deterministic forced-CALL pattern (the bench_async_train idiom):
    every row samples CALL at token counter `call_at` (a plain token for
    non-agentic tenants) and EOS is remapped away. Tool calls must not
    depend on what the randomly-initialized model happens to sample."""
    import jax.numpy as jnp

    import repro.rollout.engine as eng_mod
    import repro.rollout.prefill as pf_mod
    from repro.data import tokenizer as tok
    orig = pf_mod._sample_rows

    def biased(logits, keys, counters, temps):
        s = orig(logits, keys, counters, temps)
        s = jnp.where(s == tok.EOS, 10, s)
        return jnp.where(counters == call_at, tok.CALL, s)

    monkeypatch.setattr(pf_mod, "_sample_rows", biased)
    monkeypatch.setattr(eng_mod, "_sample_rows", biased)


def _chaos_runtime(seed=3, chaos=None, **over):
    """The test_env_stage e2e config (agentic + plain tenant, all three
    stages disaggregated) with a chaos script layered on."""
    import jax
    from conftest import tiny_lm
    from repro.core.runtime import MARLaaSRuntime, RuntimeConfig
    from repro.models import init_params
    if "p" not in _CACHE:
        cfg = tiny_lm("granite-3-2b")
        _CACHE["p"] = (cfg, init_params(jax.random.PRNGKey(0), cfg))
    cfg, params = _CACHE["p"]
    rcfg = RuntimeConfig(policy="marlaas", max_len=64, seed=seed,
                         max_slots=4, disagg_prefill=True, prefill_workers=1,
                         env_stage=True, env_workers=2, max_turns=2,
                         chaos=chaos, tool_retry_base_s=0.01,
                         tool_retry_max_s=0.05, **over)
    rt = MARLaaSRuntime(cfg, params, rcfg)
    rt.submit_task(TaskSpec("hop", "hopsearch", group_size=2, num_groups=1,
                            max_new_tokens=6, target_steps=2))
    rt.submit_task(TaskSpec("gsm", "gsm8k", group_size=2, num_groups=1,
                            max_new_tokens=4, target_steps=2))
    return rt


@pytest.mark.slow
def test_runtime_survives_stage_worker_kills(monkeypatch):
    """Prefill + env workers killed mid-job: the supervisor recovers the
    stranded work and respawns; the run still completes and every row is
    accounted for."""
    _force_calls(monkeypatch)
    rt = _chaos_runtime(chaos=ChaosConfig(
        seed=0, prefill_worker_kill=1.0, env_worker_kill=1.0,
        max_faults_per_site=1))
    rt.run(timeout_s=300.0)
    assert rt.error is None
    assert all(st.done for st in rt.mgr.tasks.values())
    c = rt.rec.counters_snapshot()
    assert rt.chaos.counts().get("prefill_worker_kill") == 1
    assert rt.chaos.counts().get("env_worker_kill") == 1
    assert c.get("supervisor_prefill_worker_restarts", 0) >= 1
    assert c.get("supervisor_env_worker_restarts", 0) >= 1
    assert c.get("supervisor_env_worker_jobs_recovered", 0) >= 1
    _assert_accounting(rt)


@pytest.mark.slow
def test_runtime_transient_tool_errors_are_bit_identical(monkeypatch):
    """Transient tool faults that retry to success leave the token stream
    (rewards, trained adapters) bit-identical to the fault-free run."""
    import jax
    _force_calls(monkeypatch)
    base = _chaos_runtime(chaos=None)
    base.run(timeout_s=300.0)
    assert base.error is None and all(st.done
                                      for st in base.mgr.tasks.values())
    faulty = _chaos_runtime(chaos=ChaosConfig(
        seed=0, tool_error_transient=1.0, transient_fail_count=1,
        max_faults_per_site=2))
    faulty.run(timeout_s=300.0)
    assert faulty.error is None
    assert all(st.done for st in faulty.mgr.tasks.values())
    assert faulty.chaos.counts().get("tool_error_transient") == 2
    assert faulty.rec.counters_snapshot().get("env_retries", 0) >= 1
    for tid in ("hop", "gsm"):
        a, b = base.mgr.state(tid), faulty.mgr.state(tid)
        assert a.reward_history == b.reward_history
        for x, y in zip(jax.tree.leaves(a.adapters),
                        jax.tree.leaves(b.adapters)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    _assert_accounting(base)
    _assert_accounting(faulty)


@pytest.mark.slow
def test_runtime_quarantine_recovers_after_transient_outage(monkeypatch):
    """A capped permanent-fault burst trips the agentic tenant's breaker;
    after the cooldown the probe round succeeds, the breaker closes, and
    the tenant still trains to target."""
    _force_calls(monkeypatch)
    rt = _chaos_runtime(chaos=ChaosConfig(
        seed=0, tool_error_permanent=1.0, max_faults_per_site=1),
        breaker_fail_threshold=1, breaker_cooldown_s=0.2,
        breaker_max_trips=3)
    rt.run(timeout_s=300.0)
    assert rt.error is None
    assert all(st.done for st in rt.mgr.tasks.values())
    assert not rt.mgr.tasks["hop"].abandoned
    assert rt.mgr.tasks["hop"].steps_done == 2
    assert rt.breaker.state("hop") == CLOSED
    c = rt.rec.counters_snapshot()
    assert c.get("quarantine_trips", 0) >= 1
    assert c.get("quarantine_recoveries", 0) >= 1
    states = [s for _, _, s in rt.rec.breaker_timeline("hop")]
    assert states[:3] == [OPEN, HALF_OPEN, CLOSED]
    d = rt.mgr.drop_counters()
    assert d["failed_rows"] >= 1
    _assert_accounting(rt)


@pytest.mark.slow
def test_runtime_abandons_tenant_with_persistent_tool_outage(monkeypatch):
    """Uncapped permanent tool errors: the agentic tenant exhausts its
    trip budget and is abandoned; the healthy plain tenant trains to
    target and the run completes without wedging."""
    _force_calls(monkeypatch)
    rt = _chaos_runtime(chaos=ChaosConfig(seed=0, tool_error_permanent=1.0),
                        breaker_fail_threshold=1, breaker_max_trips=0)
    rt.run(timeout_s=300.0)
    assert rt.error is None
    assert all(st.done for st in rt.mgr.tasks.values())
    assert rt.mgr.tasks["hop"].abandoned
    assert rt.mgr.tasks["hop"].steps_done < 2
    assert rt.mgr.tasks["gsm"].steps_done == 2
    assert not rt.mgr.tasks["gsm"].abandoned
    assert rt.breaker.state("hop") == ABANDONED
    assert rt.rec.counters_snapshot().get("quarantine_abandoned", 0) >= 1
    d = rt.mgr.drop_counters()
    assert d["failed_rows"] >= 1
    _assert_accounting(rt)
