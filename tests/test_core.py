"""MARLaaS core invariants: manager on-policy versioning, FIFO buffer,
admission control, metrics."""
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.admission import (AdmissionConfig, AdmissionController,
                                  task_state_bytes)
from repro.core.manager import MultiTaskManager, TaskSpec
from repro.core.metrics import MetricsRecorder
from repro.rl.types import TrajectoryBatch


def _tb(tid, v):
    z = np.zeros((2, 4), np.float32)
    return TrajectoryBatch(task_id=tid, version=v,
                           tokens=z.astype(np.int32),
                           prompt_lens=np.ones(2, np.int32),
                           total_lens=np.full(2, 3, np.int32),
                           rewards=np.zeros(2, np.float32), group_size=2)


def test_next_policy_issued_once_per_version():
    m = MultiTaskManager()
    m.submit(TaskSpec("t", "gsm8k", target_steps=2))
    m.admit("t")
    assert m.next_policy("t") == (0, None)
    assert m.next_policy("t") is None          # v0 already issued
    m.enqueue(_tb("t", 0))
    b = m.pop_batch()
    m.commit("t", None, None, b.version)
    assert m.next_policy("t") == (1, None)     # unlocked by the commit


def test_stale_trajectory_dropped_and_counted():
    # the on-policy assert became a bounded-staleness admission check:
    # at the default max_staleness=0 a stale batch is DROPPED and counted
    # (drop-or-train decision), never trained — and never an exception
    m = MultiTaskManager()
    m.submit(TaskSpec("t", "gsm8k"))
    m.admit("t")
    m.next_policy("t")
    m.enqueue(_tb("t", 0))
    m.pop_batch()
    m.commit("t", None, None, 0)
    assert m.enqueue(_tb("t", 0)) is False     # v0 after commit of v1 = stale
    assert m.pop_batch() is None               # dropped, not queued
    drops = m.drop_counters()
    assert drops["stale_batches_dropped"] == 1
    assert drops["stale_rows_dropped"] == 2


def test_stale_batch_within_window_admitted():
    m = MultiTaskManager(max_staleness=1, async_mode=True)
    m.submit(TaskSpec("t", "gsm8k"))
    m.admit("t")
    m.next_policy("t")
    m.enqueue(_tb("t", 0))
    m.commit("t", None, None, 0)
    assert m.enqueue(_tb("t", 0)) is True      # lag 1 <= max_staleness
    b = m.pop_batch()
    m.commit("t", None, None, b.version)       # lag-1 commit admitted too
    assert m.tasks["t"].version == 2


def test_commit_wrong_version_rejected():
    m = MultiTaskManager()
    m.submit(TaskSpec("t", "gsm8k"))
    m.admit("t")
    with pytest.raises(AssertionError):
        m.commit("t", None, None, 3)


def test_buffer_fifo_across_tasks():
    m = MultiTaskManager()
    for tid in ("a", "b", "c"):
        m.submit(TaskSpec(tid, "gsm8k"))
        m.admit(tid)
        m.next_policy(tid)
    m.enqueue(_tb("b", 0))
    m.enqueue(_tb("a", 0))
    m.enqueue(_tb("c", 0))
    order = [m.pop_batch().task_id for _ in range(3)]
    assert order == ["b", "a", "c"]


def test_task_finishes_at_target_steps():
    m = MultiTaskManager()
    m.submit(TaskSpec("t", "gsm8k", target_steps=2))
    m.admit("t")
    for v in range(2):
        m.next_policy("t")
        m.enqueue(_tb("t", v))
        m.commit("t", None, None, v)
    assert m.tasks["t"].status == "finished"
    assert m.next_policy("t") is None
    assert m.all_done()


def test_admission_budget():
    cfg = get_config("granite-3-2b")
    spec = TaskSpec("t0", "gsm8k", group_size=4, num_groups=2,
                    max_new_tokens=64)
    need = task_state_bytes(cfg, spec, prompt_len=64)
    assert need > 0
    ac = AdmissionController(cfg, AdmissionConfig(
        memory_budget_bytes=2.5 * need, strict=True))
    assert ac.try_admit(TaskSpec("a", "gsm8k", group_size=4, num_groups=2,
                                 max_new_tokens=64))
    assert ac.try_admit(TaskSpec("b", "gsm8k", group_size=4, num_groups=2,
                                 max_new_tokens=64))
    assert not ac.try_admit(TaskSpec("c", "gsm8k", group_size=4, num_groups=2,
                                     max_new_tokens=64))
    ac.release("a")
    assert ac.try_admit(TaskSpec("c", "gsm8k", group_size=4, num_groups=2,
                                 max_new_tokens=64))
    assert ac.used_bytes <= 2.5 * need


def test_admission_ssm_is_length_independent():
    cfg = get_config("mamba2-780m")
    short = TaskSpec("s", "gsm8k", max_new_tokens=8)
    long = TaskSpec("l", "gsm8k", max_new_tokens=2048)
    assert (task_state_bytes(cfg, long, 64) - task_state_bytes(cfg, short, 64)
            == 0)  # pure-SSM state does not grow with generation length
    att = get_config("granite-3-2b")
    assert task_state_bytes(att, long, 64) > task_state_bytes(att, short, 64)


def test_metrics_util_and_idle():
    rec = MetricsRecorder({"rollout": 4, "train": 1})
    rec.record("rollout", "decode", "t", 0.0, 10.0, 4)
    rec.record("train", "train", "t", 10.0, 20.0, 1)
    assert rec.span() == 20.0
    assert 0 < rec.utilization_pct() < 100
    idle = rec.idle_pct()
    # rollout busy half the span (40 dev-s of 80), train busy 10 of 100 total
    assert abs(idle - 100 * (1 - 50.0 / 100.0)) < 1e-6


def test_task_seed_is_stable_across_processes():
    """Per-tenant LoRA keys and prompt datagens derive from crc32, not the
    process-salted hash(): the same (seed, task id) gives the same value
    in every process, so two runs see the same adapters and prompts."""
    from repro.core.runtime import task_seed
    assert task_seed(0, "gsm8k-0") == 165592061
    assert task_seed(1, "gsm8k-0") == 514174910
    assert task_seed(0, "hopsearch-1") == 1761844316
