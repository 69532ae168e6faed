"""The result line: ``attempted`` and ``failed`` are ints with
``attempted >= 1`` and ``0 <= failed <= attempted`` in every run, whatever
the seed; where nothing was attempted the run exits nonzero and prints no
line; with no TPU the command fails and never falls back to the CPU."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import bench_tiny
from bench import harness
from bench import run as cli

SEEDS = (3, 674173142, 2 ** 31 + 7)


def _assert_line(line):
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "checks"
    assert type(line["attempted"]) is int and line["attempted"] >= 1
    assert type(line["failed"]) is int
    assert 0 <= line["failed"] <= line["attempted"]
    assert harness.finite(line)
    assert set(line["metrics"]) == {"step_time_s", "rollout_latency_p95_s",
                                    "rollout_tokens_per_s", "setup_s"}
    for m in line["metrics"].values():
        assert m["value"] > 0


@pytest.mark.parametrize("seed", SEEDS)
def test_counts_are_ints_at_the_shortest_window(seed):
    line = bench_tiny.run(seed, seconds=cli.MIN_SECONDS)
    _assert_line(line)
    assert line["correct"] is True, line["checks"]
    assert json.loads(json.dumps(line)) == line


def test_cli_prints_the_line_last(monkeypatch, capsys):
    orig = harness.run

    def tiny(workload, seed, seconds, trace, **kw):
        kw.pop("out_dir", None)
        return bench_tiny.run(seed, seconds) if not trace else orig(
            workload, seed, seconds, trace, **kw)

    monkeypatch.setattr(cli, "require_accelerator", lambda chips: None)
    monkeypatch.setattr(harness, "run", tiny)
    rc = cli.main(["--workload", bench_tiny.CELL, "--seed", "674173142",
                   "--seconds", "1", "--trace", "0"])
    out, err = capsys.readouterr()
    assert rc == 0
    _assert_line(json.loads(out.strip().splitlines()[-1]))
    assert err.strip().splitlines()[-1].startswith("greedy_gap ")


def test_no_work_exits_nonzero_without_a_line(monkeypatch, capsys):
    def nothing(*a, **k):
        return harness.count_work([], [], 0.0, 1.0)

    monkeypatch.setattr(cli, "require_accelerator", lambda chips: None)
    monkeypatch.setattr(harness, "run", nothing)
    rc = cli.main(["--workload", bench_tiny.CELL, "--seed", "1",
                   "--seconds", "1"])
    out, _ = capsys.readouterr()
    assert rc != 0 and out == ""


def test_count_work():
    E = harness.Episode
    eps = [E("a", 1, 0.0, 0.5, 3, 0, "eos", 0),       # before the window
           E("a", 1, 0.2, 1.5, 3, 0, "eos", 1),       # finished in it
           E("a", 1, 1.2, 1.7, 3, 0, "capacity", 2),  # failed in it
           E("a", 1, 1.9, 2.6, 3, 0, "eos", 3)]       # finished after it
    submits = [0.0, 0.2, 1.2, 1.9, 1.95]              # one never finishes
    assert harness.count_work(eps, submits, 1.0, 2.0) == (4, 1)
    with pytest.raises(harness.NoWork):
        harness.count_work(eps[:1], submits[:1], 1.0, 2.0)


def test_window_shorter_than_the_least_is_refused():
    assert cli.main(["--workload", bench_tiny.CELL, "--seed", "1",
                     "--seconds", "0.5"]) != 0


def test_no_tpu_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(harness.ROOT / "bench/run.py"),
                        "--workload", bench_tiny.CELL, "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and p.stdout == ""


def test_steps_in_flight_count_by_their_share_of_the_window():
    # tenant a commits at 0, 4, 8, 12: steps [0,4], [4,8], [8,12]; the
    # window [2, 10) holds half of the first, all of the second and half of
    # the third
    commits = [(t, "a") for t in (0.0, 4.0, 8.0, 12.0)]
    # tenant b commits at 1 and 9 and not again by 13: its open step
    # [9, 13] counts as ending at 13
    commits += [(1.0, "b"), (9.0, "b")]
    got = harness.steps_in_window(commits, ["a", "b"], 2.0, 10.0, 13.0)
    assert got == pytest.approx(2.0 + 7.0 / 8.0 + 1.0 / 4.0)
    # whole commits with no step in flight at an edge count as they are
    assert harness.steps_in_window(commits[:4], ["a"], 0.0, 12.0,
                                   12.0) == pytest.approx(3.0)


def test_check_rows_keep_the_tokens_before_an_inflight_write():
    class Comp:
        def __init__(self, idx, submitted, finished, n, reason="eos"):
            self.task_id, self.version, self.submit_index = "a", 1, idx
            self.submitted_at, self.finished_at = submitted, finished
            self.finish_reason = reason
            self.prompt_len, self.tokens = 2, [7] * (2 + n)

    comps = [Comp(0, 0.0, 0.5, 3),            # before the window
             Comp(1, 1.05, 1.45, 4),          # in it, no write while it ran
             Comp(2, 0.9, 1.8, 4),            # in it, slot rewritten at 1.0
             Comp(3, 1.1, 1.9, 2, "capacity")]
    switches = [(1.0, "a"), (1.2, "b"), (1.5, "a")]
    accepted = {1: {0: 1.1, 1: 1.2, 2: 1.3, 3: 1.4},
                2: {0: 0.95, 1: 1.4, 2: 1.6, 3: 1.7}}
    rows = harness.check_rows(comps, switches, accepted, 1.0, 2.0)
    assert [r.comp for r in rows] == comps[1:3]
    assert [r.clean for r in rows] == [4, 1]
    # a window in which the tenant finished nothing falls back to all
    # rows; a row with no accept times and a write in its life keeps none
    rows = harness.check_rows(comps, switches, accepted, 5.0, 6.0)
    assert [(r.comp, r.clean) for r in rows] == [
        (comps[0], 0), (comps[1], 4), (comps[2], 1)]
    # with no write at all every token is of the row's own version
    assert [r.clean for r in harness.check_rows(comps, [], {}, 1.0, 2.0)] \
        == [4, 4]


def test_a_reading_that_is_not_a_number_fails():
    from bench import check
    lim = {"a": 0.1, "b": 0.1}
    checks, ok = check.judge({"a": 0.01, "b": float("nan")}, lim)
    assert ok is False and checks["b"]["value"] > checks["b"]["limit"]
    assert harness.finite({"checks": checks})
    assert check.judge({"a": 0.01, "b": 0.1}, lim)[1] is True
    # a NaN among the readings survives the worst-of, wherever it stands
    assert np.isnan(check._worst(0.0, float("nan"), 0.5))
    assert check._worst() == 0.0
