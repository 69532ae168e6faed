"""The readers of the program's wait counters: the host gap per decode
step, the scheduler queue wait and the tool-resume wait, each a mean in
milliseconds over the window's deltas, None where the count is 0 or the
program has no such counter."""
import pytest

from bench import metrics

READS = {   # metric -> (seconds counter, count counter)
    "decode.host_gap_ms": ("host_gap_seconds", "host_gaps"),
    "scheduler.queue_wait_ms": ("queue_wait_seconds", "queue_pops"),
    "env.resume_wait_ms": ("resume_wait_seconds", "resumes_installed"),
}


def window(stats):
    return metrics.Window(
        seconds=10.0, stats=stats, drops={}, commits=[], episodes=[],
        compiles=0, trace=None, ctx_sum=0, row_steps=0, shape={}, rank=16,
        peaks=None, kind="TPU v5 lite")


@pytest.mark.parametrize("name", sorted(READS))
def test_mean_in_ms_over_the_window(name):
    secs, count = READS[name]
    w = window({secs: 1.5, count: 200, "decode_steps": 201})
    assert metrics.reader(name)(w) == pytest.approx(7.5)


@pytest.mark.parametrize("name", sorted(READS))
def test_none_on_a_zero_count(name):
    secs, count = READS[name]
    assert metrics.reader(name)(window({secs: 0.0, count: 0})) is None


@pytest.mark.parametrize("name", sorted(READS))
def test_none_where_the_program_has_no_counter(name):
    """A program from before the counters (the parent of the change that
    adds them) reports neither field: the metric is left out."""
    w = window({"decode_steps": 200, "sampled_tokens": 6000})
    assert metrics.reader(name)(w) is None


def test_read_all_leaves_out_what_finds_nothing():
    bench = {"per_layer": [
        {"name": n, "unit": "ms", "workloads": ["cell"]} for n in READS]}
    w = window({"host_gap_seconds": 0.7, "host_gaps": 100,
                "queue_wait_seconds": 0.0, "queue_pops": 0})
    out = metrics.read_all(bench, "cell", w)
    assert out == {"decode.host_gap_ms": {"value": pytest.approx(7.0),
                                          "unit": "ms"}}
