"""The reduction from a profiler trace to device numbers: busy and idle
time, device time by operation and by jitted program, and labelled idle
gaps; on a synthetic trace with known answers, and on a small trace
recorded on one TPU v5e chip running a cell of this benchmark."""
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

from bench import trace

DATA = Path(__file__).resolve().parent / "data"


def ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur, end_ns=start + dur)


def synthetic():
    ops = [ev("%fusion.1 = f32[8] fusion(%paged_gqa_decode.3)", 0, 100),
           ev("%paged_gqa_decode.3 = bf16[8] custom-call()", 100, 50),
           ev("%fusion.1 = f32[8] fusion(%paged_gqa_decode.3)", 400, 100),
           ev("%jvp_jit_token_logprob_flat__.1 = f32[8] custom-call()",
              450, 100),
           ev("%while.2 = (s32[]) while()", 900, 100)]
    mods = [ev("jit_step", 0, 150), ev("jit_train_step", 400, 150),
            ev("jit_step", 900, 100)]
    dev = NS(name="/device:TPU:0", lines=[NS(name="XLA Ops", events=ops),
                                          NS(name="XLA Modules",
                                             events=mods)])
    host = NS(name="/host:CPU", lines=[NS(name="t", events=[
        ev("bench:decode_step", 140, 300), ev("other", 600, 200)])])
    return NS(planes=[host, dev])


def test_busy_ops_modules_and_gaps():
    tr = trace.reduce_profile(synthetic())
    assert tr.planes == 1
    assert tr.busy_s == pytest.approx(400e-9)       # [0,150)+[400,550)+...
    assert tr.span_s == pytest.approx(1000e-9)
    assert tr.op_s["fusion.1"] == pytest.approx(200e-9)
    assert tr.time_of("paged_gqa_decode") == (pytest.approx(50e-9), 1)
    assert tr.time_of("token_logprob") == (pytest.approx(100e-9), 1)
    assert tr.time_of("train_step", modules=True) == (
        pytest.approx(150e-9), 1)
    assert tr.gaps[0] == ("unattributed", pytest.approx(350e-9))
    assert tr.gaps[1] == ("decode_step", pytest.approx(250e-9))
    b = trace.breakdown(tr)
    assert b["device_ops"][0][0] == "fusion.1" and len(b["idle_gaps"]) == 2
    assert all(not k.startswith("while") for k, _ in b["device_ops"])


def test_no_device_plane():
    pd = NS(planes=[NS(name="/host:CPU", lines=[])])
    tr = trace.reduce_profile(pd)
    assert tr.planes == 0 and tr.busy_s == 0.0 and tr.gaps == []


def test_recorded_chip_trace():
    """792 ms of a traced run of qwen3-0.6b.agentic-shared on one TPU v5e
    (one decode step, one GRPO train step, a prefix-cache suffix fill),
    cut down to the device's op and program lines and the benchmark's host
    spans. The expected numbers were summed from the same events by plain
    loops when the trace was cut."""
    import json
    path = DATA / "v5e_qwen3_shared.xplane.pb"
    want = json.loads((DATA / "v5e_qwen3_shared.xplane.pb.json").read_text())
    tr = trace.reduce_file(path)
    assert tr.planes == 1
    assert sum(tr.op_n.values()) == want["ops"]
    assert tr.busy_s == pytest.approx(want["busy_ns"] * 1e-9, rel=1e-9)
    idle = 1.0 - tr.busy_s / tr.span_s
    assert 0.0 < idle < 0.1          # a train step keeps the device busy
    sec, n = tr.time_of("train_step", modules=True)
    assert n == want["train_steps"] == 1
    assert sec == pytest.approx(want["train_step_ns"] * 1e-9, rel=1e-9)
    assert tr.time_of("paged_gqa_decode")[0] == pytest.approx(
        want["paged_ns"] * 1e-9, rel=1e-9)
    assert tr.time_of("token_logprob")[0] == pytest.approx(
        want["logprob_ns"] * 1e-9, rel=1e-9)
    assert [s for _, s in tr.gaps[:3]] == pytest.approx(
        [g * 1e-9 for g in want["gaps_ns"]], rel=1e-9)
    assert {name for name, _ in tr.gaps} <= {"decode_step", "train_step",
                                              "unattributed"}
    b = trace.breakdown(tr)
    assert len(b["device_ops"]) == 10 and b["device_ops"][0][1] > 0
