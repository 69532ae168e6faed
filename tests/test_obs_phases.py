"""Program phases on the profiler's clock (``repro.obs.phase``) and the
wait counters booked at the same boundaries.

1. One engine step under ``jax.profiler.start_trace`` puts its
   ``marlaas.engine.*`` phases on a host plane of the ``.xplane.pb``, in
   the step's order, nested inside the step.
2. With a ``Tracer`` the same phases reach its ring on the decode
   thread's track; without one there is no ring and the step runs.
3. The counters keep their invariants on a short agentic env-stage drive
   through every install path (fused refill, splice, restore/radix).
4. Garbage collections are ``marlaas.python.gc`` spans while the runtime
   runs, and the hook is gone after it.
5. The runtime's loop and trainer phases reach the ring of a traced run.

Agentic rows emit CALL deterministically (module-scoped sampler bias, the
test_obs idiom), so every drive parks and resumes.
"""
import gc
import random
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from conftest import tiny_lm
from repro.data import tokenizer as tok
from repro.envs.tasks import make_env
from repro.lora.adapters import init_lora
from repro.models import init_params
import repro.rollout.engine as eng_mod
import repro.rollout.prefill as pf_mod
from repro.obs import Tracer
from repro.obs.phase import gc_spans, phase
from repro.rollout.engine import (STEP_TRACK, ContinuousRolloutEngine,
                                  RolloutRequest)

CALL_AT = (2,)          # sampled-token counter that emits CALL (one park)
STEP_PHASES = ["engine.supervise", "engine.env_pump", "engine.restore",
               "engine.radix_fill", "engine.splice", "engine.pages",
               "engine.masks", "engine.dispatch", "engine.wait",
               "engine.accept"]


@pytest.fixture(scope="module", autouse=True)
def _biased_sampling():
    """CALL at the CALL_AT counters; EOS remapped so rows run their whole
    budget."""
    mp = pytest.MonkeyPatch()
    orig = pf_mod._sample_rows

    def biased(logits, keys, counters, temps):
        s = orig(logits, keys, counters, temps)
        s = jnp.where(s == tok.EOS, 10, s)
        hit = jnp.zeros(counters.shape, bool)
        for c in CALL_AT:
            hit = hit | (counters == c)
        return jnp.where(hit, tok.CALL, s)

    mp.setattr(pf_mod, "_sample_rows", biased)
    mp.setattr(eng_mod, "_sample_rows", biased)
    yield
    mp.undo()


_MODEL = {}


def _model():
    if not _MODEL:
        cfg = tiny_lm("granite-3-2b")
        _MODEL["m"] = (cfg, init_params(jax.random.PRNGKey(0), cfg),
                       init_lora(jax.random.PRNGKey(1), cfg))
    return _MODEL["m"]


def _engine(n_rows=4, tracer=None, **kw):
    """Tiny agentic engine with ``n_rows`` queued requests."""
    cfg, params, tree = _model()
    env = make_env("hopsearch", kb_size=8, hops=2, seed=0)
    env.env_latency_mean = 0.0
    rng = random.Random(7)
    eng = ContinuousRolloutEngine(cfg, params, max_slots=2, max_adapters=1,
                                  max_len=96, seed=0, env_stage=True,
                                  env_workers=2, tracer=tracer, **kw)
    eng.set_adapters(0, tree)
    for i in range(n_rows):
        prompt, truth = env.sample_prompt(rng)
        eng.submit(RolloutRequest("hop", 0, prompt, truth, env,
                                  max_new_tokens=6, seed=i, max_turns=2))
    return eng


FULL = dict(disagg_prefill=True, paged_kv=True, kv_page_size=16)


def _step_until_decoding(eng, timeout_s=120.0):
    """Step until a step decodes (compiles happen here)."""
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        before = eng.stats.decode_steps
        eng.step()
        if eng.stats.decode_steps > before:
            return
        time.sleep(0.001)
    raise AssertionError("the engine never decoded")


def _drive(eng, timeout_s=180.0):
    deadline = time.monotonic() + timeout_s
    comps = []
    while not eng.idle() and time.monotonic() < deadline:
        progressed = eng.step()
        comps.extend(eng.drain_completions())
        if not progressed:
            time.sleep(0.0005)
    assert eng.idle(), "drive did not finish"
    return comps


# -- 1. the profiler's trace -----------------------------------------------

def _host_events(log_dir):
    from jax.profiler import ProfileData
    files = sorted(Path(log_dir).glob("**/*.xplane.pb"))
    assert files, "the profiler wrote no trace"
    pd = ProfileData.from_file(str(files[-1]))
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for ev in line.events:
                out.append((ev.name, ev.start_ns,
                            ev.start_ns + ev.duration_ns))
    return out


def test_step_phases_on_the_profiler_trace_in_order(tmp_path):
    eng = _engine(**FULL)
    try:
        _step_until_decoding(eng)       # warm: compiles outside the trace
        jax.profiler.start_trace(str(tmp_path))
        try:
            before = eng.stats.decode_steps
            with jax.profiler.TraceAnnotation("test:step"):
                eng.step()
        finally:
            jax.profiler.stop_trace()
        assert eng.stats.decode_steps == before + 1
    finally:
        eng.shutdown()
    evs = _host_events(tmp_path)
    (step,) = [e for e in evs if e[0] == "test:step"]
    engine = sorted((e for e in evs if e[0].startswith("marlaas.engine.")),
                    key=lambda e: e[1])
    assert [e[0] for e in engine] == ["marlaas." + n for n in STEP_PHASES]
    for name, t0, t1 in engine:
        assert step[1] <= t0 <= t1 <= step[2], name
    for (_, _, end), (_, start, _) in zip(engine, engine[1:]):
        assert end <= start             # one after the other, none nested


# -- 2. the tracer's ring ---------------------------------------------------

def test_step_phases_reach_the_tracer_ring():
    tr = Tracer()
    eng = _engine(tracer=tr, **FULL)
    try:
        _step_until_decoding(eng)
        n0 = len(tr.spans())
        eng.step()
    finally:
        eng.shutdown()
    new = [s for s in tr.spans()[n0:] if (s[0], s[1]) == STEP_TRACK]
    names = [s[2] for s in sorted(new, key=lambda s: s[3])]
    assert names == STEP_PHASES
    assert all(s[3] <= s[4] for s in new)


def test_step_runs_without_a_tracer():
    eng = _engine(**FULL)
    try:
        assert eng._tracer is None
        _step_until_decoding(eng)
        n = eng.stats.decode_steps
        eng.step()
        assert eng.stats.decode_steps == n + 1
    finally:
        eng.shutdown()


def test_phase_books_its_clock_pair_into_a_tracer():
    tr = Tracer(clock=iter([1.0, 2.5]).__next__)
    with phase("trainer.step", tr, ("train", "trainer")) as ph:
        pass
    assert (ph.t0, ph.t1) == (1.0, 2.5)
    assert tr.spans() == [("train", "trainer", "trainer.step", 1.0, 2.5,
                           -1, 0, 0)]
    with phase("engine.wait") as ph:
        pass
    assert ph.t0 <= ph.t1


# -- 3. counter invariants --------------------------------------------------

@pytest.mark.parametrize("mode", [
    {}, {"disagg_prefill": True}, FULL,
], ids=["fused", "disagg", "paged"])
def test_wait_counters_hold_their_invariants(mode, monkeypatch):
    from repro.rollout.scheduler import SlotScheduler
    pushes = []
    push = SlotScheduler.push

    def counted(self, row, *a, **kw):
        pushes.append(row)
        return push(self, row, *a, **kw)

    monkeypatch.setattr(SlotScheduler, "push", counted)
    eng = _engine(n_rows=6, **mode)
    try:
        comps = _drive(eng)
    finally:
        eng.shutdown()
    st = eng.stats
    assert len(comps) == 6
    assert st.decode_steps >= 2
    assert 1 <= st.host_gaps <= st.decode_steps - 1
    assert st.host_gap_seconds >= 0.0
    assert 1 <= st.queue_pops <= len(pushes)
    assert st.queue_wait_seconds >= 0.0
    assert st.resumes >= 1               # the biased sampler parks rows
    assert 1 <= st.resumes_installed <= st.resumes
    assert st.resume_wait_seconds >= 0.0


def test_an_engine_that_stops_decoding_opens_no_gap():
    """A step that decodes nothing resets the gap: the next decode after
    an empty engine is not counted."""
    eng = _engine(n_rows=1)
    try:
        _drive(eng)
        gaps, steps = eng.stats.host_gaps, eng.stats.decode_steps
        assert eng.step() is False       # empty: no decode
        assert eng._t_read is None
        cfg, _, _ = _model()
        env = make_env("gsm8k")
        prompt, truth = env.sample_prompt(random.Random(3))
        eng.submit(RolloutRequest("hop", 0, prompt, truth, env,
                                  max_new_tokens=3, seed=99))
        _drive(eng)
    finally:
        eng.shutdown()
    new_steps = eng.stats.decode_steps - steps
    assert new_steps >= 2
    assert eng.stats.host_gaps - gaps == new_steps - 1


# -- 4. garbage collections ---------------------------------------------------

def test_gc_spans_on_the_profiler_trace(tmp_path):
    n0 = len(gc.callbacks)
    g = gc_spans().install()
    try:
        assert len(gc.callbacks) == n0 + 1
        jax.profiler.start_trace(str(tmp_path))
        try:
            gc.collect()
        finally:
            jax.profiler.stop_trace()
    finally:
        g.remove()
    assert len(gc.callbacks) == n0
    assert any(e[0] == "marlaas.python.gc" for e in _host_events(tmp_path))


def test_runtime_run_installs_the_gc_hook_and_removes_it():
    from repro.core.runtime import MARLaaSRuntime
    rt = MARLaaSRuntime.__new__(MARLaaSRuntime)
    n0 = len(gc.callbacks)
    seen = []

    def body(timeout_s):
        seen.append(len(gc.callbacks))
        raise RuntimeError("stop")

    rt._run = body
    with pytest.raises(RuntimeError, match="stop"):
        rt.run(timeout_s=1.0)
    assert seen == [n0 + 1]
    assert len(gc.callbacks) == n0


# -- 5. the runtime's loop and trainer phases ---------------------------------

def test_runtime_and_trainer_phases_reach_the_ring():
    from repro.core.manager import TaskSpec
    from repro.core.runtime import TRAIN_TRACK, MARLaaSRuntime, RuntimeConfig
    cfg, params, _ = _model()
    rt = MARLaaSRuntime(cfg, params, RuntimeConfig(
        policy="marlaas", max_len=48, max_slots=4, seed=5, trace=True,
        async_train=True, max_staleness=1))
    rt.submit_task(TaskSpec("t0", "gsm8k", group_size=2, num_groups=1,
                            max_new_tokens=4, target_steps=1))
    rt.run(timeout_s=300)
    assert rt.mgr.all_done()
    by_track = {}
    for proc, thread, name, *_ in rt.tracer.spans():
        by_track.setdefault((proc, thread), set()).add(name)
    assert {"runtime.preempt", "runtime.feed", "runtime.samples",
            "runtime.completions", "engine.supervise", "engine.refill",
            "engine.accept"} <= by_track[STEP_TRACK]
    assert {"trainer.pop", "trainer.assemble", "trainer.step",
            "trainer.commit"} <= by_track[TRAIN_TRACK]
    assert "prefill.fused" in by_track[("prefill", "fused")]
