"""The traffic generator: the same seed gives the same tenants, prompts,
turn schedules and budgets in two processes; every seed serves the same
balanced design in another order; the agent policy emits its schedule."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from bench import harness
from bench.traffic import generator

SEEDS = (0, 674173142, 2 ** 31 + 12345)
DESCRIBE = ("import json, sys; sys.path[:0] = [{root!r}, {src!r}]; "
            "from bench.traffic import generator as g; "
            "m = dict(g.load({mix!r}), **{over!r}); "
            "print(json.dumps(g.describe(m, {seed}, 151936)))")
# The agentic mix with a 512-token system prompt per tenant in front of
# every row (the generator's shared-prefix path; no cell runs it yet).
SHARED = {"system_prompt_tokens": 512, "max_len": 1024}


def shared_mix():
    return dict(generator.load("agentic-unshared"), **SHARED)


def _describe_in_child(mix, seed, over):
    code = DESCRIBE.format(root=str(harness.ROOT),
                           src=str(harness.ROOT / "src"), mix=mix,
                           over=over, seed=seed)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", code], env=env, check=True,
                         capture_output=True, text=True, timeout=300)
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("over", [pytest.param(SHARED, id="agentic-shared"),
                                  pytest.param({}, id="agentic-unshared")])
def test_same_seed_same_traffic_in_two_processes(over):
    seed = 674173142
    mix = "agentic-unshared"
    a = _describe_in_child(mix, seed, over)
    b = _describe_in_child(mix, seed, over)
    assert a == b
    here = json.loads(json.dumps(generator.describe(
        dict(generator.load(mix), **over), seed, 151936)))
    assert here == a
    other = _describe_in_child(mix, seed + 1, over)
    assert other["schedule_crc"] != a["schedule_crc"]


@pytest.mark.parametrize("seed", SEEDS)
def test_every_seed_serves_the_same_design(seed):
    mix = shared_mix()
    d = generator.design(mix)
    tab = generator.schedule_table(mix, seed)
    n = len(d)
    first = tab[:n]
    assert sorted(map(tuple, first)) == sorted(map(tuple, d))


def test_design_within_ranges():
    mix = shared_mix()
    d = generator.design(mix)
    allow = mix["response_allowance"]
    for row in d:
        calls = [c for c in row[:3] if c >= 0]
        assert mix["turns"][0] <= len(calls) <= mix["turns"][1]
        lens = np.diff([-1] + calls)
        assert lens[0] >= mix["turn_tokens"][0]
        assert all(lens[1:] - allow >= mix["turn_tokens"][0])
        assert all(lens - np.r_[0, [allow] * (len(lens) - 1)]
                   <= mix["turn_tokens"][1])
        ans = row[3] - calls[-1] - allow
        assert mix["answer_tokens"][0] <= ans <= mix["answer_tokens"][1]
    # the longest row fits under max_len with the shared prompt in front
    assert mix["system_prompt_tokens"] + 16 + d[:, 3].max() + 1 \
        < mix["max_len"]


def test_tenants_and_system_prompts():
    mix = shared_mix()
    ts = generator.tenants(mix, 5, 151936)
    assert len(ts) == 8 and len({t.task_id for t in ts}) == 8
    assert all(len(t.system_prompt) == 512 for t in ts)
    assert {t.index for t in ts if t.temperature == 0.0} == {1, 5}
    assert len({t.system_prompt for t in ts}) == 8
    un = generator.tenants(generator.load("agentic-unshared"), 5, 151936)
    assert all(t.system_prompt == () for t in un)


def test_policy_emits_schedule():
    import jax
    import jax.numpy as jnp
    from repro.data import tokenizer as tok
    mix = generator.load("agentic-unshared")
    pol = generator.AgentPolicy(mix, 9, rows=64)

    def sampler(logits, keys, counters, temps):
        return jnp.full(counters.shape, tok.EOS, jnp.int32)   # worst case

    f = jax.jit(pol.wrap(sampler))
    rows = np.arange(8)
    keys = jnp.asarray(pol.keys[rows])
    sched = pol.table[rows]
    for c in range(int(sched[:, 3].max()) + 1):
        out = np.asarray(f(None, keys, jnp.full((8,), c, jnp.int32), None))
        for r in range(8):
            if c in sched[r, :3]:
                assert out[r] == tok.CALL
            elif c >= sched[r, 3]:
                assert out[r] == tok.EOS
            else:
                assert out[r] == generator.REMAP_ID


def test_policy_refuses_to_run_untooled(monkeypatch):
    import repro.rollout.engine  # noqa: F401  (imports prefill's sampler)
    import repro.rollout.prefill as pf
    mix = generator.load("agentic-unshared")
    pol = generator.AgentPolicy(mix, 1, rows=8)
    monkeypatch.delattr(pf, "_sample_rows")
    with pytest.raises(RuntimeError, match="_sample_rows"):
        with pol.installed():
            pass


def test_reward_is_seeded():
    a = generator.reward(3, [5, 6, 7])
    assert a == generator.reward(3, [5, 6, 7])
    assert 0.0 <= a < 1.0 and a != generator.reward(4, [5, 6, 7])
