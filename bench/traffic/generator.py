"""The one traffic generator: turns a mix file (``bench/traffic/<name>.json``)
and ``--seed`` into tenants, prompts, a seeded agent policy and a seeded
reward. It is the only code behind every mix; a new mix is a new data file.

What a mix file sets (all keys required):

- ``tenants``, ``envs``: tenant i runs env ``envs[i % len(envs)]``;
- ``greedy_tenants``: tenant indices that decode greedily (temperature 0),
  mixed in so that served tokens can be checked against a reference's best;
- ``group_size``, ``num_groups``: GRPO rows per prompt and prompts per round;
- ``system_prompt_tokens``: a fixed per-tenant prefix drawn from the seed,
  shared by all of the tenant's rows (0: the env prompt alone);
- ``turns``, ``turn_tokens``, ``answer_tokens``: inclusive ranges of the
  agent policy's schedule (tool turns per row, sampled tokens per turn,
  answer tokens after the last tool response);
- ``response_allowance``: counter positions left after each CALL for the
  forced tool response (at least the longest response of these envs);
- ``design_size``: schedules in the balanced design; every seed gets the
  same design in another order;
- ``max_len``, ``max_new_tokens``, ``max_turns``, ``max_staleness``,
  ``env_workers``, ``lr``: what the runtime is given for this traffic.

The agent policy. A random base model almost never samples ``CALL``, so the
traffic carries the policy that an agentic tenant's model would follow: a
wrapper over the sampler that emits ``CALL`` at the end of each tool turn,
``EOS`` after the answer, and remaps ``EOS``/``CALL`` away anywhere else. It
sees what the sampler sees (logits, per-row keys, per-row token counters):
each row's key is ``fold_in(PRNGKey(seed), submit index)``, so the wrapper
looks the key up in a table that the generator built for submit indices
``0 .. table_rows - 1`` and reads that row's schedule. Schedules come from
a fixed balanced design (``design_size`` entries, the same for every seed)
permuted by the seed, so every seed serves the same sizes in another order.
The counter counts every token of the row, forced tool responses included,
so the schedule is laid out in counter positions: a turn of drawn length L
that follows a tool response spans ``response_allowance + L`` positions and
samples at least L tokens.
"""
from __future__ import annotations

import contextlib
import json
import random
import zlib
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, List, Tuple

import numpy as np

HERE = Path(__file__).resolve().parent
REQUIRED = ("tenants", "envs", "greedy_tenants", "group_size", "num_groups",
            "system_prompt_tokens", "turns", "turn_tokens", "answer_tokens",
            "response_allowance", "design_size", "max_len", "max_new_tokens",
            "max_turns", "max_staleness", "env_workers", "lr")
TABLE_ROWS = 16384        # submit indices the policy table covers; rows
                          # past it draw their schedule from the key bits
REMAP_ID = 10             # where a stray EOS/CALL sample is sent
MAX_CALLS = 3             # schedule columns: call positions, then the end


def load(name: str, root: Path = HERE) -> dict:
    """The mix file ``<root>/<name>.json``, checked for every key."""
    mix = json.loads((root / f"{name}.json").read_text())
    missing = [k for k in REQUIRED if k not in mix]
    if missing:
        raise ValueError(f"traffic {name!r} lacks {missing}")
    if max(mix["turns"]) > MAX_CALLS:
        raise ValueError(f"traffic {name!r}: at most {MAX_CALLS} turns")
    return mix


def runtime_seed(seed: int) -> int:
    """The 31-bit seed the runtime is built with (the CLI takes any whole
    number; the runtime's keys and task seeds want 31 bits)."""
    return int(seed) % (2 ** 31 - 1)


@dataclass(frozen=True)
class Tenant:
    index: int
    task_id: str
    env: str
    temperature: float
    system_prompt: Tuple[int, ...]


def tenants(mix: dict, seed: int, vocab_size: int) -> List[Tenant]:
    """Every tenant of the mix with its seeded system prompt."""
    from repro.data import tokenizer as tok
    out = []
    n_sys = mix["system_prompt_tokens"]
    for i in range(mix["tenants"]):
        env = mix["envs"][i % len(mix["envs"])]
        rng = random.Random(zlib.crc32(f"sys:{seed}:{i}".encode()))
        sys_prompt = ()
        if n_sys:
            sys_prompt = (tok.BOS,) + tuple(
                rng.randrange(len(tok.SPECIALS), vocab_size)
                for _ in range(n_sys - 1))
        temp = 0.0 if i in mix["greedy_tenants"] else 1.0
        out.append(Tenant(i, f"{env}-{i}", env, temp, sys_prompt))
    return out


def design(mix: dict) -> np.ndarray:
    """The balanced schedule design, the same for every seed: int32
    [design_size, MAX_CALLS + 1] of call counter positions (-1: no call)
    and the end position (EOS). Turn counts alternate over the range and
    lengths are spread evenly over their ranges, then shuffled by a fixed
    stream so that neighbours differ."""
    n = mix["design_size"]
    t_lo, t_hi = mix["turns"]
    l_lo, l_hi = mix["turn_tokens"]
    a_lo, a_hi = mix["answer_tokens"]
    allow = mix["response_allowance"]
    fixed = random.Random(0x5EED)
    turn_lens = list(np.linspace(l_lo, l_hi, n * MAX_CALLS).round()
                     .astype(int))
    answers = list(np.linspace(a_lo, a_hi, n).round().astype(int))
    fixed.shuffle(turn_lens)
    fixed.shuffle(answers)
    rows = np.full((n, MAX_CALLS + 1), -1, np.int32)
    for j in range(n):
        n_turns = t_lo + j % (t_hi - t_lo + 1)
        c = -1
        for k in range(n_turns):
            c += turn_lens[j * MAX_CALLS + k] + (allow if k else 0)
            rows[j, k] = c
        rows[j, MAX_CALLS] = c + allow + answers[j]
    return rows


def schedule_table(mix: dict, seed: int, rows: int = TABLE_ROWS) -> np.ndarray:
    """Schedules of submit indices 0..rows-1: the design, repeated, each
    repetition in its own seeded order."""
    d = design(mix)
    rng = np.random.default_rng(runtime_seed(seed))
    reps = -(-rows // len(d))
    order = np.concatenate([rng.permutation(len(d)) for _ in range(reps)])
    return d[order[:rows]]


def row_keys(seed: int, rows: int = TABLE_ROWS) -> np.ndarray:
    """The per-row base keys the engine gives submit indices 0..rows-1."""
    import jax
    master = jax.random.PRNGKey(runtime_seed(seed))
    keys = jax.vmap(lambda i: jax.random.fold_in(master, i))(
        np.arange(rows, dtype=np.uint32))
    return np.asarray(keys, np.uint32)


class AgentPolicy:
    """The seeded agent policy as a sampler wrapper (see module doc)."""

    MODULES = ("repro.rollout.engine", "repro.rollout.prefill")

    def __init__(self, mix: dict, seed: int, rows: int = TABLE_ROWS):
        import jax.numpy as jnp
        self.keys = row_keys(seed, rows)
        self.table = schedule_table(mix, seed, rows)
        self._design = jnp.asarray(design(mix))
        self._keys = jnp.asarray(self.keys)
        self._table = jnp.asarray(self.table)

    def wrap(self, orig):
        import jax.numpy as jnp
        from repro.data import tokenizer as tok
        tkeys, table, dsg = self._keys, self._table, self._design

        def policy(logits, keys, counters, temps):
            s = orig(logits, keys, counters, temps)
            k = keys.astype(jnp.uint32)
            eq = ((k[:, None, 0] == tkeys[None, :, 0])
                  & (k[:, None, 1] == tkeys[None, :, 1]))
            sched = jnp.where(jnp.any(eq, axis=1)[:, None],
                              table[jnp.argmax(eq, axis=1)],
                              dsg[k[:, 1] % dsg.shape[0]])
            c = counters.astype(jnp.int32)[:, None]
            call = jnp.any(c == sched[:, :MAX_CALLS], axis=1)
            end = c[:, 0] >= sched[:, MAX_CALLS]
            s = jnp.where((s == tok.EOS) | (s == tok.CALL), REMAP_ID, s)
            s = jnp.where(call, tok.CALL, s)
            return jnp.where(end, tok.EOS, s)

        return policy

    @contextlib.contextmanager
    def installed(self):
        """Install the wrapper in both modules that sample; fail loudly if
        either has no ``_sample_rows`` to wrap (never run untooled)."""
        import importlib
        mods = [importlib.import_module(m) for m in self.MODULES]
        missing = [m.__name__ for m in mods
                   if not callable(getattr(m, "_sample_rows", None))]
        if missing:
            raise RuntimeError(f"no _sample_rows to wrap in {missing}: the "
                               "agent policy cannot be installed")
        origs = [m._sample_rows for m in mods]
        try:
            for m, o in zip(mods, origs):
                m._sample_rows = self.wrap(o)
            yield self
        finally:
            for m, o in zip(mods, origs):
                m._sample_rows = o


def reward(seed: int, completion) -> float:
    """The traffic's seeded reward: a random base model earns no env
    reward, so each episode is graded by a hash of its tokens (uniform in
    [0, 1)); GRPO advantages are then nonzero and the trainer's update is
    real work."""
    data = np.asarray(list(completion), np.int64).tobytes()
    return (zlib.crc32(data, runtime_seed(seed) & 0xFFFFFFFF) % 100003) / 100003


class TrafficEnv:
    """An env as the tenant sees it: the program's env with the tenant's
    system prompt ahead of every prompt and the seeded reward. Everything
    else (tools, sessions, latencies, turn budget) is the env's own."""

    def __init__(self, env, tenant: Tenant, seed: int):
        self._env = env
        self._prefix = list(tenant.system_prompt)
        self._seed = seed

    def __getattr__(self, name):
        return getattr(self._env, name)

    def sample_prompt(self, rng):
        prompt, truth = self._env.sample_prompt(rng)
        if self._prefix:
            prompt = self._prefix + list(prompt[1:])     # one BOS, leading
        return prompt, truth

    def verify(self, truth, completion_ids) -> float:
        return reward(self._seed, completion_ids)


def describe(mix: dict, seed: int, vocab_size: int) -> Dict[str, object]:
    """What the generator gives for one seed, as plain data (the test of
    determinism compares two processes' descriptions)."""
    return {
        "tenants": [(t.task_id, t.env, t.temperature,
                     zlib.crc32(np.asarray(t.system_prompt, np.int64)
                                .tobytes()))
                    for t in tenants(mix, seed, vocab_size)],
        "schedule_crc": zlib.crc32(schedule_table(mix, seed).tobytes()),
        "keys_crc": zlib.crc32(row_keys(seed, 256).tobytes()),
        "budget": mix["max_new_tokens"],
    }
