"""One run of one cell: build, warm up, measure a window, check, report.

The system under test is the program's own runtime, built the way its CLI
builds it (``MARLaaSRuntime`` with the continuous engine, disaggregated
prefill, the env stage, the paged KV pool with its prefix cache, the
bounded-staleness async trainer and the Pallas kernels). The benchmark
gives it the weights, the tenants and their traffic; it reads the
runtime's own counters, and times everything on the host clock.

A run:

1. set-up: seeded weights and adapters made on the device, the runtime
   and its tenants, then warm-up until every tenant has committed a GRPO
   step, every agentic tenant has had a row parked and resumed, and each
   check tenant has committed its first ``CHECK_STEPS`` steps;
2. the window: ``seconds`` of the runtime running, read by counters at
   both ends (and, with ``trace``, a profiler trace of its first
   ``TRACE_SECONDS``, or up to the first commit after them); without
   ``trace`` the runtime then runs on until every tenant has committed
   once more, so that the steps in flight at the window's end can be
   counted by their share inside it;
3. the runtime is stopped and freed; the plain reference then checks what
   the timed path produced (bench/check.py).
"""
from __future__ import annotations

import dataclasses
import gc
import json
import math
import sys
import threading
import time
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
CHECK_STEPS = 2           # first steps of each check tenant whose loss,
                          # gradient and update are compared
CHECK_TENANTS = (1, 2)    # tenant indices whose steps and rows are checked
TRACE_SECONDS = 10.0      # traced span at the start of a --trace 1 window
WARMUP_TIMEOUT_S = 300.0
TAIL_TIMEOUT_S = 90.0     # longest wait after the window for each tenant's
                          # next commit
LOG_EVERY_S = 15.0
POLL_S = 0.05
FAILED_REASONS = ("tool_error", "capacity", "quarantined", "aborted")


class NoWork(RuntimeError):
    """The window saw no rollout episode: there is nothing to report."""


def compile_cache() -> str:
    """JAX's persistent compilation cache at one fixed place (the path is
    part of a cached program's key): ``JAX_COMPILATION_CACHE_DIR`` where
    that is set, else ``.jax_cache/`` in the checkout. Every compile is
    cached, however quick, so a cell's later runs compile nothing."""
    import os
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def find_cell(bench: dict, workload: str):
    """(configuration file, traffic mix) of a cell, found by name."""
    from bench.traffic import generator
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    return (json.loads((ROOT / conf["file"]).read_text()),
            generator.load(cell["traffic"]))


def model_config(conf_file: dict):
    """The program's ModelConfig for a configuration file: its registry
    entry with every size the file states put in place, so what runs is
    what the file says."""
    from repro.configs import get_config
    c = conf_file["config"]
    cfg = get_config(conf_file["registry"])
    heads = c["num_attention_heads"]
    cfg = dataclasses.replace(
        cfg, num_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        num_heads=heads, num_kv_heads=c["num_key_value_heads"],
        head_dim=c.get("head_dim") or c["hidden_size"] // heads,
        d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
        rope_theta=float(c["rope_theta"]), norm_eps=c["rms_norm_eps"],
        tie_embeddings=c["tie_word_embeddings"], dtype=c["torch_dtype"],
        qk_norm=c["model_type"] == "qwen3")
    if cfg.family != "dense" or cfg.mlp_act != "swiglu" or cfg.qkv_bias:
        raise ValueError(f"{cfg.name}: the reference covers dense SwiGLU "
                         "GQA without biases")
    return cfg


# -- seeded weights and adapters ------------------------------------------

def make_weights(cfg, seed: int):
    """Base weights in the program's layout, made on the device in one
    jitted call from the seed, in the type they are served in."""
    import jax
    import jax.numpy as jnp
    from repro.models import init_params
    struct = jax.eval_shape(lambda k: init_params(k, cfg),
                            jax.ShapeDtypeStruct((2,), jnp.uint32))
    leaves, tdef = jax.tree_util.tree_flatten_with_path(struct)

    def make(key):
        out = []
        for i, (path, sd) in enumerate(leaves):
            name = jax.tree_util.keystr(path)
            k = jax.random.fold_in(key, i)
            x = jax.random.normal(k, sd.shape, jnp.float32)
            if "embed" in name:
                x = x * 0.02
            elif len(sd.shape) == 3:                  # [L, fan_in, fan_out]
                x = x / np.sqrt(sd.shape[1])
            else:                                     # norm gains, as g - 1
                x = x * 0.1
            out.append(x.astype(sd.dtype))
        return jax.tree_util.tree_unflatten(tdef, out)

    return jax.jit(make)(jax.random.PRNGKey(seed))


def make_adapters(cfg, seed: int, n: int):
    """``n`` tenants' LoRA adapters in one jitted call: A ~ N(0, 1/fan_in),
    B ~ N(0, 0.01) (adapters part-way through training, so every leaf has
    a gradient from the first step)."""
    import jax
    import jax.numpy as jnp
    from repro.lora.adapters import init_lora
    struct = jax.eval_shape(lambda k: init_lora(k, cfg),
                            jax.ShapeDtypeStruct((2,), jnp.uint32))
    leaves, tdef = jax.tree_util.tree_flatten_with_path(struct)

    def make(key):
        trees = []
        for t in range(n):
            out = []
            for i, (path, sd) in enumerate(leaves):
                k = jax.random.fold_in(jax.random.fold_in(key, t), i)
                x = jax.random.normal(k, sd.shape, jnp.float32)
                if jax.tree_util.keystr(path).endswith("['a']"):
                    x = x / np.sqrt(sd.shape[1])
                else:
                    x = x * 0.01
                out.append(x.astype(sd.dtype))
            trees.append(jax.tree_util.tree_unflatten(tdef, out))
        return trees

    return jax.jit(make)(jax.random.PRNGKey(seed + 1))


# -- instrumentation of the runtime ----------------------------------------

@dataclasses.dataclass
class CheckRow:
    """A check tenant's finished episode and how many of its generated
    tokens came from its own adapter version alone: those accepted before
    the first write to its tenant's adapter slot after its submission. A
    write lands in flight (the runtime writes a tenant's new adapter when
    it feeds the tenant's next round), so a later token's logits, and the
    K/V it attends to, mix two versions that no single reference policy
    reproduces."""
    comp: object
    clean: int


@dataclasses.dataclass
class Episode:
    task_id: str
    version: int
    submitted_at: float
    finished_at: float
    sampled: int
    forced: int
    finish_reason: str
    submit_index: int


class Probe:
    """Wraps runtime entry points with bookkeeping that the window's
    metrics and the checks read: every submission's time, every completed
    episode (and, for check tenants, the whole completion and the time
    each of its tokens was accepted), every adapter slot write, and every
    commit (and, for the check tenants, each step's trained batch and
    loss, and the program's state norms after its first steps). The token
    hook is on the engine's row class; ``unpatch`` takes it off."""

    def __init__(self, rt, check_tids: Dict[str, int], check_steps: int,
                 init_lora: Dict[str, object]):
        import jax
        import jax.numpy as jnp
        from repro.rollout import engine as engine_mod
        self.rt = rt
        self.lock = threading.Lock()
        self.accepted: Dict[int, Dict[int, float]] = {}  # row -> {token: t}
        self.submits: List[float] = []
        self.episodes: List[Episode] = []
        self.check_rows: Dict[str, list] = {t: [] for t in check_tids}
        self.resumed: Dict[str, int] = {}
        self.commits: List[tuple] = []     # (time, tid, rows, lens)
        self.steps: Dict[str, list] = {t: [] for t in check_tids}
        self.check_steps = check_steps
        self.init_lora = init_lora
        self.switches: List[tuple] = []    # (time, tenant) of slot writes
        self.ctx_sum = 0
        self.row_steps = 0
        self.count_ctx = False
        b1 = rt._tc(rt.mgr.spec_for(next(iter(check_tids)))).adamw.b1

        def norms(tree):
            return jax.tree.map(lambda x: jnp.sqrt(jnp.sum(x * x)), tree)

        self._grad_norms = jax.jit(lambda m: norms(
            jax.tree.map(lambda x: x / (1.0 - b1), m)))
        self._delta_norms = jax.jit(lambda a, b: norms(
            jax.tree.map(jnp.subtract, a, b)))

        eng = rt.cengine
        sub, hc, tr1, stp = (eng.submit, rt._handle_completion,
                             rt._train_one, eng.step)
        put = eng.set_adapters

        def set_adapters(slot, tree):
            for tid, st in rt.mgr.task_items():
                if st.adapter_slot == slot:
                    with self.lock:
                        self.switches.append((time.monotonic(), tid))
            return put(slot, tree)

        def submit(req, meta=None):
            idx = sub(req, meta)
            with self.lock:
                self.submits.append(time.monotonic())
            return idx

        def handle(comp, rounds):
            ep = Episode(comp.task_id, comp.version, comp.submitted_at,
                         comp.finished_at, comp.sampled_tokens,
                         comp.forced_tokens, comp.finish_reason,
                         comp.submit_index)
            with self.lock:
                self.episodes.append(ep)
                if comp.forced_tokens > 0:
                    self.resumed[comp.task_id] = (
                        self.resumed.get(comp.task_id, 0) + 1)
                if comp.task_id in self.check_rows:
                    self.check_rows[comp.task_id].append(comp)
            return hc(comp, rounds)

        def train_one(tb, trained_version=None):
            tid = tb.task_id
            check = tid in self.steps
            if check:
                batch = {"tokens": tb.tokens, "prompt_lens": tb.prompt_lens,
                         "total_lens": tb.total_lens, "rewards": tb.rewards,
                         "loss_mask": tb.meta["loss_mask"],
                         "behavior_logprobs": tb.behavior_logprobs}
            with _Span("train_step"):
                tr1(tb, trained_version)
            now = time.monotonic()
            with self.lock:
                self.commits.append((now, tid, tb.num_rows,
                                     (tb.prompt_lens, tb.total_lens)))
            if check:
                st = rt.mgr.state(tid)
                step = {"batch": batch, "loss": st.loss_history[-1],
                        "version": tb.version}
                k = len(self.steps[tid]) + 1
                if k == 1:
                    step["grad_norms"] = jax.device_get(
                        self._grad_norms(st.opt_state["m"]))
                if k == self.check_steps:
                    step["delta_norms"] = jax.device_get(
                        self._delta_norms(st.adapters, self.init_lora[tid]))
                with self.lock:
                    self.steps[tid].append(step)

        def step():
            if self.count_ctx:
                rows = [r for r in getattr(eng, "_rows", ()) if r is not None]
                self.ctx_sum += sum(r.prompt_len + len(r.gen) for r in rows)
                self.row_steps += len(rows)
            with _Span("decode_step"):
                return stp()

        row_cls = engine_mod._Row
        accept = row_cls.accept

        def accept_timed(row, token, lp, mask, max_total):
            out = accept(row, token, lp, mask, max_total)
            if row.meta.get("task_id") in self.steps:
                with self.lock:
                    self.accepted.setdefault(row.submit_index, {})[
                        len(row.gen) - 1] = time.monotonic()
            return out

        eng.submit, rt._handle_completion = submit, handle
        eng.set_adapters = set_adapters
        rt._train_one, eng.step = train_one, step
        row_cls.accept = accept_timed
        self.unpatch = lambda: setattr(row_cls, "accept", accept)

    def check_done(self) -> bool:
        with self.lock:
            return all(len(v) >= self.check_steps
                       for v in self.steps.values())

    def release(self):
        self.rt = None


class _Span:
    """``bench:<name>`` host span in the profiler trace (traced runs)."""
    on = False

    def __init__(self, name):
        self.name = name
        self.ann = None

    def __enter__(self):
        if _Span.on:
            import jax
            self.ann = jax.profiler.TraceAnnotation("bench:" + self.name)
            self.ann.__enter__()

    def __exit__(self, *exc):
        if self.ann is not None:
            self.ann.__exit__(*exc)


class CompileCounter:
    """Backend compiles (and persistent-cache loads) as JAX reports them;
    one listener per process, however many runs it makes."""
    _instance = None

    def __new__(cls):
        if cls._instance is None:
            import jax
            from jax._src import dispatch
            cls._instance = super().__new__(cls)
            cls._instance.n = 0
            cls._instance.event = dispatch.BACKEND_COMPILE_EVENT
            jax.monitoring.register_event_duration_secs_listener(
                cls._instance._on)
        return cls._instance

    def _on(self, event, duration, **kw):
        if event == self.event:
            self.n += 1


def steps_in_window(commits: List[tuple], tids, t0: float, t1: float,
                    t_end: float) -> float:
    """GRPO steps committed in the window [t0, t1), over all tenants. A
    tenant's step runs from its previous commit to its own; a step in
    flight at either edge counts by the share of its interval inside the
    window, so the count is not quantised to whole commits. A tenant that
    had not committed again by ``t_end`` counts its open step as ending
    then."""
    total = 0.0
    for tid in tids:
        times = sorted(c[0] for c in commits if c[1] == tid)
        if times and times[-1] < t1:
            times.append(max(t_end, t1))
        for a, b in zip(times, times[1:]):
            total += max(0.0, min(b, t1) - max(a, t0)) / (b - a)
    return total


def check_rows(comps: List, switches: List[tuple],
               accepted: Dict[int, Dict[int, float]], t0: float,
               t1: float) -> List[CheckRow]:
    """A check tenant's usable episodes finished in the window [t0, t1)
    (all it finished where none did), each with the number of its leading
    generated tokens accepted before the first write to its tenant's slot
    after its submission."""
    ok = [c for c in comps if c.finish_reason not in FAILED_REASONS]
    win = [c for c in ok if t0 <= c.finished_at < t1]
    out = []
    for c in win or ok:
        first = min((t for t, tid in switches if tid == c.task_id
                     and t > c.submitted_at), default=math.inf)
        times = accepted.get(c.submit_index, {})
        n = len(c.tokens) - c.prompt_len
        clean = n if first == math.inf else next(
            (j for j in range(n) if times.get(j, math.inf) >= first), n)
        out.append(CheckRow(c, clean))
    return out


def count_work(episodes: List[Episode], submits: List[float], t0: float,
               t1: float):
    """(attempted, failed) over the window [t0, t1): ``attempted`` counts
    the rollout episodes active at any time in it (finished in it, or
    issued before its end and not finished by then: resident, queued or
    parked); ``failed`` those finished in it with no usable result. Both
    are ints; NoWork where nothing was active."""
    finished = sum(1 for e in episodes if t0 <= e.finished_at < t1)
    open_at_end = (sum(1 for t in submits if t < t1)
                   - sum(1 for e in episodes if e.finished_at < t1))
    attempted = int(finished + open_at_end)
    failed = int(sum(1 for e in episodes if t0 <= e.finished_at < t1
                     and e.finish_reason in FAILED_REASONS))
    if attempted < 1:
        raise NoWork(f"no rollout episode was active in the window "
                     f"[{t0:.3f}, {t1:.3f})")
    return attempted, failed


def _stats(eng) -> dict:
    st = eng.stats
    return {f.name: getattr(st, f.name) for f in dataclasses.fields(st)
            if isinstance(getattr(st, f.name), (int, float))}


# -- the run ---------------------------------------------------------------

def run(workload: str, seed: int, seconds: float, trace: bool, *,
        t_process: float, out_dir: Optional[Path] = None,
        overrides: Optional[dict] = None, log=None,
        calibrate: bool = False) -> dict:
    """One run of one cell; returns the result line's object. ``overrides``
    (tests only) replaces parts of the cell: ``config_file`` (a whole
    configuration file), ``mix`` (keys of the traffic mix), ``runtime``
    (RuntimeConfig fields), ``fault`` (a callable given the built
    runtime before it starts), ``limits`` (the limits of ``correct``),
    ``compile_cache`` (False: leave JAX's persistent cache as it is).
    ``calibrate`` (bench/calibrate.py only) adds the control's and the
    planted faults' readings."""
    import jax
    from bench import check
    from bench.traffic import generator
    from repro.core.manager import TaskSpec
    from repro.core.runtime import MARLaaSRuntime, RuntimeConfig

    log = log or (lambda m: print(m, file=sys.stderr, flush=True))
    overrides = overrides or {}
    bench = load_benchmark()
    conf_file, mix = find_cell(bench, workload)
    mix = dict(mix, **overrides.get("mix", {}))
    conf_file = overrides.get("config_file", conf_file)
    cfg = model_config(conf_file)
    rseed = generator.runtime_seed(seed)
    if overrides.get("compile_cache", True):
        log(f"compile cache: {compile_cache()}")
    compiles = CompileCounter()

    params = make_weights(cfg, rseed)
    tenants = generator.tenants(mix, seed, cfg.vocab_size)
    check_tids = {tenants[i].task_id: i for i in CHECK_TENANTS}
    adapters = make_adapters(cfg, rseed, len(tenants))
    rt_conf = conf_file["runtime"]
    rcfg = RuntimeConfig(
        policy="marlaas", rollout_mode="continuous",
        max_slots=rt_conf["max_slots"],
        max_adapter_slots=rt_conf["max_adapter_slots"],
        disagg_prefill=True, prefill_workers=rt_conf["prefill_workers"],
        prefill_chunk=rt_conf["prefill_chunk"],
        env_stage=True, env_workers=mix["env_workers"],
        max_turns=mix["max_turns"], paged_kv=True,
        kv_page_size=rt_conf["kv_page_size"],
        kv_pool_pages=rt_conf["kv_pool_pages"][str(mix["max_len"])],
        prefix_cache=True, async_train=True,
        max_staleness=mix["max_staleness"], min_train_rows=0,
        max_len=mix["max_len"], use_kernel=True, seed=rseed)
    rcfg = dataclasses.replace(rcfg, **overrides.get("runtime", {}))
    policy = generator.AgentPolicy(mix, seed)
    trainer = check.trainer_settings(mix)

    with policy.installed():
        rt = MARLaaSRuntime(cfg, params, rcfg)
        for t, lora in zip(tenants, adapters):
            rt.submit_task(TaskSpec(
                t.task_id, t.env, group_size=mix["group_size"],
                num_groups=mix["num_groups"],
                max_new_tokens=mix["max_new_tokens"], target_steps=10 ** 9,
                temperature=t.temperature, lr=mix["lr"]), adapters=lora)
            rt.envs[t.task_id] = generator.TrafficEnv(rt.envs[t.task_id], t,
                                                      seed)
        check.assert_trainer_matches(rt, trainer)
        init_lora = {tid: adapters[i] for tid, i in check_tids.items()}
        probe = Probe(rt, check_tids, CHECK_STEPS, init_lora)
        if "fault" in overrides:
            overrides["fault"](rt)
        agentic = [t.task_id for t in tenants
                   if rt.envs[t.task_id].is_agentic]
        err: List[BaseException] = []

        def drive():
            try:
                rt.run(timeout_s=1e9)
            except BaseException as e:        # surfaced by the main thread
                err.append(e)

        runner = threading.Thread(target=drive, name="bench-runtime",
                                  daemon=True)
        runner.start()
        try:
            deadline = time.monotonic() + WARMUP_TIMEOUT_S
            next_log = 0.0
            while True:
                if err or rt.error is not None:
                    raise RuntimeError("runtime failed during warm-up") from (
                        err[0] if err else rt.error)
                committed = all(s.steps_done >= 1
                                for _, s in rt.mgr.task_items())
                with probe.lock:
                    resumed = all(probe.resumed.get(t, 0) >= 1
                                  for t in agentic)
                if committed and resumed and probe.check_done():
                    break
                now = time.monotonic()
                if now >= next_log:
                    next_log = now + LOG_EVERY_S
                    st = rt.cengine.stats
                    ps = rt.cengine.page_stats()
                    log(f"warm-up {now - t_process:.0f} s: commits "
                        f"{[s.steps_done for _, s in rt.mgr.task_items()]}, "
                        f"resumed {len(probe.resumed)} tenants, decode "
                        f"steps {st.decode_steps}, sampled "
                        f"{st.sampled_tokens}, compiles {compiles.n}, "
                        f"pages {ps['kv_pages_used']:.0f}, snapshots "
                        f"{st.snapshots}, replays {st.replays}, restores "
                        f"{st.restores}, dev resumes "
                        f"{st.device_resident_resumes}, preemptions "
                        f"{st.preemptions}, exhausted {st.pool_exhausted}")
                if now > deadline:
                    raise RuntimeError(
                        f"warm-up not done in {WARMUP_TIMEOUT_S} s: commits "
                        f"{ {t: s.steps_done for t, s in rt.mgr.task_items()} }"
                        f", resumed {probe.resumed}")
                time.sleep(POLL_S)

            # -- the measured window --------------------------------------
            t0 = time.monotonic()
            setup_s = t0 - t_process
            c0, s0 = compiles.n, _stats(rt.cengine)
            drops0 = rt.mgr.drop_counters()
            tr = None
            span = seconds
            if trace:
                span = min(seconds, TRACE_SECONDS)
                tr = out_dir / "trace"
                _Span.on = True
                probe.count_ctx = True
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                opts.host_tracer_level = 1
                jax.profiler.start_trace(str(tr), profiler_options=opts)
                t_tr0 = time.monotonic()
                time.sleep(max(0.0, t_tr0 + span - time.monotonic()))
                # the trainer's metrics need a commit inside the span: go
                # on to the first one, within the window
                while time.monotonic() < t0 + seconds:
                    with probe.lock:
                        if any(c[0] >= t_tr0 for c in probe.commits):
                            break
                    time.sleep(POLL_S)
                t_tr1 = time.monotonic()
                s_tr, c_tr = _stats(rt.cengine), compiles.n
                drops_tr = rt.mgr.drop_counters()
                probe.count_ctx = False
                _Span.on = False
                jax.profiler.stop_trace()
            time.sleep(max(0.0, t0 + seconds - time.monotonic()))
            t1 = time.monotonic()
            s1 = _stats(rt.cengine)
            dev = jax.devices()[0]
            mem = dev.memory_stats() or {}
            device = {"platform": dev.platform, "kind": dev.device_kind,
                      "count": len(jax.devices()),
                      "memory_peak_bytes": int(mem.get("peak_bytes_in_use",
                                                       0))}
            if not trace:                  # the steps in flight at t1 end
                tail_end = t1 + TAIL_TIMEOUT_S
                while time.monotonic() < tail_end and not (err or rt.error):
                    with probe.lock:
                        after = {c[1] for c in probe.commits if c[0] >= t1}
                    if len(after) == len(tenants):
                        break
                    time.sleep(POLL_S)
            t_end = time.monotonic()
        finally:
            rt._stop.set()
            runner.join(timeout=300)
            probe.unpatch()
        if runner.is_alive():
            raise RuntimeError("runtime did not stop within 300 s")
        if err:
            raise RuntimeError("runtime failed") from err[0]

    # -- what the window did ----------------------------------------------
    with probe.lock:
        episodes = list(probe.episodes)
        submits = list(probe.submits)
        commits = list(probe.commits)
    done = [e for e in episodes if t0 <= e.finished_at < t1]
    attempted, failed = count_work(episodes, submits, t0, t1)
    accounting = check.row_accounting(rt)
    with rt.rec._lock:
        pages = [u for t, u, _, _ in rt.rec.page_samples if t0 <= t < t1]
    log(f"kv pages in use in the window: max {max(pages, default=0)} of "
        f"{rcfg.kv_pool_pages}; device memory peak "
        f"{device['memory_peak_bytes']}")
    restarts = {k: v for k, v in rt.rec.counters_snapshot().items()
                if k.startswith("supervisor_") and v}
    n_tenants = len(tenants)
    win_commits = [c for c in commits if t0 <= c[0] < t1]

    def delta(a, b):
        return {k: b[k] - a[k] for k in a}

    metrics: Dict[str, dict] = {}
    breakdown = None
    if not trace:
        lat = [e.finished_at - e.submitted_at for e in done]
        if not lat:
            raise NoWork("the window finished no episode; its latency "
                         "does not exist")
        n_steps = steps_in_window(commits, [t.task_id for t in tenants],
                                  t0, t1, t_end)
        metrics = {
            "step_time_s": {"value": seconds * n_tenants / n_steps,
                            "unit": "s"},
            "rollout_latency_p95_s": {"value": float(np.percentile(lat, 95)),
                                      "unit": "s"},
            "rollout_tokens_per_s": {
                "value": (s1["sampled_tokens"] - s0["sampled_tokens"])
                / seconds, "unit": "tokens/s"},
            "setup_s": {"value": setup_s, "unit": "s"},
        }
        log(f"window {seconds} s: {len(done)} episodes finished, "
            f"{len(win_commits)} commits ({n_steps:.3f} steps counting "
            f"those in flight at its edges; last tenant's next commit "
            f"{t_end - t1:.1f} s after it), setup {setup_s:.3f} s")
    else:
        from bench import metrics as readers
        from bench import trace as tracemod
        xp = tracemod.find_xplane(tr)
        dtr = tracemod.reduce_file(xp) if xp is not None else None
        tw = readers.Window(
            seconds=t_tr1 - t_tr0, stats=delta(s0, s_tr),
            drops=delta(drops0, drops_tr),
            commits=[c for c in commits if t_tr0 <= c[0] < t_tr1],
            episodes=[e for e in episodes
                      if t_tr0 <= e.finished_at < t_tr1],
            compiles=c_tr - c0, trace=dtr, ctx_sum=probe.ctx_sum,
            row_steps=probe.row_steps, shape=check.shape_of(conf_file),
            rank=cfg.lora.rank, peaks=None, kind=device["kind"])
        metrics = readers.read_all(bench, workload, tw)
        if dtr is not None and dtr.planes:
            device["busy_s"] = dtr.busy_s
            device["window_s"] = tw.seconds
            breakdown = tracemod.breakdown(dtr)
        log(f"traced {tw.seconds:.3f} s: {tw.row_steps} decoded row-steps, "
            f"{len(tw.commits)} commits, busy {device.get('busy_s')}")

    # -- free the program's state, then check against the reference --------
    steps = probe.steps
    rows = {tid: check_rows(comps, probe.switches, probe.accepted, t0, t1)
            for tid, comps in probe.check_rows.items()}
    probe.release()
    del rt, probe, runner
    gc.collect()
    checks, correct = check.run_checks(
        conf_file=conf_file, cfg=cfg, params=params, mix=mix, seed=seed,
        tenants=tenants, check_tids=check_tids, adapters=adapters,
        steps=steps, rows=rows, trainer=trainer, accounting=accounting,
        restarts=restarts, log=log, workload=workload, calibrate=calibrate,
        limit_values=overrides.get("limits"))
    calibration = checks.pop("_calibration", None)
    line = {"correct": bool(correct), "attempted": attempted,
            "failed": failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    if calibration is not None:
        line["calibration"] = calibration
    line["checks"] = checks
    return line


def finite(line: dict) -> bool:
    """Every number of a result line is finite."""
    def ok(x):
        if isinstance(x, dict):
            return all(ok(v) for v in x.values())
        if isinstance(x, (list, tuple)):
            return all(ok(v) for v in x)
        if isinstance(x, float):
            return math.isfinite(x)
        return True
    return ok(line)
