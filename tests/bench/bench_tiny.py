"""A reduced cell for CPU tests: Qwen3's code path at toy widths, four
tenants, the unshared mix's short prompts, short turns, jnp kernels (the
Pallas ones would run interpreted)."""
import copy
import json
import time

from bench import harness

CELL = "qwen3-0.6b.agentic-unshared"
_RUN = harness.run        # the harness itself, even where a test patches it


def config_file():
    conf = json.loads((harness.ROOT / "bench/configs/qwen3-0.6b.json")
                      .read_text())
    conf = copy.deepcopy(conf)
    conf["config"].update({"hidden_size": 64, "num_attention_heads": 4,
                           "num_key_value_heads": 2, "head_dim": 16,
                           "intermediate_size": 128, "vocab_size": 256,
                           "num_hidden_layers": 2})
    conf["runtime"].update({"max_slots": 8, "max_adapter_slots": 4,
                            "kv_pool_pages": {"128": 256}})
    return conf


MIX = {"tenants": 4, "system_prompt_tokens": 0, "max_len": 128,
       "turn_tokens": [4, 8], "answer_tokens": [1, 4], "env_workers": 8,
       "greedy_tenants": [1]}


# Limits for this toy size, set like the cells' from its own readings on
# the CPU (program at most 0.011 lp_gap, 0.0087 train_grad_gap, 0.0034
# train_update_gap over the seeds these tests use; the float8 control and
# the planted faults read at least 3x these, see test_bench_control.py).
LIMITS = {"rows_unaccounted": 0, "supervisor_restarts": 0, "lp_gap": 0.05,
          "greedy_gap": 0.05, "train_loss_gap": 0.006, "train_grad_gap": 0.03,
          "train_update_gap": 0.01}


def run(seed, seconds=1.0, fault=None, calibrate=False, workload=CELL):
    over = {"config_file": config_file(), "mix": dict(MIX),
            "runtime": {"use_kernel": False}, "limits": dict(LIMITS),
            "compile_cache": False}     # other tests share the process
    if fault is not None:
        over["fault"] = fault
    return _RUN(workload, seed, seconds, False,
                       t_process=time.monotonic(), overrides=over,
                       calibrate=calibrate, log=lambda m: None)
