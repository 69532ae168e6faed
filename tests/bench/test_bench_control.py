"""The control at a test size: the reference computed in float8 (the
precision below the configuration's bfloat16), put in the program's place
on the same served tokens and trained batches, lies far above the
program's readings and comes out not correct under the same limits; so do
the planted faults (half a batch left out, a token altered). The chip
readings the limits are set from are in PERF.md."""
import bench_tiny

SEPARATION = 3.0          # an upper reading is at least 3x the lower one


def test_control_and_faults_separate_from_the_program():
    line = bench_tiny.run(21, calibrate=True)
    prog = {k: v["value"] for k, v in line["checks"].items()}
    cal = line["calibration"]
    assert line["correct"] is True, line["checks"]
    for k in ("lp_gap", "train_grad_gap", "train_loss_gap"):
        assert cal["control_" + k] >= SEPARATION * prog[k], (k, cal, prog)
    assert cal["halfbatch_train_grad_gap"] >= SEPARATION * prog[
        "train_grad_gap"]
    assert cal["altered_token_lp_gap"] >= SEPARATION * prog["lp_gap"]
    for name in ("control", "halfbatch", "altered_token"):
        assert cal[name + "_correct"] is False, (name, cal)


def test_served_logprobs_match_the_reference_in_float32():
    """With the program in float32 at toy size, the tokens of each row up
    to an in-flight adapter write read the reference's logprobs to
    rounding: the check compares like with like, and the cut is where the
    program's versions part (whole rows read far more)."""
    from bench import harness
    conf = bench_tiny.config_file()
    conf["config"]["torch_dtype"] = "float32"
    logs = []
    line = harness.run(bench_tiny.CELL, 31, 3.0, False, t_process=0.0,
                       overrides={"config_file": conf,
                                  "mix": dict(bench_tiny.MIX),
                                  "runtime": {"use_kernel": False},
                                  "limits": dict(bench_tiny.LIMITS),
                                  "compile_cache": False},
                       log=logs.append)
    assert line["correct"] is True, line["checks"]
    assert line["checks"]["lp_gap"]["value"] < 1e-4, line["checks"]
    assert line["checks"]["train_loss_gap"]["value"] < 1e-4
    said = [m for m in logs if m.startswith("checks:")]
    assert said and " 0 cut" not in said[0], said
