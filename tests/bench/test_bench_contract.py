"""BENCHMARK.json keeps to the benchmark's contract, and every cell's
configuration, traffic, metric readers and limits are found by name."""
import json
import re

import pytest

from bench import harness
from bench.traffic import generator

ROOT = harness.ROOT
B = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
E2E = {m["name"] for m in B["end_to_end"]}
CELLS = {w["name"]: w for w in B["workloads"]}
CHECKS = ("rows_unaccounted", "supervisor_restarts", "lp_gap", "greedy_gap",
          "train_loss_gap", "train_grad_gap", "train_update_gap")


def _line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_keys():
    assert set(B) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= len(B["command"]) <= 32 and all(map(_line, B["command"]))
    assert 1 <= len(B["paths"]) <= 16
    for p in B["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert (ROOT / p).is_dir()
    for word in B["command"][1:]:
        assert not word.startswith("/") and ".." not in word
        assert any(word.startswith(p + "/") for p in B["paths"])
    assert isinstance(B["run_seconds"], int) and 1 <= B["run_seconds"] <= 51
    assert len(json.dumps(B)) <= 64 * 1024


@pytest.mark.parametrize("kind,keys", [
    ("configs", {"name", "source", "file", "reduced", "why"}),
    ("workloads", {"name", "config", "traffic", "chips", "why"}),
    ("end_to_end", {"name", "unit", "better", "bound", "source"}),
    ("per_layer", {"name", "unit", "better", "source", "layer", "moves"}),
])
def test_entries_have_their_keys_and_names(kind, keys):
    names = [e["name"] for e in B[kind]]
    assert len(set(names)) == len(names)
    for e in B[kind]:
        assert set(e) - {"workloads"} == keys, e
        assert NAME.match(e["name"]), e["name"]
        if "unit" in e:
            assert UNIT.match(e["unit"]) and e["better"] in ("lower",
                                                              "higher")
        for k in ("why", "layer", "source"):
            if k in e:
                assert _line(e[k]), e


def test_metric_names_unique_across_kinds():
    names = [m["name"] for m in B["end_to_end"] + B["per_layer"]]
    assert len(set(names)) == len(names)


def test_end_to_end_bounds_and_sources():
    assert "setup_s" in E2E and 2 <= len(E2E) <= 16
    for m in B["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25


def test_cells_name_their_config_and_traffic():
    confs = {c["name"]: c for c in B["configs"]}
    pairs = {(w["config"], w["traffic"]) for w in B["workloads"]}
    assert len(pairs) == len(B["workloads"])
    for w in B["workloads"]:
        assert w["config"] in confs and w["chips"] in (1, 4)
        assert NAME.match(w["traffic"])
        generator.load(w["traffic"])             # the mix file is found
    used = {w["config"] for w in B["workloads"]}
    assert used == set(confs)
    assert sum(w["chips"] == 4 for w in B["workloads"]) <= max(
        1, len(B["workloads"]) // 2)


@pytest.mark.parametrize("conf", B["configs"], ids=lambda c: c["name"])
def test_config_file_and_reference_found(conf):
    path = ROOT / conf["file"]
    assert any(conf["file"].startswith(p + "/") for p in B["paths"])
    f = json.loads(path.read_text())
    assert f["name"] == conf["name"] and f["source"] == conf["source"]
    assert f["reduced"] == conf["reduced"]
    for k in conf["reduced"]:
        assert NAME.match(k)
        assert not (k.endswith(("_dim", "_rank", "_size")) or "head" in k
                    or "expert" in k), f"a width may not be reduced: {k}"
    assert (ROOT / "bench/configs" / f"{f['reference']}.py").is_file()
    cfg = harness.model_config(f)
    c = f["config"]
    assert (cfg.num_layers, cfg.d_model, cfg.d_ff, cfg.vocab_size) == (
        c["num_hidden_layers"], c["hidden_size"], c["intermediate_size"],
        c["vocab_size"])


@pytest.mark.parametrize("metric", B["per_layer"], ids=lambda m: m["name"])
def test_per_layer_reader_found_and_moves_is_reported(metric):
    from bench import metrics
    assert callable(metrics.reader(metric["name"]))
    assert metric["moves"] in E2E
    for cell in metric.get("workloads", CELLS):
        assert cell in CELLS
        for m in B["end_to_end"]:          # every cell reports every e2e
            assert "workloads" not in m or cell in m["workloads"]
    assert metric["source"] in ("device_trace", "program_span",
                                "program_counter", "host_clock")


def test_layer_names_consistent():
    layers = {m["layer"] for m in B["per_layer"]}
    assert all(_line(x) for x in layers)
    for m in B["per_layer"]:
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%" and m["layer"] == "kernels"
        if "mfu" in m["name"]:
            assert m["unit"] == "%"


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_limits_found_for_every_cell(cell):
    from bench import check
    lim = check.limits(cell)
    assert set(lim) == set(CHECKS)
    assert lim["rows_unaccounted"] == 0 and lim["supervisor_restarts"] == 0


def test_check_budget_fits():
    n = 24
    per_cell = 14 * (B["run_seconds"] + 60) + 2 * 90
    assert 2 * (B["run_seconds"] + 60) + n * per_cell + 1200 <= 43200
