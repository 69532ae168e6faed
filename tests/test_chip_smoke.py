"""CPU rehearsal of chip_smoke.py's phases 3 and 4 on the reduced
qwen3-0.6b config: the kernels run in interpret mode, every stage of the
runtime is on, and the TPU check of phase 1 is not part of these calls."""
import importlib.util
import math
from pathlib import Path

import jax
import pytest

from repro.launch.train import model_config
from repro.models import init_params

_PATH = Path(__file__).resolve().parents[1] / "chip_smoke.py"


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", _PATH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def model():
    cfg = model_config("qwen3-0.6b", reduce=True)
    return cfg, init_params(jax.random.PRNGKey(0), cfg)


def test_phase_model_kernel_matches_jnp(smoke, model):
    cfg, params = model
    diff = smoke.phase_model(cfg, params, seed=0, steps=6)
    assert max(diff.values()) < 1e-3      # fp32 model: summation order only


def test_phase_runtime_commits_with_kernels(smoke, model):
    cfg, params = model
    out = smoke.phase_runtime(cfg, params, seed=0, timeout_s=600.0)
    assert out["commits"] == {t: 2 for t in out["commits"]}
    assert out["parks"] > 0 and out["resumes"] > 0
    assert all(math.isfinite(x) for v in out["losses"].values() for x in v)


def test_phase_device_refuses_cpu(smoke):
    with pytest.raises(smoke.SmokeFailure, match="no TPU"):
        smoke.phase_device()


# -- the training CLI shares the builder ------------------------------------

def test_cli_builds_the_registry_config_unreduced():
    from repro.configs import REGISTRY
    assert model_config("qwen3-0.6b") == REGISTRY["qwen3-0.6b"]
    assert model_config("qwen3-0.6b", reduce=True).vocab_size < 151936


@pytest.mark.parametrize("timeout,rc", [(600.0, 0), (0.0, 1)])
def test_cli_exit_code_follows_target_steps(monkeypatch, timeout, rc):
    """Exit 0 when every tenant commits --steps, 1 when the run ends (here
    at a zero deadline) with a tenant short of them."""
    import repro.launch.train as cli
    monkeypatch.setattr(cli, "enable_compile_cache", lambda: "")
    assert cli.main(["--arch", "qwen3-0.6b", "--reduced", "--tasks", "1",
                     "--steps", "1", "--timeout", str(timeout)]) == rc
