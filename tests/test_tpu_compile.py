"""Compile-only checks of the main path's Pallas kernels for a described
(not attached) TPU v5e at Qwen3-0.6B's widths, plus the paged decode
kernel at Granite's head size of 64 and token_logprob at granite's odd
vocabulary. Nothing runs: the TPU compiler must accept each
kernel (Mosaic refuses misaligned blocks and vector loads from SMEM that
interpret mode lets through) and leave a ``tpu_custom_call`` in the
program. The topology is described inside a fixture, so a worker without
libtpu skips these tests instead of failing at import."""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache as cc
from jax.sharding import SingleDeviceSharding

from repro.configs import REGISTRY
from repro.kernels.gqa_decode import gqa_decode
from repro.kernels.paged_decode import paged_gqa_decode
from repro.kernels.sgmv import sgmv
from repro.kernels.token_logprob import token_logprob_flat

QWEN = REGISTRY["qwen3-0.6b"]
B, PAGE, MAX_LEN, RANK, TENANTS = 8, 16, 96, 16, 4
ROWS = 4 * 96                                  # GRPO rows x sequence


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:              # no libtpu on this host
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # compiles for a described chip are written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def _compile_paged(cfg, sharding, batch, pool, max_pages):
    H, KVH, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    text = _compile(lambda q, k, v, t, p: paged_gqa_decode(q, k, v, t, p,
                                                           interpret=False),
                    sharding, ((batch, H, hd), jnp.bfloat16),
                    ((pool + 1, PAGE, KVH, hd), jnp.bfloat16),
                    ((pool + 1, PAGE, KVH, hd), jnp.bfloat16),
                    ((batch, max_pages), jnp.int32), ((batch,), jnp.int32))
    # the device trace finds the kernel by this name
    assert re.search(r"%paged_gqa_decode[.\d]* = .*custom-call", text)


def test_paged_gqa_decode_compiles(one_chip):
    _compile_paged(QWEN, one_chip, B, B * MAX_LEN // PAGE, MAX_LEN // PAGE)


def test_paged_gqa_decode_compiles_at_granite_widths(one_chip):
    """Head size 64 (a lane-padded minor dimension) at the benchmark's
    runtime shapes: 32 rows, 1024 pages, tables of 32."""
    _compile_paged(REGISTRY["granite-3-2b"], one_chip, 32, 1024, 32)


def test_gqa_decode_compiles(one_chip):
    H, KVH, hd = QWEN.num_heads, QWEN.num_kv_heads, QWEN.head_dim
    _compile(lambda q, k, v, p: gqa_decode(q, k, v, p, interpret=False),
             one_chip, ((B, H, hd), jnp.bfloat16),
             ((B, MAX_LEN, KVH, hd), jnp.bfloat16),
             ((B, MAX_LEN, KVH, hd), jnp.bfloat16), ((B,), jnp.int32))


def test_sgmv_compiles(one_chip):
    d = QWEN.d_model
    _compile(lambda x, a, b, i: sgmv(x, a, b, i, interpret=False),
             one_chip, ((B, d), jnp.bfloat16),
             ((TENANTS, d, RANK), jnp.float32),
             ((TENANTS, RANK, QWEN.q_dim), jnp.float32), ((B,), jnp.int32))


@pytest.mark.parametrize("arch", ["qwen3-0.6b", "granite-3-2b"])
def test_token_logprob_compiles(one_chip, arch):
    cfg = REGISTRY[arch]                # vocab 151936, and 49155 (odd)
    _compile(lambda h, w, t: token_logprob_flat(h, w, t, interpret=False),
             one_chip, ((ROWS, cfg.d_model), jnp.bfloat16),
             ((cfg.d_model, cfg.vocab_size), jnp.bfloat16),
             ((ROWS,), jnp.int32))
