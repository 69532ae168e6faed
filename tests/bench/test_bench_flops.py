"""The FLOP and byte functions against values worked out by hand at
Qwen3-0.6B's and Granite-3.0-2B's widths."""
import json

import pytest

from bench import flops, harness
from bench.configs import dense_gqa


def _shape(name):
    f = json.loads((harness.ROOT / f"bench/configs/{name}.json").read_text())
    return dense_gqa.shape(f["config"])


QWEN, GRANITE = _shape("qwen3-0.6b"), _shape("granite-3-2b")


def test_layer_and_lora_params():
    # Qwen3-0.6B: q 1024x2048, k,v 1024x1024, o 2048x1024, in 1024x6144,
    # out 3072x1024
    assert flops.layer_params(QWEN) == (2097152 + 2 * 1048576 + 2097152
                                        + 6291456 + 3145728)
    # rank 16 x sum(in + out) = 16 x (3072 + 2048 + 2048 + 3072 + 7168
    # + 4096)
    assert flops.lora_params(QWEN, 16) == 16 * 21504
    # Granite: q 2048x2048, k,v 2048x512, o 2048x2048, in 2048x16384,
    # out 8192x2048
    assert flops.layer_params(GRANITE) == (4194304 + 2 * 1048576 + 4194304
                                           + 33554432 + 16777216)


def test_dense_flops_per_token():
    want = 2 * (28 * (15728640 + 344064) + 1024 * 151936)
    assert flops.dense_flops_per_token(QWEN, 16) == want
    want_g = 2 * (40 * (60817408 + 16 * (4096 + 2560 + 2560 + 4096
                                         + 18432 + 10240))
                  + 2048 * 49155)
    assert flops.dense_flops_per_token(GRANITE, 16) == want_g


def test_decode_attention():
    # 3 rows at contexts 100, 200, 300 on Qwen: 4 * 28 * 16 * 128 * 600
    f, b = flops.decode_attention(QWEN, 600, 3)
    assert f == 4 * 28 * 16 * 128 * 600
    assert b == 600 * 28 * 2 * 8 * 128 * 2 + 3 * 28 * 2 * 16 * 128 * 2
    f, b = flops.decode_attention(GRANITE, 1000, 2)
    assert f == 4 * 40 * 32 * 64 * 1000
    assert b == 1000 * 40 * 2 * 8 * 64 * 2 + 2 * 40 * 2 * 32 * 64 * 2


def test_token_logprob():
    f, b = flops.token_logprob(QWEN, 1000, 2)
    assert f == 2 * 1000 * 1024 * 151936
    assert b == 2 * 1024 * 151936 * 2 + 1000 * 1024 * 2 + 1000 * 4
    f, _ = flops.token_logprob(GRANITE, 10, 1)
    assert f == 2 * 10 * 2048 * 49155


def test_train_flops():
    n = 10
    dense = flops.dense_flops_per_token(QWEN, 16) * n
    attn = 2 * 28 * 16 * 128 * n * (n + 1)
    lora = 2 * 28 * 16 * 21504 * n
    assert flops.train_flops(QWEN, 16, n) == 2 * dense + 3 * attn + lora


def test_least_seconds_and_peaks():
    pk = flops.peaks("TPU v5 lite")
    t, bound = flops.least_seconds(197e12, 1.0, pk)
    assert t == pytest.approx(1.0) and bound == "compute"
    t, bound = flops.least_seconds(1.0, 819e9, pk)
    assert t == pytest.approx(1.0) and bound == "memory"
    with pytest.raises(KeyError):
        flops.peaks("cpu")
