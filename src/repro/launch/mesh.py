"""Production meshes. Single pod: 16×16 = 256 chips (data, model).
Multi-pod: 2×16×16 = 512 chips (pod, data, model) — the 'pod' axis carries
only hierarchical data parallelism (reduce-scatter intra-pod, cross-pod
all-reduce on scattered shards; DCN-friendly).

Defined as functions, never module-level constants: importing this module
must not touch jax device state (the dry-run pins a 512-device host platform
before any jax import). Every axis is explicitly Auto."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    """`jax.make_mesh` with explicit Auto axis_types."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_abstract_mesh(shape, axes):
    """Device-less AbstractMesh with explicit Auto axis_types."""
    return jax.sharding.AbstractMesh(tuple(shape), tuple(axes),
                                     axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False, kind: str = "train"):
    """kind="train": 16×16 (balanced FSDP×TP). kind="serve": 32×8 — tp=8
    divides every assigned arch's kv_heads, so decode caches shard on the
    kv-head dim and per-row cache writes stay shard-local and in-place
    (EXPERIMENTS.md §Perf iter A3). Same 256 chips/pod either way."""
    if kind == "serve":
        shape = (2, 32, 8) if multi_pod else (32, 8)
    else:
        shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh():
    """Whatever this host actually has — used by tests/examples (1 device)."""
    n = len(jax.devices())
    return make_mesh((n, 1), ("data", "model"))
