"""Logical-axis sharding helpers.

Models annotate activations with *logical* axes ("dp", "tp", "sp"); the
launcher installs a mesh + logical→physical rules and annotations become
``with_sharding_constraint``. Outside a mesh context they are no-ops, so the
same model code runs single-device tests and 512-chip dry-runs unchanged.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Dict, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_TLS = threading.local()

# default logical→physical rules (single-pod); launcher overrides for multi-pod
DEFAULT_RULES: Dict[str, Union[str, Tuple[str, ...], None]] = {
    "dp": ("data",),         # batch / fsdp axis
    "tp": ("model",),        # tensor / expert axis
    "sp": ("model",),        # sequence axis for sharded long-KV decode
    None: None,
}

MULTIPOD_RULES: Dict[str, Union[str, Tuple[str, ...], None]] = {
    "dp": ("pod", "data"),
    "tp": ("model",),
    "sp": ("model",),
    None: None,
}


def _state():
    if not hasattr(_TLS, "mesh"):
        _TLS.mesh = None
        _TLS.rules = DEFAULT_RULES
    return _TLS


@contextlib.contextmanager
def mesh_context(mesh: Optional[Mesh], rules=None):
    st = _state()
    prev = (st.mesh, st.rules)
    st.mesh = mesh
    st.rules = rules or (MULTIPOD_RULES if (mesh is not None and "pod" in mesh.axis_names)
                         else DEFAULT_RULES)
    try:
        if mesh is not None:
            with jax.sharding.set_mesh(mesh):
                yield
        else:
            yield
    finally:
        st.mesh, st.rules = prev


def current_mesh() -> Optional[Mesh]:
    return _state().mesh


def resolve(*logical) -> P:
    rules = _state().rules
    phys = []
    for ax in logical:
        if ax is None:
            phys.append(None)
        elif isinstance(ax, (tuple, list)):
            flat = []
            for a in ax:
                r = rules.get(a, None)
                if r is None:
                    continue
                flat.extend([r] if isinstance(r, str) else list(r))
            phys.append(tuple(flat) if flat else None)
        else:
            r = rules.get(ax, None)
            if r is None:
                phys.append(None)
            elif isinstance(r, str):
                phys.append(r)
            else:
                phys.append(r if len(r) > 1 else r[0])
    return P(*phys)


def constrain(x, *logical):
    """with_sharding_constraint via logical axes; no-op without a mesh."""
    mesh = current_mesh()
    if mesh is None:
        return x
    spec = resolve(*logical)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def named_sharding(*logical) -> Optional[NamedSharding]:
    mesh = current_mesh()
    if mesh is None:
        return None
    return NamedSharding(mesh, resolve(*logical))
