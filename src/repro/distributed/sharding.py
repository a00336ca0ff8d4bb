"""Logical-axis sharding: models annotate tensors with *logical* axis names;
the launcher binds logical names to physical mesh axes. Outside a mesh
context every annotation is a no-op, so the same model code runs on one
device and under a mesh (``launch/shardings.py``'s rules).

Logical axes used by the model zoo:
  "batch"    — data-parallel batch dim            -> ("pod", "data")
  "seq"      — sequence/context dim               -> None
  "model"    — hidden size / head / expert shards -> "model"
  "vocab"    — embedding vocab shard              -> "model"
  "expert"   — MoE expert dim                     -> "model"
  "ff"       — MLP hidden dim                     -> "model"
  "heads"    — attention head dim                 -> "model"
  "kv_heads" — KV head dim (GQA)                  -> "model" when divisible
"""
from __future__ import annotations

import contextlib
import threading
from typing import Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_state = threading.local()

DEFAULT_RULES: dict[str, Optional[str | tuple[str, ...]]] = {
    "batch": ("pod", "data"),
    "seq": None,
    "model": "model",
    "vocab": "model",
    "expert": "model",
    "ff": "model",
    "heads": "model",
    "kv_heads": "model",
    "state": None,
}


def _current() -> tuple[Optional[Mesh], dict]:
    mesh = getattr(_state, "mesh", None)
    rules = getattr(_state, "rules", DEFAULT_RULES)
    return mesh, rules


@contextlib.contextmanager
def use_mesh(mesh: Mesh, rules: dict | None = None):
    """Bind a mesh + logical->physical rules for the enclosed region. Rules
    entries may name mesh axes that don't exist on this mesh — they are
    dropped (so the same rules work for single- and multi-pod meshes)."""
    prev = (getattr(_state, "mesh", None), getattr(_state, "rules", DEFAULT_RULES))
    eff_rules = dict(DEFAULT_RULES)
    if rules:
        eff_rules.update(rules)
    # prune axes not present on the mesh
    pruned: dict[str, Optional[str | tuple[str, ...]]] = {}
    for k, v in eff_rules.items():
        if v is None:
            pruned[k] = None
        elif isinstance(v, tuple):
            kept = tuple(a for a in v if a in mesh.axis_names)
            pruned[k] = kept if kept else None
        else:
            pruned[k] = v if v in mesh.axis_names else None
    _state.mesh, _state.rules = mesh, pruned
    try:
        with mesh:
            yield
    finally:
        _state.mesh, _state.rules = prev


def logical_to_spec(*logical_axes: Optional[str]) -> P:
    """Translate logical axis names (one per tensor dim; None = replicated)
    into a PartitionSpec under the current rules."""
    _, rules = _current()
    return P(*[rules.get(a) if a is not None else None for a in logical_axes])


def shard(x: jax.Array, *logical_axes: Optional[str]) -> jax.Array:
    """Annotate ``x`` with a sharding constraint; no-op without a mesh.
    Axes whose dim size does not divide the mapped mesh axes are dropped
    (so e.g. a "seq" constraint is harmless on a 1-token decode step)."""
    mesh, _ = _current()
    if mesh is None:
        return x
    spec = logical_to_spec(*logical_axes)
    entries = []
    for dim, entry in zip(x.shape, tuple(spec) + (None,) * (x.ndim - len(spec))):
        if entry is None:
            entries.append(None)
            continue
        axes = (entry,) if isinstance(entry, str) else tuple(entry)
        size = 1
        for a in axes:
            size *= mesh.shape[a]
        entries.append(entry if dim % size == 0 and dim >= size else None)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*entries)))


def named_sharding(*logical_axes: Optional[str]) -> Optional[NamedSharding]:
    mesh, _ = _current()
    if mesh is None:
        return None
    return NamedSharding(mesh, logical_to_spec(*logical_axes))


def current_mesh() -> Optional[Mesh]:
    return _current()[0]


def axis_size(logical: str) -> int:
    """Product of mesh-axis sizes the logical axis maps to (1 if unmapped)."""
    mesh, rules = _current()
    if mesh is None:
        return 1
    phys = rules.get(logical)
    if phys is None:
        return 1
    if isinstance(phys, str):
        phys = (phys,)
    size = 1
    for a in phys:
        size *= mesh.shape[a]
    return size
