"""Per-architecture sharding rules.

Logical->physical rules are computed per arch (divisibility-guarded), and
parameter/cache/batch PartitionSpecs are derived from pytree paths.
Anything that cannot shard cleanly falls back to replication.
``tests/test_tpu_compile.py`` compiles the serving and training steps under
these rules for a described v5e:2x2, and ``tests/test_shardings.py`` runs
decode under them on a four-device mesh.
"""
from __future__ import annotations

from typing import Any, Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig

MODEL_AXIS = "model"
DATA_AXES = ("pod", "data")


def _mesh_size(mesh: Mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    n = 1
    for a in axes:
        n *= mesh.shape.get(a, 1) if hasattr(mesh.shape, "get") else dict(zip(mesh.axis_names, mesh.devices.shape))[a]
    return n


def make_rules(cfg: ArchConfig, mesh: Mesh) -> dict[str, Optional[str | tuple[str, ...]]]:
    """Logical-axis rules for this arch on this mesh (divisibility-guarded)."""
    msize = _mesh_size(mesh, MODEL_AXIS)
    data_axes = tuple(a for a in DATA_AXES if a in mesh.axis_names)
    return {
        "batch": data_axes or None,
        "seq": None,
        "model": MODEL_AXIS if cfg.d_model % msize == 0 else None,
        "vocab": MODEL_AXIS if cfg.vocab % msize == 0 else None,
        "expert": MODEL_AXIS if (cfg.moe and cfg.moe.n_experts % msize == 0) else None,
        "ff": MODEL_AXIS,
        "heads": MODEL_AXIS if cfg.n_heads % msize == 0 else None,
        "kv_heads": MODEL_AXIS if cfg.n_kv_heads % msize == 0 else None,
        "state": None,
    }


def _guard(spec: P, shape: tuple[int, ...], mesh: Mesh) -> P:
    """Drop spec axes whose dim isn't divisible by the mesh-axis product."""
    out = []
    for i, entry in enumerate(spec):
        if entry is None:
            out.append(None)
            continue
        if shape[i] % _mesh_size(mesh, entry) != 0:
            out.append(None)
        else:
            out.append(entry)
    return P(*out)


def _path_str(path) -> str:
    return "/".join(
        str(getattr(p, "key", getattr(p, "idx", getattr(p, "name", p)))) for p in path
    )


def param_spec(path: str, shape: tuple[int, ...], cfg: ArchConfig, mesh: Mesh) -> P:
    """PartitionSpec for one parameter leaf (layer-stacked leaves have a
    leading L dim — detected by ndim vs the table below)."""
    name = path.split("/")[-1]
    M = MODEL_AXIS
    nd = len(shape)

    def tail(spec_tail: tuple) -> P:
        """Right-align the spec; leading (layer-stack) dims replicate."""
        lead = (None,) * (nd - len(spec_tail))
        return P(*(lead + spec_tail))

    if name == "embed":
        spec = P(M, None)
    elif name == "unembed":
        spec = P(None, M)
    elif name in ("wq",):
        spec = tail((None, M, None))        # (d, H, hd)
    elif name in ("wk", "wv"):
        spec = tail((None, M, None))        # (d, K, hd)
    elif name == "wo" and nd >= 3:
        spec = tail((M, None, None))        # (H, hd, d)
    elif name in ("w_gate", "w_up"):
        if cfg.moe is not None and nd >= 3 and shape[-3] == cfg.moe.n_experts:
            spec = tail((M, None, None))    # (E, d, de): expert-sharded
        else:
            spec = tail((None, M))          # (d, F)
    elif name == "w_down":
        if cfg.moe is not None and nd >= 3 and shape[-3] == cfg.moe.n_experts:
            spec = tail((M, None, None))    # (E, de, d)
        else:
            spec = tail((M, None))          # (F, d)
    elif name == "router":
        spec = tail((None, None))
    elif name in ("w_in",):                 # mamba in_proj (d, mixed-out)
        spec = tail((None, None))
    elif name == "w_out" and nd >= 2:
        spec = tail((M, None))              # (d_inner, d) row-parallel
    elif name in ("w_i", "w_f"):
        spec = tail((M, None))              # (d_inner, H)
    elif name == "R":
        spec = tail((None, None, None, None)) if nd >= 4 else P(*([None] * nd))
    elif name == "conv_w":
        spec = tail((None, M))              # (K, conv_dim) channel-sharded
    elif name in ("conv_b", "ynorm", "hnorm"):
        spec = tail((M,))
    else:
        spec = P(*([None] * nd))
    # pad/truncate to ndim
    entries = list(spec)
    entries = entries[:nd] + [None] * (nd - len(entries))
    return _guard(P(*entries), shape, mesh)


def cache_spec(path: str, shape: tuple[int, ...], cfg: ArchConfig, mesh: Mesh,
               data_axes) -> P:
    """KV caches / recurrent state sharding for decode/prefill."""
    parts = path.split("/")
    # a transformer keeps its "k" and "v" stacks by kind of layer
    name = parts[-2] if len(parts) > 1 and parts[-1] in ("window", "full") else parts[-1]
    M = MODEL_AXIS
    msize = _mesh_size(mesh, M)
    nd = len(shape)
    if name in ("k", "v", "attn_k", "attn_v", "cross_k", "cross_v"):
        # (L, B, K, S, hd): shard batch on data; kv-heads on model if they
        # divide; else head_dim (updates stay local, attention pays a small
        # score all-reduce — §Perf pick-3 iter-2: S-sharding made the
        # per-step cache update all-gather the whole cache); else the
        # cache LENGTH as last resort.
        if cfg.n_kv_heads % msize == 0:
            spec = P(None, data_axes, M, None, None)
        elif cfg.head_dim % msize == 0:
            spec = P(None, data_axes, None, None, M)
        else:
            spec = P(None, data_axes, None, M, None)
    elif name == "h":                        # mamba state (L, B, H, N, P)
        spec = P(None, data_axes, M, None, None)
    elif name == "conv":                     # (L, B, K-1, conv_dim)
        spec = P(None, data_axes, None, M)
    elif name == "lengths":
        spec = P(data_axes)
    elif name in ("0", "1", "2", "3"):
        # xlstm tuple states: mLSTM (count,B,H,P,P)/(count,B,H,P)/(count,B,H)
        # or sLSTM (B,H,P): shard batch; shard the first P axis on model.
        if nd == 5:
            spec = P(None, data_axes, None, M, None)
        elif nd == 4:
            spec = P(None, data_axes, None, M)
        elif nd == 3:
            spec = P(data_axes, None, M)
        else:
            spec = P(*([None] * nd))
    else:
        spec = P(*([None] * nd))
    entries = list(spec)[:nd] + [None] * (nd - len(list(spec)))
    return _guard(P(*entries), shape, mesh)


def batch_spec(path: str, shape: tuple[int, ...], mesh: Mesh, data_axes) -> P:
    spec = P(data_axes, *([None] * (len(shape) - 1)))
    return _guard(spec, shape, mesh)


def shard_inputs(cfg: ArchConfig, mesh: Mesh, specs: dict[str, Any]):
    """Attach NamedShardings to ``{"batch": ..., "cache": ... or None}``,
    pytrees of ShapeDtypeStructs. Returns (batch_sds, cache_sds) with
    .sharding set."""
    data_axes = tuple(a for a in DATA_AXES if a in mesh.axis_names)
    data_axes = data_axes if data_axes else None

    def with_sharding(tree, fn):
        def one(path, leaf):
            spec = fn(_path_str(path), tuple(leaf.shape))
            return jax.ShapeDtypeStruct(
                leaf.shape, leaf.dtype, sharding=NamedSharding(mesh, spec)
            )

        return jax.tree_util.tree_map_with_path(one, tree)

    batch = with_sharding(
        specs["batch"], lambda p, s: batch_spec(p, s, mesh, data_axes)
    )
    cache = None
    if specs["cache"] is not None:
        cache = with_sharding(
            specs["cache"], lambda p, s: cache_spec(p, s, cfg, mesh, data_axes)
        )
    return batch, cache


def shard_params_like(params_shape, cfg: ArchConfig, mesh: Mesh):
    """ShapeDtypeStruct param tree with NamedShardings attached."""

    def one(path, leaf):
        spec = param_spec(_path_str(path), tuple(leaf.shape), cfg, mesh)
        return jax.ShapeDtypeStruct(
            leaf.shape, leaf.dtype, sharding=NamedSharding(mesh, spec)
        )

    return jax.tree_util.tree_map_with_path(one, params_shape)
