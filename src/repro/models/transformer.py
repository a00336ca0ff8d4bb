"""Dense (and MoE — the FFN is pluggable) decoder-only transformer with
scanned layer stacks, KV-cache prefill/decode, and sliding-window support.

Used directly by: llama3.2-1b, phi3-mini, qwen3, mistral-large-123b,
chameleon-34b (early-fusion VLM: image tokens are ordinary vocab ids), and
with MoE FFNs by deepseek-moe-16b / granite-moe-1b / mellum2-12b-a2.5b.

Layers come in kinds, "window" (attention over the last ``sliding_window``
positions, a ring cache of that many slots) and "full", laid out by
``cfg.period``: the layer scan steps one period at a time and unrolls the
period's layers in its body, so a model whose layers are all alike is a
period of one layer. The KV cache holds one stack per kind of layer.
"""
from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.distributed.sharding import shard
from .attention import decode_attention_step, init_attention, prefill_attention
from .layers import cross_entropy, init_swiglu, normal_init, rms_norm, swiglu, unembed
from . import moe as moe_lib


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def _init_layer(cfg: ArchConfig, key) -> dict[str, Any]:
    k_attn, k_mlp = jax.random.split(key)
    p = {
        "ln1": jnp.ones((cfg.d_model,), cfg.jax_dtype),
        "attn": init_attention(
            k_attn, cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
            cfg.qk_norm, cfg.jax_dtype,
        ),
        "ln2": jnp.ones((cfg.d_model,), cfg.jax_dtype),
    }
    if cfg.moe is not None:
        p["mlp"] = moe_lib.init_moe(k_mlp, cfg)
    else:
        p["mlp"] = init_swiglu(k_mlp, cfg.d_model, cfg.d_ff, cfg.jax_dtype)
    return p


def init_params(cfg: ArchConfig, key) -> dict[str, Any]:
    k_emb, k_layers, k_out = jax.random.split(key, 3)
    layer_keys = jax.random.split(k_layers, cfg.n_layers)
    layers = jax.vmap(functools.partial(_init_layer, cfg))(layer_keys)
    params = {
        "embed": normal_init(k_emb, (cfg.vocab, cfg.d_model), 1.0, cfg.jax_dtype),
        "layers": layers,
        "final_norm": jnp.ones((cfg.d_model,), cfg.jax_dtype),
    }
    if not cfg.tie_embeddings:
        params["unembed"] = normal_init(
            k_out, (cfg.d_model, cfg.vocab), cfg.d_model**-0.5, cfg.jax_dtype
        )
    return params


# ---------------------------------------------------------------------------
# Layer application
# ---------------------------------------------------------------------------


def _mlp_apply(cfg: ArchConfig, p_mlp, x):
    """Training forward. Returns (y, aux_loss); MoE dispatches by capacity."""
    if cfg.moe is not None:
        return moe_lib.apply_moe(cfg, p_mlp, x)
    return swiglu(x, p_mlp["w_gate"], p_mlp["w_up"], p_mlp["w_down"]), 0.0


def _mlp_serve(cfg: ArchConfig, p_mlp, x, layer=None):
    """Prefill and decode. Returns (y, 0.0); MoE drops no token. With
    ``layer``, a MoE's ``p_mlp`` is every layer's stacked (decode)."""
    if cfg.moe is not None:
        return moe_lib.apply_moe_dropless(cfg, p_mlp, x, layer), 0.0
    return swiglu(x, p_mlp["w_gate"], p_mlp["w_up"], p_mlp["w_down"]), 0.0


def _attn_args(cfg: ArchConfig, kind: str) -> dict:
    r = cfg.rope_of(kind)
    return dict(rope_theta=r.theta, eps=cfg.norm_eps, window=cfg.window_of(kind),
                yarn=r if r.yarn_factor else None)


# The named scopes "attn", "mlp" and "lm_head" (and "kv_write" inside
# attention) label each compiled op's op_name; bench/scopes.py splits an
# executable's device time by them. Inside "attn" the layer's kind
# ("window" or "full") names its attention, inside "mlp" a MoE names its
# "moe_route", "moe_experts" and "moe_combine".
def _layer_prefill(cfg: ArchConfig, p, x, positions, kind, serve: bool):
    """The training forward's layer, or with ``serve`` the serving
    prefill's: the MoE drops no token and a full causal layer may take the
    flash kernel, which has no reverse-mode rule."""
    mlp = _mlp_serve if serve else _mlp_apply
    with jax.named_scope("attn"):
        with jax.named_scope(kind):
            h, (k, v) = prefill_attention(
                p["attn"], rms_norm(x, p["ln1"], cfg.norm_eps), positions, causal=True,
                flash=serve, **_attn_args(cfg, kind),
            )
        x = x + h
    with jax.named_scope("mlp"):
        m, aux = mlp(cfg, p["mlp"], rms_norm(x, p["ln2"], cfg.norm_eps))
        return x + m, (k, v), aux


def _layer_decode(cfg: ArchConfig, p, x, k_cache, v_cache, lengths, kind, layer,
                  mlp_layer=None):
    """k_cache/v_cache: the stacked (L_kind, B, K, S, hd) caches of this
    layer's kind; ``layer``'s row is written in place and the stacks are
    returned. With ``mlp_layer``, ``p["mlp"]`` is every layer's stacked and
    the MoE reads layer ``mlp_layer``'s experts where they lie."""
    with jax.named_scope("attn"):
        with jax.named_scope(kind):
            h, k_cache, v_cache = decode_attention_step(
                p["attn"], rms_norm(x, p["ln1"], cfg.norm_eps), k_cache, v_cache, lengths,
                layer=layer, **_attn_args(cfg, kind),
            )
        x = x + h
    with jax.named_scope("mlp"):
        m, _ = _mlp_serve(cfg, p["mlp"], rms_norm(x, p["ln2"], cfg.norm_eps), mlp_layer)
        return x + m, k_cache, v_cache


# ---------------------------------------------------------------------------
# The period scan and the per-kind cache stacks
# ---------------------------------------------------------------------------


def _kinds(cfg: ArchConfig) -> tuple[str, ...]:
    """The kinds of layer, in the order they first appear in a period."""
    return tuple(dict.fromkeys(cfg.period))


def _periods(cfg: ArchConfig) -> jax.Array:
    """The serving scans' xs: the index of each period."""
    return jnp.arange(cfg.n_layers // len(cfg.period), dtype=jnp.int32)


def _layer(cfg: ArchConfig, layers, i, j):
    """The weights of period ``i``'s ``j``-th layer, indexed in the stacked
    arrays where they lie: a period's block of weights as the scan's xs is
    copied out every decode step before its layers are sliced from it."""
    P = len(cfg.period)
    return jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i * P + j, 0, keepdims=False), layers)


def _stack_index(cfg: ArchConfig, i, j):
    """Where period ``i``'s ``j``-th layer lies in its kind's cache stack."""
    kind = cfg.period[j]
    return i * cfg.period.count(kind) + cfg.period[:j].count(kind)


# ---------------------------------------------------------------------------
# Public model functions
# ---------------------------------------------------------------------------


def forward(
    cfg: ArchConfig,
    params,
    tokens: jax.Array,  # (B, S) int32
    *,
    remat: bool = True,
) -> tuple[jax.Array, jax.Array]:
    """Training/prefill forward pass. Returns (logits (B,S,V), aux_loss)."""
    B, S = tokens.shape
    x = jnp.take(params["embed"], tokens, axis=0)
    x = shard(x, "batch", "seq", None)
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))

    # period blocks as the scan's xs, so the weights' gradient comes out
    # stacked as its ys
    P = len(cfg.period)
    blocks = jax.tree.map(lambda a: a.reshape(-1, P, *a.shape[1:]), params["layers"])

    def body(x, block):
        auxs = []
        for j, kind in enumerate(cfg.period):
            p = jax.tree.map(lambda a: a[j], block)
            x, _, aux = _layer_prefill(cfg, p, x, positions, kind, serve=False)
            auxs.append(aux)
        return x, sum(auxs[1:], auxs[0])

    if remat:
        body = jax.checkpoint(body)
    x, auxs = jax.lax.scan(body, x, blocks)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = unembed(x, params["unembed"] if "unembed" in params else params["embed"].T)
    return logits, jnp.sum(auxs)


def loss_fn(cfg: ArchConfig, params, batch, *, remat: bool = True):
    logits, aux = forward(cfg, params, batch["tokens"], remat=remat)
    ce, nll = cross_entropy(logits, batch["labels"])
    return ce + aux, {"ce": ce, "nll": nll, "aux": aux}


def init_cache(cfg: ArchConfig, batch: int, max_len: int):
    """KV cache pytree: "k" and "v" each map a kind of layer to its stack
    (L_kind, B, K, S, hd). A window layer's cache is a ring of
    min(max_len, window) slots."""
    k, v = {}, {}
    for kind in _kinds(cfg):
        window = cfg.window_of(kind)
        S = min(max_len, window) if window is not None else max_len
        L = cfg.n_layers // len(cfg.period) * cfg.period.count(kind)
        shape = (L, batch, cfg.n_kv_heads, S, cfg.head_dim)
        k[kind] = jnp.zeros(shape, cfg.jax_dtype)
        v[kind] = jnp.zeros(shape, cfg.jax_dtype)
    return {"k": k, "v": v, "lengths": jnp.zeros((batch,), jnp.int32)}


def _fill(stack: jax.Array, ks: jax.Array, S: int, window: Optional[int]) -> jax.Array:
    """A cache stack holding the prompt's (L, B, K, S, hd) keys or values."""
    S_c = stack.shape[3]
    if window is not None and S > S_c:
        # keep the last `window` positions; ring alignment: slot = pos % window
        ks = jnp.roll(ks[:, :, :, -S_c:], shift=(S - S_c) % S_c, axis=3)
    return stack.at[:, :, :, : ks.shape[3]].set(ks) if ks.shape[3] < S_c else ks


def prefill(cfg: ArchConfig, params, tokens: jax.Array, cache):
    """Run the prompt through the stack, filling each kind's cache stack.
    Returns (last-token logits, cache)."""
    B, S = tokens.shape
    x = jnp.take(params["embed"], tokens, axis=0)
    x = shard(x, "batch", "seq", None)
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))

    def body(x, i):
        kv = {kind: [] for kind in _kinds(cfg)}
        for j, kind in enumerate(cfg.period):
            p = _layer(cfg, params["layers"], i, j)
            x, k_v, _ = _layer_prefill(cfg, p, x, positions, kind, serve=True)
            kv[kind].append(k_v)
        # a kind's layers of this period, stacked where there are several
        return x, {kind: l[0] if len(l) == 1 else jax.tree.map(lambda *a: jnp.stack(a), *l)
                   for kind, l in kv.items()}

    x, kv = jax.lax.scan(body, x, _periods(cfg))
    with jax.named_scope("lm_head"):
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = unembed(
            x[:, -1:, :], params["unembed"] if "unembed" in params else params["embed"].T
        )
    k_c, v_c = dict(cache["k"]), dict(cache["v"])
    for kind, (ks, vs) in kv.items():
        if cfg.period.count(kind) > 1:  # (periods, per period, ...) -> (L_kind, ...)
            ks, vs = (a.reshape(-1, *a.shape[2:]) for a in (ks, vs))
        k_c[kind] = _fill(k_c[kind], ks, S, cfg.window_of(kind))
        v_c[kind] = _fill(v_c[kind], vs, S, cfg.window_of(kind))
    cache = {"k": k_c, "v": v_c, "lengths": jnp.full((B,), S, jnp.int32)}
    return logits, cache


def decode_step(cfg: ArchConfig, params, cache, tokens: jax.Array):
    """One greedy decode step. tokens: (B, 1) int32 — the current token.
    Returns (logits (B,1,V), new cache)."""
    B = tokens.shape[0]
    x = jnp.take(params["embed"], tokens, axis=0)
    x = shard(x, "batch", "seq", None)
    lengths = cache["lengths"]

    # The stacked caches ride in the carry and each layer writes its row in
    # place: as the scan's xs/ys they would be sliced and re-stacked whole.
    # A MoE's experts stay stacked too: its loop over the held picks reads
    # each from the stack, where a layer's slice would be copied into it.
    P = len(cfg.period)

    def body(carry, i):
        x, ks, vs = carry
        ks, vs = dict(ks), dict(vs)
        for j, kind in enumerate(cfg.period):
            p, mlp_layer = _layer(cfg, params["layers"], i, j), None
            if cfg.moe is not None:
                p, mlp_layer = {**p, "mlp": params["layers"]["mlp"]}, i * P + j
            x, ks[kind], vs[kind] = _layer_decode(
                cfg, p, x, ks[kind], vs[kind], lengths, kind, _stack_index(cfg, i, j),
                mlp_layer)
        return (x, ks, vs), None

    (x, ks, vs), _ = jax.lax.scan(body, (x, cache["k"], cache["v"]), _periods(cfg))
    with jax.named_scope("lm_head"):
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = unembed(x, params["unembed"] if "unembed" in params else params["embed"].T)
    new_cache = {"k": ks, "v": vs, "lengths": lengths + 1}
    return logits, new_cache
