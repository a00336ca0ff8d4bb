"""Dense (and MoE — the FFN is pluggable) decoder-only transformer with
scanned layer stacks, KV-cache prefill/decode, and sliding-window support.

Used directly by: llama3.2-1b, phi3-mini, qwen3, mistral-large-123b,
chameleon-34b (early-fusion VLM: image tokens are ordinary vocab ids), and
with MoE FFNs by deepseek-moe-16b / granite-moe-1b.
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
    """Returns (y, aux_loss)."""
    if cfg.moe is not None:
        return moe_lib.apply_moe(cfg, p_mlp, x)
    return swiglu(x, p_mlp["w_gate"], p_mlp["w_up"], p_mlp["w_down"]), 0.0


# The named scopes "attn", "mlp" and "lm_head" (and "kv_write" inside
# attention) label each compiled op's op_name; bench/scopes.py splits an
# executable's device time by them.
def _layer_prefill(cfg: ArchConfig, p, x, positions, window):
    with jax.named_scope("attn"):
        h, (k, v) = prefill_attention(
            p["attn"], rms_norm(x, p["ln1"], cfg.norm_eps), positions,
            rope_theta=cfg.rope_theta, eps=cfg.norm_eps, causal=True, window=window,
        )
        x = x + h
    with jax.named_scope("mlp"):
        m, aux = _mlp_apply(cfg, p["mlp"], rms_norm(x, p["ln2"], cfg.norm_eps))
        return x + m, (k, v), aux


def _layer_decode(cfg: ArchConfig, p, x, k_cache, v_cache, lengths, window, layer):
    """k_cache/v_cache: the stacked (L, B, K, S, hd) caches; ``layer``'s row
    is written in place and the stacks are returned."""
    with jax.named_scope("attn"):
        h, k_cache, v_cache = decode_attention_step(
            p["attn"], rms_norm(x, p["ln1"], cfg.norm_eps), k_cache, v_cache, lengths,
            rope_theta=cfg.rope_theta, eps=cfg.norm_eps, window=window, layer=layer,
        )
        x = x + h
    with jax.named_scope("mlp"):
        m, _ = _mlp_apply(cfg, p["mlp"], rms_norm(x, p["ln2"], cfg.norm_eps))
        return x + m, k_cache, v_cache


# ---------------------------------------------------------------------------
# Public model functions
# ---------------------------------------------------------------------------


def forward(
    cfg: ArchConfig,
    params,
    tokens: jax.Array,  # (B, S) int32
    *,
    remat: bool = True,
    window: Optional[int] = None,
) -> tuple[jax.Array, jax.Array]:
    """Training/prefill forward pass. Returns (logits (B,S,V), aux_loss)."""
    B, S = tokens.shape
    window = window if window is not None else cfg.sliding_window
    x = jnp.take(params["embed"], tokens, axis=0)
    x = shard(x, "batch", "seq", None)
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))

    def body(x, p):
        y, _, aux = _layer_prefill(cfg, p, x, positions, window)
        return y, aux

    if remat:
        body = jax.checkpoint(body)
    x, auxs = jax.lax.scan(body, x, params["layers"])
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = unembed(x, params["unembed"] if "unembed" in params else params["embed"].T)
    return logits, jnp.sum(auxs)


def loss_fn(cfg: ArchConfig, params, batch, *, remat: bool = True):
    logits, aux = forward(cfg, params, batch["tokens"], remat=remat)
    ce, nll = cross_entropy(logits, batch["labels"])
    return ce + aux, {"ce": ce, "nll": nll, "aux": aux}


def init_cache(cfg: ArchConfig, batch: int, max_len: int, *, window: Optional[int] = None):
    """KV cache pytree. With a window, the cache is a ring of size window."""
    window = window if window is not None else cfg.sliding_window
    S = min(max_len, window) if window is not None else max_len
    shape = (cfg.n_layers, batch, cfg.n_kv_heads, S, cfg.head_dim)
    return {
        "k": jnp.zeros(shape, cfg.jax_dtype),
        "v": jnp.zeros(shape, cfg.jax_dtype),
        "lengths": jnp.zeros((batch,), jnp.int32),
    }


def prefill(cfg: ArchConfig, params, tokens: jax.Array, cache):
    """Run the prompt through the stack, filling the cache. Returns
    (last-token logits, cache)."""
    B, S = tokens.shape
    window = cfg.sliding_window
    x = jnp.take(params["embed"], tokens, axis=0)
    x = shard(x, "batch", "seq", None)
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))

    def body(x, p):
        y, (k, v), _ = _layer_prefill(cfg, p, x, positions, window)
        return y, (k, v)

    x, (ks, vs) = jax.lax.scan(body, x, params["layers"])
    with jax.named_scope("lm_head"):
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = unembed(
            x[:, -1:, :], params["unembed"] if "unembed" in params else params["embed"].T
        )
    S_c = cache["k"].shape[3]
    if window is not None and S > S_c:
        # keep the last `window` positions; ring alignment: slot = pos % window
        ks, vs = ks[:, :, :, -S_c:], vs[:, :, :, -S_c:]
        shift = (S - S_c) % S_c
        ks = jnp.roll(ks, shift=shift, axis=3)
        vs = jnp.roll(vs, shift=shift, axis=3)
    cache = {
        "k": cache["k"].at[:, :, :, : ks.shape[3]].set(ks) if ks.shape[3] < S_c else ks,
        "v": cache["v"].at[:, :, :, : vs.shape[3]].set(vs) if vs.shape[3] < S_c else vs,
        "lengths": jnp.full((B,), S, jnp.int32),
    }
    return logits, cache


def decode_step(cfg: ArchConfig, params, cache, tokens: jax.Array):
    """One greedy decode step. tokens: (B, 1) int32 — the current token.
    Returns (logits (B,1,V), new cache)."""
    B = tokens.shape[0]
    window = cfg.sliding_window
    x = jnp.take(params["embed"], tokens, axis=0)
    x = shard(x, "batch", "seq", None)
    lengths = cache["lengths"]

    # The stacked caches ride in the carry and each layer writes its row in
    # place: as the scan's xs/ys they would be sliced and re-stacked whole.
    def body(carry, layer):
        x, ks, vs = carry
        p, i = layer
        y, ks, vs = _layer_decode(cfg, p, x, ks, vs, lengths, window, i)
        return (y, ks, vs), None

    layer_ids = jnp.arange(cfg.n_layers, dtype=jnp.int32)
    (x, ks, vs), _ = jax.lax.scan(
        body, (x, cache["k"], cache["v"]), (params["layers"], layer_ids))
    with jax.named_scope("lm_head"):
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = unembed(x, params["unembed"] if "unembed" in params else params["embed"].T)
    new_cache = {"k": ks, "v": vs, "lengths": lengths + 1}
    return logits, new_cache
