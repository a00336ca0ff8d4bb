"""Whisper-style encoder-decoder backbone (arXiv:2212.04356).

The modality frontend (mel-spectrogram + conv1d feature extractor) is the
permitted STUB: inputs are precomputed frame embeddings (B, frames, d_model)
supplied by the caller. Everything downstream — the bidirectional
encoder, the causal decoder with cross-attention, KV caches for decode — is
implemented. RoPE replaces Whisper's absolute embeddings (TPU-idiomatic;
noted in DESIGN.md §8).
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.distributed.sharding import shard
from .attention import decode_attention_step, init_attention, prefill_attention
from .layers import cross_entropy, init_swiglu, normal_init, rms_norm, swiglu, unembed


def _init_enc_layer(cfg: ArchConfig, key):
    k1, k2 = jax.random.split(key)
    return {
        "ln1": jnp.ones((cfg.d_model,), cfg.jax_dtype),
        "attn": init_attention(
            k1, cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, False,
            cfg.jax_dtype,
        ),
        "ln2": jnp.ones((cfg.d_model,), cfg.jax_dtype),
        "mlp": init_swiglu(k2, cfg.d_model, cfg.d_ff, cfg.jax_dtype),
    }


def _init_dec_layer(cfg: ArchConfig, key):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "ln1": jnp.ones((cfg.d_model,), cfg.jax_dtype),
        "self_attn": init_attention(
            k1, cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, False,
            cfg.jax_dtype,
        ),
        "ln_x": jnp.ones((cfg.d_model,), cfg.jax_dtype),
        "cross_attn": init_attention(
            k2, cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, False,
            cfg.jax_dtype,
        ),
        "ln2": jnp.ones((cfg.d_model,), cfg.jax_dtype),
        "mlp": init_swiglu(k3, cfg.d_model, cfg.d_ff, cfg.jax_dtype),
    }


def init_params(cfg: ArchConfig, key) -> dict[str, Any]:
    k_emb, k_enc, k_dec, k_out = jax.random.split(key, 4)
    enc_keys = jax.random.split(k_enc, cfg.n_encoder_layers)
    dec_keys = jax.random.split(k_dec, cfg.n_layers)
    return {
        "embed": normal_init(k_emb, (cfg.vocab, cfg.d_model), 1.0, cfg.jax_dtype),
        "encoder": jax.vmap(functools.partial(_init_enc_layer, cfg))(enc_keys),
        "enc_norm": jnp.ones((cfg.d_model,), cfg.jax_dtype),
        "decoder": jax.vmap(functools.partial(_init_dec_layer, cfg))(dec_keys),
        "final_norm": jnp.ones((cfg.d_model,), cfg.jax_dtype),
        "unembed": normal_init(
            k_out, (cfg.d_model, cfg.vocab), cfg.d_model**-0.5, cfg.jax_dtype
        ),
    }


def encode(cfg: ArchConfig, params, frames: jax.Array, *, remat: bool = True):
    """frames: (B, F, d_model) stub conv-frontend output."""
    B, F, _ = frames.shape
    x = shard(frames.astype(cfg.jax_dtype), "batch", "seq", None)
    positions = jnp.broadcast_to(jnp.arange(F, dtype=jnp.int32)[None], (B, F))

    def body(x, p):
        h, _ = prefill_attention(
            p["attn"], rms_norm(x, p["ln1"], cfg.norm_eps), positions,
            rope_theta=cfg.rope_theta, eps=cfg.norm_eps, causal=False,
        )
        x = x + h
        m = swiglu(rms_norm(x, p["ln2"], cfg.norm_eps), **p["mlp"])
        return x + m, None

    if remat:
        body = jax.checkpoint(body)
    x, _ = jax.lax.scan(body, x, params["encoder"])
    return rms_norm(x, params["enc_norm"], cfg.norm_eps)


def _cross_kv(cfg, p_attn, enc_out):
    k = jnp.einsum("bsd,dhk->bshk", enc_out, p_attn.wk)
    v = jnp.einsum("bsd,dhk->bshk", enc_out, p_attn.wv)
    return k, v


def decode_train(cfg: ArchConfig, params, tokens, enc_out, *, remat: bool = True):
    B, S = tokens.shape
    x = jnp.take(params["embed"], tokens, axis=0)
    x = shard(x, "batch", "seq", None)
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))

    def body(x, p):
        h, _ = prefill_attention(
            p["self_attn"], rms_norm(x, p["ln1"], cfg.norm_eps), positions,
            rope_theta=cfg.rope_theta, eps=cfg.norm_eps, causal=True,
        )
        x = x + h
        kv = _cross_kv(cfg, p["cross_attn"], enc_out)
        h, _ = prefill_attention(
            p["cross_attn"], rms_norm(x, p["ln_x"], cfg.norm_eps), positions,
            rope_theta=cfg.rope_theta, eps=cfg.norm_eps, causal=False,
            cross_kv=kv, use_rope=False,
        )
        x = x + h
        m = swiglu(rms_norm(x, p["ln2"], cfg.norm_eps), **p["mlp"])
        return x + m, None

    if remat:
        body = jax.checkpoint(body)
    x, _ = jax.lax.scan(body, x, params["decoder"])
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return unembed(x, params["unembed"])


def forward(cfg: ArchConfig, params, batch, *, remat: bool = True):
    enc_out = encode(cfg, params, batch["frames"], remat=remat)
    return decode_train(cfg, params, batch["tokens"], enc_out, remat=remat), 0.0


def loss_fn(cfg: ArchConfig, params, batch, *, remat: bool = True):
    logits, aux = forward(cfg, params, batch, remat=remat)
    ce, nll = cross_entropy(logits, batch["labels"])
    return ce + aux, {"ce": ce, "nll": nll, "aux": aux}


def init_cache(cfg: ArchConfig, batch: int, max_len: int, **_):
    shape = (cfg.n_layers, batch, cfg.n_kv_heads, max_len, cfg.head_dim)
    cross = (cfg.n_layers, batch, cfg.n_kv_heads, cfg.encoder_frames, cfg.head_dim)
    return {
        "k": jnp.zeros(shape, cfg.jax_dtype),
        "v": jnp.zeros(shape, cfg.jax_dtype),
        "cross_k": jnp.zeros(cross, cfg.jax_dtype),
        "cross_v": jnp.zeros(cross, cfg.jax_dtype),
        "lengths": jnp.zeros((batch,), jnp.int32),
    }


def prefill(cfg: ArchConfig, params, batch, cache):
    """Encode frames + store cross-KV; decoder starts empty (lengths=0).

    batch: {"frames": (B,F,d)}.
    """
    enc_out = encode(cfg, params, batch["frames"], remat=False)

    def kv_body(_, p):
        k, v = _cross_kv(cfg, p["cross_attn"], enc_out)
        return None, (k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3))

    _, (ck, cv) = jax.lax.scan(kv_body, None, params["decoder"])
    cache = dict(cache)
    cache["cross_k"], cache["cross_v"] = ck, cv
    return None, cache


def decode_step(cfg: ArchConfig, params, cache, tokens):
    B = tokens.shape[0]
    x = jnp.take(params["embed"], tokens, axis=0)
    x = shard(x, "batch", "seq", None)
    lengths = cache["lengths"]
    frames = cache["cross_k"].shape[3]
    all_frames = jnp.full((B,), frames, jnp.int32)

    def body(x, layer):
        p, kc, vc, ck, cv = layer
        h, kc, vc = decode_attention_step(
            p["self_attn"], rms_norm(x, p["ln1"], cfg.norm_eps), kc, vc, lengths,
            rope_theta=cfg.rope_theta, eps=cfg.norm_eps,
        )
        x = x + h
        h, _, _ = decode_attention_step(
            p["cross_attn"], rms_norm(x, p["ln_x"], cfg.norm_eps), ck, cv,
            all_frames, rope_theta=cfg.rope_theta, eps=cfg.norm_eps,
            use_rope=False, update_cache=False,
        )
        x = x + h
        m = swiglu(rms_norm(x, p["ln2"], cfg.norm_eps), **p["mlp"])
        return x + m, (kc, vc)

    x, (ks, vs) = jax.lax.scan(
        body, x, (params["decoder"], cache["k"], cache["v"],
                  cache["cross_k"], cache["cross_v"])
    )
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = unembed(x, params["unembed"])
    new_cache = dict(cache)
    new_cache["k"], new_cache["v"] = ks, vs
    new_cache["lengths"] = lengths + 1
    return logits, new_cache
