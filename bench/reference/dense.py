"""Plain float32 reference of a decoder-only transformer, written from the
published Qwen3 / Phi-3 equations (Hugging Face ``modeling_qwen3`` /
``modeling_phi3``), and importing nothing of the program.

    x = embed[tokens]
    per layer:  h = RMSNorm(x) ; q, k, v = h Wq, h Wk, h Wv
                (Qwen3: q, k = RMSNorm over head_dim) ; RoPE (rotate-half)
                x += softmax(q k^T / sqrt(hd) + causal) v Wo
                x += W_down(silu(RMSNorm(x) W_gate) * RMSNorm(x) W_up)
    logits = RMSNorm(x) W_unembed   (tied: embed^T)

Layer by layer, one layer's weights upcast at a time, every product at
``Precision.HIGHEST``: so it fits beside the served bf16 weights of a model
that fills half the chip. ``quant="fp8"`` is the control, the same
arithmetic computed in float8 e4m3 with float32 accumulation: both operands
of every product rounded to e4m3, weights with one scale per tensor and
activations with one scale per row (absmax -> 448).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
PAD = 256  # sequences are padded to a multiple of this (causal: no effect)


def _fp8(x: jax.Array, axes) -> jax.Array:
    """Round to float8 e4m3 with one scale per slice over ``axes``."""
    scale = jnp.max(jnp.abs(x), axis=axes, keepdims=True) / 448.0
    scale = jnp.where(scale > 0, scale, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _q(w: jax.Array, quant: str | None) -> jax.Array:
    """A weight in float32, rounded to fp8 first for the control."""
    w = w.astype(jnp.float32)
    if quant is None:
        return w
    if quant != "fp8":
        raise ValueError(f"unknown quant {quant!r}")
    return _fp8(w, None)


def _mm(spec: str, a: jax.Array, b: jax.Array, quant: str | None) -> jax.Array:
    """einsum at HIGHEST; the control also rounds ``a`` per row (its
    contracted axes) to fp8."""
    if quant is not None:
        lhs = spec.split(",")[0]
        rhs = spec.split(",")[1].split("->")[0]
        axes = tuple(i for i, c in enumerate(lhs) if c in rhs)
        a = _fp8(a, axes)
    return jnp.einsum(spec, a, b, precision=HI)


def _rms(x, w, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * w.astype(jnp.float32)


def _rope(x, theta):
    """x: (S, heads, hd); rotate-half RoPE at positions 0..S-1."""
    S, _, hd = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None]
    ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]
    rot = jnp.concatenate([-x[..., hd // 2:], x[..., : hd // 2]], axis=-1)
    return x * jnp.cos(ang) + rot * jnp.sin(ang)


@functools.partial(jax.jit, static_argnames=("eps", "theta", "qk_norm", "quant"))
def _layer(x, W, l, *, eps, theta, qk_norm, quant):
    g = lambda name: _q(W[name][l], quant)  # noqa: E731
    S = x.shape[0]
    h = _rms(x, W["ln1"][l], eps)
    q = _mm("sd,dhk->shk", h, g("wq"), quant)
    k = _mm("sd,dhk->shk", h, g("wk"), quant)
    v = _mm("sd,dhk->shk", h, g("wv"), quant)
    if qk_norm:
        q = _rms(q, W["q_norm"][l], eps)
        k = _rms(k, W["k_norm"][l], eps)
    q, k = _rope(q, theta), _rope(k, theta)
    H, K, hd = q.shape[1], k.shape[1], q.shape[2]
    k = jnp.repeat(k, H // K, axis=1)
    v = jnp.repeat(v, H // K, axis=1)
    if quant is not None:  # keys and values: one scale per position
        k, v = _fp8(k, (1, 2)), _fp8(v, (1, 2))
    s = _mm("qhk,shk->hqs", q, k, quant) / np.sqrt(hd)
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
    o = _mm("hqs,shk->qhk", p, v, quant)
    x = x + _mm("qhk,hkd->qd", o, g("wo"), quant)
    h = _rms(x, W["ln2"][l], eps)
    gate = _mm("sd,df->sf", h, g("w_gate"), quant)
    up = _mm("sd,df->sf", h, g("w_up"), quant)
    return x + _mm("sf,fd->sd", jax.nn.silu(gate) * up, g("w_down"), quant)


@functools.partial(jax.jit, static_argnames=("quant",))
def _embed(table, tokens, *, quant):
    if quant is None:
        return jnp.take(table, tokens, axis=0).astype(jnp.float32)
    scale = jnp.max(jnp.abs(table.astype(jnp.float32))) / 448.0
    rows = jnp.take(table, tokens, axis=0).astype(jnp.float32) / scale
    return rows.astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


@functools.partial(jax.jit, static_argnames=("eps", "tied", "quant"))
def _head(x, W, rows, *, eps, tied, quant):
    h = _rms(x[rows], W["final_norm"], eps)
    w = _q(W["embed"], quant).T if tied else _q(W["unembed"], quant)
    return _mm("sd,dv->sv", h, w, quant)


def logits_at(W: dict, s: dict, tokens: np.ndarray, rows: np.ndarray, *,
              quant: str | None = None) -> np.ndarray:
    """Float32 logits (len(rows), V) of ``tokens`` at positions ``rows``.

    ``s`` is ``adapters/dense.sizes(cfg)``: plain numbers, no program object.
    """
    S = len(tokens)
    Sp = -(-S // PAD) * PAD
    tok = np.zeros(Sp, np.int32)
    tok[:S] = tokens
    x = _embed(W["embed"], jnp.asarray(tok), quant=quant)
    for l in range(s["L"]):
        x = _layer(x, W, jnp.int32(l), eps=s["eps"], theta=s["theta"],
                   qk_norm=s["qk_norm"], quant=quant)
    out = _head(x, W, jnp.asarray(rows, jnp.int32), eps=s["eps"], tied=s["tied"],
                quant=quant)
    return np.asarray(out, np.float32)


def gaps(W: dict, s: dict, prompt: np.ndarray, served: np.ndarray, *,
         control: bool = False) -> np.ndarray:
    """Per served token: how far its reference logit lies below the
    reference's best, in units of that position's reference logit std.

    The reference runs once over the prompt followed by the served tokens;
    row ``len(prompt) - 1 + i`` predicts served token ``i``. With
    ``control``, the token judged at each position is the one that the fp8
    control puts first there, not the served one.
    """
    S, T = len(prompt), len(served)
    seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
    rows = np.arange(S - 1, S - 1 + T)
    ref = logits_at(W, s, seq, rows)
    pick = served
    if control:
        pick = logits_at(W, s, seq, rows, quant="fp8").argmax(axis=-1)
    best = ref.max(axis=-1)
    got = ref[np.arange(T), pick]
    return (best - got) / ref.std(axis=-1)
