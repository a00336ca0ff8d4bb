"""Shared neural building blocks (pure JAX, no framework deps)."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.distributed.sharding import shard


def normal_init(key, shape, scale: float, dtype=jnp.float32):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def rms_norm(x: jax.Array, weight: jax.Array, eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * weight.astype(jnp.float32)).astype(dt)


def layer_norm(x: jax.Array, weight: jax.Array, bias: jax.Array, eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    x = (x - mu) * jax.lax.rsqrt(var + eps)
    return (x * weight.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dt)


def yarn_frequencies(hd: int, r) -> np.ndarray:
    """YaRN's inverse frequencies (hd // 2,) for a ``RopeConfig`` ``r``, as
    Hugging Face's ``_compute_yarn_parameters`` gives them: dimensions that
    turn fewer than beta_slow times over the original context are
    interpolated by yarn_factor, those that turn more than beta_fast times
    are kept, and a linear ramp blends the ones between."""
    def dim(rotations):  # the dimension that turns `rotations` times
        return hd * math.log(r.original_max_positions / (2 * math.pi * rotations)) / (
            2 * math.log(r.theta))

    low = max(math.floor(dim(r.beta_fast)), 0)
    high = min(math.ceil(dim(r.beta_slow)), hd - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(hd // 2) - low) / (high - low), 0.0, 1.0)
    plain = 1.0 / r.theta ** (np.arange(0, hd, 2) / hd)
    return (plain / r.yarn_factor * ramp + plain * (1.0 - ramp)).astype(np.float32)


def rope(x: jax.Array, positions: jax.Array, theta: float, yarn=None) -> jax.Array:
    """Rotary embedding. x: (..., seq, heads, head_dim); positions: (..., seq).
    With ``yarn`` (a ``RopeConfig``), YaRN's frequencies at its theta, and
    cos and sin scaled by its attention_factor."""
    hd = x.shape[-1]
    half = hd // 2
    if yarn is None:
        freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    else:
        freqs = jnp.asarray(yarn_frequencies(hd, yarn))
    angles = positions.astype(jnp.float32)[..., None] * freqs  # (..., seq, half)
    cos = jnp.cos(angles)[..., None, :]  # broadcast over heads
    sin = jnp.sin(angles)[..., None, :]
    if yarn is not None:
        cos, sin = cos * yarn.attention_factor, sin * yarn.attention_factor
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )
    return out.astype(x.dtype)


def swiglu(x: jax.Array, w_gate: jax.Array, w_up: jax.Array, w_down: jax.Array) -> jax.Array:
    """SwiGLU MLP: down( silu(x@gate) * (x@up) ). Hidden dim sharded on "ff"."""
    h = jax.nn.silu(x @ w_gate) * (x @ w_up)
    h = shard(h, "batch", None, "ff")
    return h @ w_down


def init_swiglu(key, d_model: int, d_ff: int, dtype):
    k1, k2, k3 = jax.random.split(key, 3)
    s_in = d_model**-0.5
    s_out = d_ff**-0.5
    return {
        "w_gate": normal_init(k1, (d_model, d_ff), s_in, dtype),
        "w_up": normal_init(k2, (d_model, d_ff), s_in, dtype),
        "w_down": normal_init(k3, (d_ff, d_model), s_out, dtype),
    }


def unembed(x: jax.Array, w: jax.Array) -> jax.Array:
    """Logits projection; vocab dim sharded."""
    logits = x @ w
    return shard(logits, "batch", None, "vocab")


def cross_entropy(logits: jax.Array, labels: jax.Array, z_weight: float = 1e-4):
    """Token-mean cross entropy with z-loss; logits (B, S, V), labels (B, S)."""
    logits = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = lse - gold
    z = z_weight * (lse**2)
    return jnp.mean(nll + z), jnp.mean(nll)
