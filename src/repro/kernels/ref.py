"""Pure-jnp oracles for every Pallas kernel in this package.

These are the ground truth the kernels are validated against
(tests/test_kernels.py sweeps shapes and dtypes with assert_allclose), and
the path the models take wherever a kernel is not wired in or would run
interpreted.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def matmul_ref(a: jax.Array, b: jax.Array) -> jax.Array:
    return jnp.dot(a, b, preferred_element_type=a.dtype)


def _repeat_kv(k: jax.Array, group: int) -> jax.Array:
    """(batch, kv_heads, s, d) -> (batch, kv_heads*group, s, d)."""
    if group == 1:
        return k
    b, h, s, d = k.shape
    return jnp.broadcast_to(k[:, :, None], (b, h, group, s, d)).reshape(b, h * group, s, d)


def attention_ref(
    q: jax.Array,  # (batch, q_heads, q_seq, d)
    k: jax.Array,  # (batch, kv_heads, kv_seq, d)
    v: jax.Array,
    *,
    causal: bool = True,
    sm_scale: float | None = None,
    lengths: jax.Array | None = None,  # (batch,) valid kv prefix
    window: int | None = None,  # sliding-window size (None = full)
) -> jax.Array:
    """GQA attention oracle. Grouped einsum — the KV repeat is NEVER
    materialized (§Perf pick-3 iter-3: broadcasting the cache to q_heads in
    f32 cost 2x512 MiB all-gathers per layer per decode step)."""
    batch, q_heads, q_seq, d = q.shape
    _, kv_heads, kv_seq, _ = k.shape
    group = q_heads // kv_heads
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    qg = q.reshape(batch, kv_heads, group, q_seq, d)
    s = jnp.einsum(
        "bkgqd,bksd->bkgqs", qg.astype(jnp.float32), k.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    ) * sm_scale
    q_pos = jnp.arange(q_seq)[:, None] + (kv_seq - q_seq)  # align ends (decode)
    k_pos = jnp.arange(kv_seq)[None, :]
    mask = jnp.ones((q_seq, kv_seq), bool)
    if causal:
        mask &= q_pos >= k_pos
    if window is not None:
        mask &= q_pos - k_pos < window
    s = jnp.where(mask[None, None, None], s, -1e30)
    if lengths is not None:
        valid = k_pos < lengths[:, None, None]       # (batch, q_seq=1?, kv_seq)
        s = jnp.where(valid[:, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgqs,bksd->bkgqd", p, v.astype(jnp.float32))
    return out.reshape(batch, q_heads, q_seq, d).astype(q.dtype)


def decode_attention_ref(
    q: jax.Array,        # (batch, q_heads, 1, d)
    k_cache: jax.Array,  # (batch, kv_heads, S, d)
    v_cache: jax.Array,
    lengths: jax.Array,  # (batch,)
    *,
    sm_scale: float | None = None,
) -> jax.Array:
    return attention_ref(
        q, k_cache, v_cache, causal=False, sm_scale=sm_scale, lengths=lengths
    )
