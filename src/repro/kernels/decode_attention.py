"""Pallas TPU single-token decode attention kernel.

Decode (the ``decode_32k`` / ``long_500k`` shapes) computes attention of ONE
new query token against a long KV cache. Arithmetic intensity is O(1)
FLOP/byte — this kernel is memory-bound by design; its job is to stream the
cache through VMEM exactly once with block-level masking for the valid
prefix ``lengths``.

Variable cache occupancy is supported through scalar prefetch
(PrefetchScalarGridSpec): ``lengths[b]`` masks keys at positions >= length.
Fully-masked KV blocks are skipped with ``pl.when`` so short sequences in a
long cache don't pay for the whole stride.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


def _decode_kernel(
    lengths_ref,  # scalar-prefetch (batch,) int32
    q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
    *, sm_scale: float, block_k: int, n_kv: int, q_heads: int,
):
    h = pl.program_id(0)
    ik = pl.program_id(1)
    b = h // q_heads
    length = lengths_ref[b]

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(ik * block_k < length)
    def _step():
        q = q_ref[0]  # (1, d) — the single new token
        k = k_ref[0]  # (block_k, d)
        v = v_ref[0]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * sm_scale  # (1, block_k)
        k_pos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(k_pos < length, s, _NEG_INF)
        m_prev = m_ref[...]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_cur[:, None])
        alpha = jnp.exp(m_prev - m_cur)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32
        )
        m_ref[...] = m_cur

    @pl.when(ik == n_kv - 1)
    def _flush():
        l = jnp.maximum(l_ref[...], 1e-20)
        o_ref[0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("sm_scale", "block_k", "interpret")
)
def decode_attention(
    q: jax.Array,        # (batch, q_heads, 1, d)
    k_cache: jax.Array,  # (batch, kv_heads, S, d)
    v_cache: jax.Array,  # (batch, kv_heads, S, d)
    lengths: jax.Array,  # (batch,) int32 valid prefix per sequence
    *,
    sm_scale: float | None = None,
    block_k: int = 256,
    interpret: bool,
) -> jax.Array:
    batch, q_heads, one, d = q.shape
    if one != 1:
        raise ValueError("decode kernel expects exactly one query token")
    _, kv_heads, s_len, _ = k_cache.shape
    group = q_heads // kv_heads
    block_k = min(block_k, s_len)
    if s_len % block_k:
        raise ValueError(f"cache length {s_len} must divide block_k {block_k}")
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    n_kv = s_len // block_k

    qf = q.reshape(batch * q_heads, 1, d)
    kf = k_cache.reshape(batch * kv_heads, s_len, d)
    vf = v_cache.reshape(batch * kv_heads, s_len, d)

    def q_map(h, ik, lengths):
        return (h, 0, 0)

    def kv_map(h, ik, lengths):
        b = h // q_heads
        qh = h % q_heads
        return (b * kv_heads + qh // group, ik, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(batch * q_heads, n_kv),
        in_specs=[
            pl.BlockSpec((1, 1, d), q_map),
            pl.BlockSpec((1, block_k, d), kv_map),
            pl.BlockSpec((1, block_k, d), kv_map),
        ],
        out_specs=pl.BlockSpec((1, 1, d), q_map),
        scratch_shapes=[
            pltpu.VMEM((1,), jnp.float32),
            pltpu.VMEM((1,), jnp.float32),
            pltpu.VMEM((1, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _decode_kernel,
            sm_scale=float(sm_scale),
            block_k=block_k,
            n_kv=n_kv,
            q_heads=q_heads,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((batch * q_heads, 1, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(lengths.astype(jnp.int32), qf, kf, vf)
    return out.reshape(batch, q_heads, 1, d)
