"""Pallas TPU flash-attention (prefill) kernel.

Block-streaming online-softmax attention for the prefill path — the
compute hot-spot of the serving workload Minos gates. Causal masking and
GQA (q_heads >= kv_heads) are handled inside the kernel; the KV block index
map folds the head-group division so KV tiles are fetched once per group.

Grid: (batch * q_heads, q_seq / block_q, kv_seq / block_k), KV innermost so
the running max / sum / accumulator scratch carries across KV steps.
VMEM working set per step ≈ block_q*d + 2*block_k*d + block_q*block_k
floats — (128, 128, d=128) f32 ≈ 0.25 MB, far under VMEM.

Causal skip: for q-block i, KV blocks strictly after the diagonal are
skipped via ``pl.when`` (no FLOPs, no scratch update), the standard TPU
flash-attention trick.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
    *, causal: bool, sm_scale: float, block_q: int, block_k: int, n_kv: int,
):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def _step():
        q = q_ref[0]  # (block_q, d)
        k = k_ref[0]  # (block_k, d)
        v = v_ref[0]  # (block_k, d)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * sm_scale
        if causal:
            q_pos = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            k_pos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
        m_prev = m_ref[...]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_cur[:, None])
        alpha = jnp.exp(m_prev - m_cur)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32
        )
        m_ref[...] = m_cur

    if causal:
        # skip fully-masked KV blocks above the diagonal
        pl.when(ik * block_k <= iq * block_q + block_q - 1)(_step)
    else:
        _step()

    @pl.when(ik == n_kv - 1)
    def _flush():
        l = jnp.maximum(l_ref[...], 1e-20)
        o_ref[0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "sm_scale", "block_q", "block_k", "interpret"),
)
def flash_attention(
    q: jax.Array,  # (batch, q_heads, q_seq, d)
    k: jax.Array,  # (batch, kv_heads, kv_seq, d)
    v: jax.Array,  # (batch, kv_heads, kv_seq, d)
    *,
    causal: bool = True,
    sm_scale: float | None = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool,
) -> jax.Array:
    batch, q_heads, q_seq, d = q.shape
    _, kv_heads, kv_seq, _ = k.shape
    if q_heads % kv_heads:
        raise ValueError(f"q_heads {q_heads} not a multiple of kv_heads {kv_heads}")
    group = q_heads // kv_heads
    block_q = min(block_q, q_seq)
    block_k = min(block_k, kv_seq)
    if q_seq % block_q or kv_seq % block_k:
        raise ValueError(f"seq ({q_seq},{kv_seq}) must divide blocks ({block_q},{block_k})")
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    n_kv = kv_seq // block_k

    # fold (batch, heads) into one grid axis
    qf = q.reshape(batch * q_heads, q_seq, d)
    kf = k.reshape(batch * kv_heads, kv_seq, d)
    vf = v.reshape(batch * kv_heads, kv_seq, d)

    def q_map(h, iq, ik):
        return (h, iq, 0)

    def kv_map(h, iq, ik):
        # GQA: query head h uses kv head (h % q_heads) // group within batch
        b = h // q_heads
        qh = h % q_heads
        return (b * kv_heads + qh // group, ik, 0)

    out = pl.pallas_call(
        functools.partial(
            _flash_kernel,
            causal=causal,
            sm_scale=float(sm_scale),
            block_q=block_q,
            block_k=block_k,
            n_kv=n_kv,
        ),
        grid=(batch * q_heads, q_seq // block_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, block_q, d), q_map),
            pl.BlockSpec((1, block_k, d), kv_map),
            pl.BlockSpec((1, block_k, d), kv_map),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), q_map),
        out_shape=jax.ShapeDtypeStruct((batch * q_heads, q_seq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(batch, q_heads, q_seq, d)
