"""Pallas TPU decode attention: one new query token per sequence against its
layer's KV cache, read where it lies in the stack of every layer's.

Decode attention does O(1) FLOPs a byte, so its time is the bytes it reads.
The kernel streams only the valid prefix of each sequence's cache, once:

- Operands: q (batch, q_heads, 1, d), viewed as (batch, kv_heads, group, d);
  the whole stacked caches (layers, batch, kv_heads, S, d); and, prefetched
  as scalars, ``layer`` and the valid prefix ``lengths`` (batch,). The K/V
  index maps pick ``layer`` from the stack, so no layer is sliced out or
  copied before the call.
- Grid: (batch, cdiv(S, block_k)). A block holds every KV head of a
  sequence, and a K/V block is read once for all ``group`` query heads of
  its head. Where ``block_k`` does not divide S the last block reaches past
  the cache; its rows there are masked in V as well as in the scores.
- Blocks past the valid prefix copy nothing: the K/V index map is clamped
  at the last valid block, so a later step names the block already
  resident, and ``pl.when`` skips its compute. The last valid block is
  masked by position.
- Precision as the jnp reference's (``ref.decode_attention_ref``): the
  scores are q·kᵀ with f32 accumulation, the online softmax is f32, and
  P·V takes p at f32 (:func:`_pv`).

:func:`decode_block` picks ``block_k`` from the shapes.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30
_LANES = 128
# bytes of one K (or V) block in VMEM: every KV head of a sequence, block_k
# slots, head_dim padded to the lanes. Double-buffered K and V blocks and the
# step's f32 temporaries take ~12 MiB at most, under _VMEM_LIMIT.
_BLOCK_BYTES = 2 * 2**20
_VMEM_LIMIT = 48 * 2**20


def decode_block(seq: int, kv_heads: int, head_dim: int, itemsize: int) -> int:
    """``block_k`` for a cache of ``seq`` slots: the largest power of two
    from 128 that is no longer than ``seq`` and whose block of ``kv_heads``
    heads fits ``_BLOCK_BYTES``; a cache shorter than 128 is one block.
    qwen3-0.6b's 8 heads of 128 in bf16 take 1,024 slots a block, phi3-mini's
    32 heads 256, mellum2's 4 heads 2,048, so a cache of 8,192 slots (2,048
    for phi3) is at most 8 blocks. Any ``seq`` is tiled: one it does not
    divide ends in a partial block."""
    lanes = pl.cdiv(head_dim, _LANES) * _LANES
    fits = min(seq, _BLOCK_BYTES // (kv_heads * lanes * itemsize))
    return min(seq, max(_LANES, pl.next_power_of_2(fits + 1) // 2))


def _pv(p: jax.Array, v: jax.Array) -> jax.Array:
    """(kv_heads, group, block_k) f32 p times (kv_heads, block_k, d) v, at
    f32. A bf16 v meets three bf16 parts of p whose sum is p exactly: each
    product is exact, so this is the f32 product up to the order of its
    sums, in three MXU passes where an f32 (HIGHEST) product takes six. Any
    other v is upcast to f32."""
    if v.dtype != jnp.bfloat16:
        return jnp.einsum("kgs,ksd->kgd", p, v.astype(jnp.float32),
                          preferred_element_type=jnp.float32,
                          precision=jax.lax.Precision.HIGHEST)
    out, rest = 0.0, p
    for _ in range(3):
        part = rest.astype(jnp.bfloat16)
        out = out + jnp.einsum("kgs,ksd->kgd", part, v, preferred_element_type=jnp.float32)
        rest = rest - part.astype(jnp.float32)
    return out


def _decode_kernel(
    layer_ref, valid_ref,  # scalar prefetch: (1,), (batch,) int32
    q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
    *, sm_scale: float, block_k: int, seq: int,
):
    b = pl.program_id(0)
    ik = pl.program_id(1)
    valid = valid_ref[b]

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def step(v):
        # (kv_heads, group, d) · (kv_heads, block_k, d) -> (kv_heads, group, block_k)
        s = jnp.einsum("kgd,ksd->kgs", q_ref[...], k_ref[...],
                       preferred_element_type=jnp.float32) * sm_scale
        pos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        s = jnp.where(pos < valid, s, _NEG_INF)
        m_prev = m_ref[...]
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
        p = jnp.exp(s - m_next)
        alpha = jnp.exp(m_prev - m_next)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=2, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + _pv(p, v)
        m_ref[...] = m_next

    reads = ik * block_k < valid
    if seq % block_k == 0:
        pl.when(reads)(lambda: step(v_ref[...]))
    else:
        # the last block reaches past the cache, and its rows there hold
        # anything: p is 0 on them, but 0 × NaN is NaN, so V is masked too
        edge = ik == pl.num_programs(1) - 1
        pl.when(reads & ~edge)(lambda: step(v_ref[...]))

        @pl.when(reads & edge)
        def _edge():
            v = v_ref[...]
            row = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, v.shape, 1)
            step(jnp.where(row < seq, v, jnp.zeros_like(v)))

    @pl.when(ik == pl.num_programs(1) - 1)
    def _flush():
        o_ref[...] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-20)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("sm_scale", "block_k", "interpret"))
def decode_attention(
    q: jax.Array,        # (batch, q_heads, 1, d)
    k_cache: jax.Array,  # (layers, batch, kv_heads, S, d)
    v_cache: jax.Array,  # (layers, batch, kv_heads, S, d)
    lengths: jax.Array,  # (batch,) int32 valid prefix per sequence
    layer: jax.Array,    # int32 scalar: the stack's layer attended over
    *,
    sm_scale: float | None = None,
    block_k: int | None = None,
    interpret: bool,
) -> jax.Array:
    """Attention of each sequence's one query over the first ``lengths[b]``
    slots of layer ``layer``'s cache. ``block_k`` defaults to
    :func:`decode_block` of the shapes."""
    batch, q_heads, one, d = q.shape
    if one != 1:
        raise ValueError("decode kernel expects exactly one query token")
    _, _, kv_heads, s_len, _ = k_cache.shape
    if q_heads % kv_heads:
        raise ValueError(f"q_heads {q_heads} not a multiple of kv_heads {kv_heads}")
    group = q_heads // kv_heads
    block_k = block_k or decode_block(s_len, kv_heads, d, k_cache.dtype.itemsize)
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)

    def q_map(b, ik, layer_ref, valid_ref):
        return (b, 0, 0, 0)

    def kv_map(b, ik, layer_ref, valid_ref):
        # past the valid prefix: the block already resident, so nothing is copied
        last = jnp.maximum(valid_ref[b] - 1, 0) // block_k
        return (layer_ref[0], b, 0, jnp.minimum(ik, last), 0)

    if not interpret:
        # the stacks stay in HBM: left free, XLA may move a stack that fits
        # into VMEM around every call (mellum2's do), copying all of it
        k_cache, v_cache = (pltpu.with_memory_space_constraint(c, pltpu.HBM)
                            for c in (k_cache, v_cache))
    squeezed = pl.Squeezed()
    kv_spec = pl.BlockSpec((squeezed, squeezed, kv_heads, block_k, d), kv_map)
    q_spec = pl.BlockSpec((squeezed, kv_heads, group, d), q_map)
    out = pl.pallas_call(
        functools.partial(_decode_kernel, sm_scale=float(sm_scale), block_k=block_k,
                          seq=s_len),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(batch, pl.cdiv(s_len, block_k)),
            in_specs=[q_spec, kv_spec, kv_spec],
            out_specs=q_spec,
            scratch_shapes=[
                pltpu.VMEM((kv_heads, group, 1), jnp.float32),
                pltpu.VMEM((kv_heads, group, 1), jnp.float32),
                pltpu.VMEM((kv_heads, group, d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((batch, kv_heads, group, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=interpret,
        name="decode_attention",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), lengths.astype(jnp.int32),
      q.reshape(batch, kv_heads, group, d), k_cache, v_cache)
    return out.reshape(batch, q_heads, 1, d)
