"""Pallas TPU flash-attention (prefill) kernel.

Block-streaming online-softmax attention: the full causal layers' prefill
attention on the TPU (``models/attention.py``), so no prefill writes its
scores out. q, k and v enter in their own dtype; the scores, the running
max and sum and the accumulator are f32, and p meets v on the MXU in v's
dtype with f32 accumulation.

Grid: (batch * kv_heads, q_seq / block_q, kv_seq / block_k), KV innermost so
the running max / sum / accumulator scratch carries across KV steps. GQA:
a KV head's ``group`` query heads share one q tile of group * block_q rows,
so each K/V tile is read once per KV head, not once per query head.

Blocks: :func:`flash_blocks` picks them from the shapes, as large as the
sequence allows up to 512, with the group's f32 score and accumulator
tiles held to 2.5 MiB a step (``_TILE_ELEMS``), well under the scoped VMEM
default with p and the double-buffered q/k/v/o tiles beside them. A
caller pads a sequence to a multiple of :func:`seq_multiple`.

Causal skip: the K/V index map is clamped at the diagonal, so a step above
it names the block already resident and nothing is copied for it, and
``pl.when`` skips its compute. Blocks wholly below the diagonal run without
a mask; only those the diagonal crosses build one. Query i sees keys
0..i (starts aligned).
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
_MAX_BLOCK = 512
# f32 elements of one step's score and accumulator tiles,
# group * block_q * (block_k + head_dim): 2.5 MiB
_TILE_ELEMS = 5 * 2**17


def seq_multiple(seq: int) -> int:
    """The power of two from 128 to 512 that a sequence of ``seq`` tokens is
    padded to a multiple of: the next one at or above ``seq``, or 512. Every
    block :func:`flash_blocks` picks divides it, and the padded sequence
    gets the same blocks as ``seq``."""
    return min(_MAX_BLOCK, max(_LANES, pl.next_power_of_2(seq)))


def flash_blocks(seq: int, group: int, head_dim: int) -> tuple[int, int]:
    """(block_q, block_k) for ``seq`` tokens, ``group`` query heads a KV
    head and ``head_dim``: :func:`seq_multiple` of ``seq``, halved (block_q
    first) until the f32 tiles of a step fit ``_TILE_ELEMS``."""
    block_q = block_k = seq_multiple(seq)

    def fits():
        return group * block_q * (block_k + head_dim) <= _TILE_ELEMS

    while not fits() and block_q > _LANES:
        block_q //= 2
    while not fits() and block_k > _LANES:
        block_k //= 2
    return block_q, block_k


def _lanes(x: jax.Array, n: int) -> jax.Array:
    """(rows, 128) whose lanes are equal -> (rows, n)."""
    return jnp.tile(x, (1, pl.cdiv(n, _LANES)))[:, :n]


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
    *, causal: bool, sm_scale: float, block_q: int, block_k: int, n_kv: int,
):
    iq = pl.program_id(1)
    ik = pl.program_id(2)
    _, group, _, d = q_ref.shape
    rows = group * block_q

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def _step(masked: bool):
        q = q_ref[0].reshape(rows, d)  # the group's heads, stacked
        s = jax.lax.dot_general(q, k_ref[0], (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * sm_scale
        if masked:
            shape = (block_q, block_k)
            q_pos = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
            k_pos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
            s = jnp.where((q_pos >= k_pos)[None], s.reshape(group, *shape),
                          _NEG_INF).reshape(rows, block_k)
        m_prev = m_ref[...]
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - _lanes(m_next, block_k))
        alpha = jnp.exp(m_prev - m_next)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = _lanes(alpha, d) * acc_ref[...] + jnp.dot(
            p.astype(v_ref.dtype), v_ref[0], preferred_element_type=jnp.float32
        )
        m_ref[...] = m_next

    if causal:
        runs = ik * block_k <= iq * block_q + block_q - 1
        crosses = (ik + 1) * block_k - 1 > iq * block_q
        pl.when(runs & ~crosses)(functools.partial(_step, False))
        pl.when(runs & crosses)(functools.partial(_step, True))
    else:
        _step(False)

    @pl.when(ik == n_kv - 1)
    def _flush():
        inv = 1.0 / jnp.maximum(l_ref[...], 1e-20)
        o_ref[0] = (acc_ref[...] * _lanes(inv, d)).reshape(group, block_q, d).astype(o_ref.dtype)


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
    block_q: int | None = None,
    block_k: int | None = None,
    interpret: bool,
) -> jax.Array:
    """Blocks default to :func:`flash_blocks` of the shapes; a sequence
    they do not divide raises."""
    batch, q_heads, q_seq, d = q.shape
    _, kv_heads, kv_seq, _ = k.shape
    if q_heads % kv_heads:
        raise ValueError(f"q_heads {q_heads} not a multiple of kv_heads {kv_heads}")
    group = q_heads // kv_heads
    auto_q, auto_k = flash_blocks(max(q_seq, kv_seq), group, d)
    block_q = min(block_q or auto_q, q_seq)
    block_k = min(block_k or auto_k, kv_seq)
    if q_seq % block_q or kv_seq % block_k:
        raise ValueError(f"seq ({q_seq},{kv_seq}) must divide blocks ({block_q},{block_k})")
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    n_kv = kv_seq // block_k

    # one grid row per (batch, kv head); its query heads ride in one tile
    qf = q.reshape(batch * kv_heads, group, q_seq, d)
    kf = k.reshape(batch * kv_heads, kv_seq, d)
    vf = v.reshape(batch * kv_heads, kv_seq, d)

    def q_map(h, iq, ik):
        return (h, 0, iq, 0)

    def kv_map(h, iq, ik):
        if causal:  # above the diagonal: the block already resident
            ik = jnp.minimum(ik, (iq * block_q + block_q - 1) // block_k)
        return (h, ik, 0)

    rows = group * block_q
    out = pl.pallas_call(
        functools.partial(
            _flash_kernel,
            causal=causal,
            sm_scale=float(sm_scale),
            block_q=block_q,
            block_k=block_k,
            n_kv=n_kv,
        ),
        grid=(batch * kv_heads, q_seq // block_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, group, block_q, d), q_map),
            pl.BlockSpec((1, block_k, d), kv_map),
            pl.BlockSpec((1, block_k, d), kv_map),
        ],
        out_specs=pl.BlockSpec((1, group, block_q, d), q_map),
        out_shape=jax.ShapeDtypeStruct(qf.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((rows, _LANES), jnp.float32),
            pltpu.VMEM((rows, _LANES), jnp.float32),
            pltpu.VMEM((rows, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="prefill_flash",
    )(qf, kf, vf)
    return out.reshape(batch, q_heads, q_seq, d)
