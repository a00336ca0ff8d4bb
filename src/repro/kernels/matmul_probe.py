"""Pallas TPU matmul kernel — the Minos benchmark probe (paper §II-C, [10]).

The paper's CPU probe is a Go matrix multiplication; the TPU-native
adaptation is an MXU-tiled matmul with explicit VMEM BlockSpecs. Block
shapes default to (128, 128, 512): the MXU wants multiples of 128 in the
contracted and lane dimensions, and 3 blocks of 128x512 f32 ≈ 0.8 MB keeps
the working set comfortably inside the ~16 MB/core VMEM with room for
double-buffering.

Validated in interpret mode on CPU against ``ref.matmul_ref``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _matmul_kernel(a_ref, b_ref, o_ref, acc_ref, *, n_k: int):
    """Grid = (M/bm, N/bn, K/bk); K is the innermost (sequential) axis so the
    f32 accumulator scratch carries across K steps."""
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        a_ref[...], b_ref[...], preferred_element_type=jnp.float32
    )

    @pl.when(pl.program_id(2) == n_k - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("block_m", "block_n", "block_k", "interpret")
)
def matmul(
    a: jax.Array,
    b: jax.Array,
    *,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 512,
    interpret: bool,
) -> jax.Array:
    """C = A @ B with explicit MXU tiling. Shapes must divide the blocks
    (the ops wrapper pads otherwise)."""
    m, k = a.shape
    k2, n = b.shape
    if k != k2:
        raise ValueError(f"contraction mismatch {a.shape} @ {b.shape}")
    block_m = min(block_m, m)
    block_n = min(block_n, n)
    block_k = min(block_k, k)
    if m % block_m or n % block_n or k % block_k:
        raise ValueError(
            f"shapes ({m},{k})x({k},{n}) must divide blocks ({block_m},{block_n},{block_k})"
        )
    n_k = k // block_k
    grid = (m // block_m, n // block_n, n_k)
    return pl.pallas_call(
        functools.partial(_matmul_kernel, n_k=n_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((block_k, block_n), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), a.dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(a, b)
