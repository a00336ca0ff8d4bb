"""Public wrappers around the Pallas kernels.

Handles padding to block multiples, dtype management, and the interpret-mode
decision: Pallas compiles these kernels for the TPU only, so a kernel whose
operands live on a TPU runs compiled (Mosaic) and anywhere else its body
runs in the Pallas interpreter. :func:`runs_interpreted` makes that choice
per call from the operands. Importing this module touches no device and
loads no Pallas: each wrapper imports its kernel when first called.
``use_pallas=False`` calls the pure-jnp oracle instead (used inside pjit'd
model code where a CPU-interpreted pallas_call cannot be SPMD-partitioned).
Shapes the attention kernels cannot tile raise; they are never handed to the
oracle behind the caller's back.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import ref as _ref


def runs_interpreted(x: jax.Array) -> bool:
    """True unless ``x`` is on a TPU. A traced operand has no placement yet;
    it is judged by the backend the trace will compile for."""
    if isinstance(x, jax.core.Tracer):
        return jax.default_backend() != "tpu"
    return any(d.platform != "tpu" for d in x.devices())


def _pad_to(x: jax.Array, axis: int, multiple: int) -> tuple[jax.Array, int]:
    size = x.shape[axis]
    pad = (-size) % multiple
    if pad == 0:
        return x, 0
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), pad


def matmul(
    a: jax.Array,
    b: jax.Array,
    *,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 512,
    use_pallas: bool = True,
) -> jax.Array:
    """Tiled matmul; pads M/N/K up to block multiples then slices back."""
    if not use_pallas:
        return _ref.matmul_ref(a, b)
    from .matmul_probe import matmul as _matmul

    m, k = a.shape
    _, n = b.shape
    bm, bn, bk = (min(block_m, m), min(block_n, n), min(block_k, k))
    # pallas wants divisibility; round blocks down to powers that fit, pad rest
    a, _ = _pad_to(a, 0, bm)
    a, _ = _pad_to(a, 1, bk)
    b, _ = _pad_to(b, 0, bk)
    b, _ = _pad_to(b, 1, bn)
    out = _matmul(a, b, block_m=bm, block_n=bn, block_k=bk,
                  interpret=runs_interpreted(a))
    return out[:m, :n]


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    sm_scale: float | None = None,
    block_q: int | None = None,
    block_k: int | None = None,
    use_pallas: bool = True,
) -> jax.Array:
    """Blocks default to ``flash_attention.flash_blocks`` of the shapes."""
    if not use_pallas:
        return _ref.attention_ref(q, k, v, causal=causal, sm_scale=sm_scale)
    from .flash_attention import flash_attention as _flash_attention

    # ragged lengths raise in the kernel: padding needs length masking
    return _flash_attention(
        q, k, v, causal=causal, sm_scale=sm_scale, block_q=block_q,
        block_k=block_k, interpret=runs_interpreted(q),
    )


def decode_attention(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    lengths: jax.Array,
    *,
    layer: jax.Array | None = None,
    sm_scale: float | None = None,
    use_pallas: bool = True,
) -> jax.Array:
    """q: (B, H, 1, hd); k/v: (B, K, S, hd), or with ``layer`` the stacked
    (L, B, K, S, hd) whose layer ``layer`` is attended over. The kernel
    reads the layer where it lies, in blocks from
    ``decode_attention.decode_block`` of the shapes; the oracle slices it."""
    if not use_pallas:
        if layer is not None:
            k_cache, v_cache = (jax.lax.dynamic_index_in_dim(c, layer, 0, keepdims=False)
                                for c in (k_cache, v_cache))
        return _ref.decode_attention_ref(q, k_cache, v_cache, lengths, sm_scale=sm_scale)
    from .decode_attention import decode_attention as _decode_attention

    if layer is None:  # one layer's cache: a stack of one
        k_cache, v_cache, layer = k_cache[None], v_cache[None], jnp.int32(0)
    return _decode_attention(
        q, k_cache, v_cache, lengths, layer, sm_scale=sm_scale,
        interpret=runs_interpreted(q),
    )


@functools.cache
def kernel_names() -> tuple[str, ...]:
    return ("matmul", "flash_attention", "decode_attention")
