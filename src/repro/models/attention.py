"""GQA attention: prefill (full / sliding-window / causal) and single-token
decode against a KV cache.

The Pallas kernels serve where they compile for a TPU and no mesh of
several devices would have to partition them (``_serves_kernel``).
Everywhere else the jnp reference serves: on the CPU, where a kernel would
run interpreted, and in a sharded prefill or decode, which a pallas_call
cannot be partitioned into.

The transformer's serving prefill (``flash=True``) runs a full causal
layer (no window, no cross attention) through the flash kernel
(``kernels/flash_attention.py``). The training forward keeps the
reference: it is differentiated, and the kernel has no reverse-mode rule.
Window layers take the banded path once the prompt outgrows the window,
cross attention and non-causal calls the reference.

Decode writes the new row in place (one ``dynamic_update_slice`` for one
sequence, one clamped scatter for a batch, a per-layer ``vmap`` where the
cache is not a stack). A step over a stacked cache (``layer`` given)
attends by the decode kernel (``kernels/decode_attention.py``), which reads
the layer where it lies in the stack and only its valid prefix; a per-layer
cache, and every step where the kernel does not serve, by the jnp
reference (``ops.decode_attention(..., use_pallas=False)``).
"""
from __future__ import annotations

import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.distributed.sharding import current_mesh, shard
from repro.kernels import ops
from repro.kernels import ref as kref
from .layers import normal_init, rms_norm, rope


def _write_cache_row(cache: jax.Array, new: jax.Array, slot: jax.Array,
                     layer: Optional[jax.Array] = None) -> jax.Array:
    """cache: (B, K, S, hd), or with ``layer`` the stacked (L, B, K, S, hd)
    whose layer ``layer`` is written; new: (B, K, 1, hd); slot: (B,) int32."""
    if layer is not None:
        rows = new[:, :, 0].astype(cache.dtype)  # (B, K, hd), written in place
        if cache.shape[1] == 1:
            # one sequence: a dynamic_update_slice, which on TPU leaves the
            # cache in the layout the attention reads without staging it
            return jax.lax.dynamic_update_slice(
                cache, rows[None, :, :, None], (layer, 0, 0, slot[0], 0))
        # a batch: one scatter, which partitions where the batch is sharded;
        # out-of-range slots clamp, as dynamic_update_slice's do
        return cache.at[layer, jnp.arange(cache.shape[1]), :, slot, :].set(
            rows, mode="clip", indices_are_sorted=True, unique_indices=True)

    def one(c, n, s):  # (K, S, hd), (K, 1, hd), scalar
        return jax.lax.dynamic_update_slice(c, n.astype(c.dtype), (0, s, 0))

    return jax.vmap(one)(cache, new, slot)


class AttnParams(NamedTuple):
    wq: jax.Array  # (d, H, hd)
    wk: jax.Array  # (d, K, hd)
    wv: jax.Array  # (d, K, hd)
    wo: jax.Array  # (H, hd, d)
    q_norm: Optional[jax.Array]  # (hd,) or None
    k_norm: Optional[jax.Array]


def init_attention(key, d_model: int, n_heads: int, n_kv_heads: int, head_dim: int,
                   qk_norm: bool, dtype) -> AttnParams:
    kq, kk, kv, ko = jax.random.split(key, 4)
    s = d_model**-0.5
    so = (n_heads * head_dim) ** -0.5
    return AttnParams(
        wq=normal_init(kq, (d_model, n_heads, head_dim), s, dtype),
        wk=normal_init(kk, (d_model, n_kv_heads, head_dim), s, dtype),
        wv=normal_init(kv, (d_model, n_kv_heads, head_dim), s, dtype),
        wo=normal_init(ko, (n_heads, head_dim, d_model), so, dtype),
        q_norm=jnp.ones((head_dim,), dtype) if qk_norm else None,
        k_norm=jnp.ones((head_dim,), dtype) if qk_norm else None,
    )


def _project_qkv(p: AttnParams, x: jax.Array, positions: jax.Array,
                 rope_theta: float, eps: float, use_rope: bool = True, yarn=None):
    """x: (B, S, d) -> q (B, S, H, hd), k/v (B, S, K, hd)."""
    q = jnp.einsum("bsd,dhk->bshk", x, p.wq)
    k = jnp.einsum("bsd,dhk->bshk", x, p.wk)
    v = jnp.einsum("bsd,dhk->bshk", x, p.wv)
    if p.q_norm is not None:
        q = rms_norm(q, p.q_norm, eps)
        k = rms_norm(k, p.k_norm, eps)
    if use_rope:
        q = rope(q, positions, rope_theta, yarn)
        k = rope(k, positions, rope_theta, yarn)
    q = shard(q, "batch", None, "heads", None)
    k = shard(k, "batch", None, "kv_heads", None)
    v = shard(v, "batch", None, "kv_heads", None)
    return q, k, v


def prefill_attention(
    p: AttnParams,
    x: jax.Array,                  # (B, S, d)
    positions: jax.Array,          # (B, S)
    *,
    rope_theta: float,
    eps: float,
    causal: bool = True,
    window: Optional[int] = None,
    use_rope: bool = True,
    cross_kv: Optional[tuple[jax.Array, jax.Array]] = None,  # (B, S_kv, K, hd)
    yarn=None,
    flash: bool = False,
) -> tuple[jax.Array, tuple[jax.Array, jax.Array]]:
    """Returns (out (B,S,d), (k_cache, v_cache) in (B,K,S,hd) layout).
    ``yarn`` (a ``RopeConfig``) switches RoPE to YaRN (``layers.rope``).
    ``flash``: a serving caller's full causal layer may take the flash
    kernel (``_serves_kernel``); a forward that is differentiated keeps the
    reference."""
    if cross_kv is None:
        q, k, v = _project_qkv(p, x, positions, rope_theta, eps, use_rope, yarn)
    else:
        q = jnp.einsum("bsd,dhk->bshk", x, p.wq)
        if p.q_norm is not None:
            q = rms_norm(q, p.q_norm, eps)
        if use_rope:
            q = rope(q, positions, rope_theta)
        k, v = cross_kv
    # (B, heads, S, hd) layout for the kernels
    qh = q.transpose(0, 2, 1, 3)
    kh = k.transpose(0, 2, 1, 3)
    vh = v.transpose(0, 2, 1, 3)
    bq = _band_block(qh.shape[2], window) if causal and cross_kv is None else None
    if flash and causal and window is None and cross_kv is None and _serves_kernel(qh):
        out = _flash_prefill(qh, kh, vh)
    elif bq:
        out = _banded_attention(qh, kh, vh, window, bq)
    else:
        out = kref.attention_ref(qh, kh, vh, causal=causal, window=window)
    out = out.transpose(0, 2, 1, 3)  # (B, S, H, hd)
    y = jnp.einsum("bshk,hkd->bsd", out, p.wo)
    y = shard(y, "batch", "seq", None)
    return y, (kh, vh)


def _serves_kernel(q: jax.Array) -> bool:
    """A Pallas kernel serves where it compiles for a TPU and no mesh of
    several devices would have to partition it."""
    mesh = current_mesh()
    return not ops.runs_interpreted(q) and (mesh is None or mesh.size == 1)


def _flash_prefill(q: jax.Array, k: jax.Array, v: jax.Array) -> jax.Array:
    """Causal self-attention by the flash kernel, q: (B, H, S, hd), k/v:
    (B, K, S, hd). A prompt the blocks do not divide is padded at the tail
    and the output sliced back: under the causal mask no real query sees a
    padded key, so the padding changes nothing. The kernel picks its blocks
    from the padded shapes."""
    from repro.kernels.flash_attention import seq_multiple

    S = q.shape[2]
    pad = -S % seq_multiple(S)
    if pad:
        q, k, v = (jnp.pad(a, ((0, 0), (0, 0), (0, pad), (0, 0))) for a in (q, k, v))
    return ops.flash_attention(q, k, v, causal=True)[:, :, :S]


def _band_block(S: int, window: Optional[int]) -> Optional[int]:
    """Queries a block of the banded window attention holds (a quarter of the
    window), or None where the band would not be narrower than the prompt."""
    bq = window // 4 if window else 0
    return bq if bq and S % bq == 0 and S > window + bq else None


def _banded_attention(q: jax.Array, k: jax.Array, v: jax.Array, window: int,
                      bq: int) -> jax.Array:
    """Causal attention of each query over the last ``window`` keys,
    ``kref.attention_ref``'s masked softmax computed by blocks of ``bq``
    queries: a block's scores span only the window + bq keys that reach it,
    not the whole prompt. q: (B, H, S, hd), k/v: (B, K, S, hd)."""
    B, H, S, d = q.shape
    K = k.shape[1]
    nb, span = S // bq, window + bq

    def bands(a):  # (B, K, S, d) -> (B, K, nb, span, d): block b's keys from b·bq − window
        a = jnp.pad(a.astype(jnp.float32), ((0, 0), (0, 0), (window, 0), (0, 0)))
        a = a.reshape(B, K, nb + window // bq, bq, d)
        return jnp.concatenate([a[:, :, i:i + nb] for i in range(span // bq)], axis=3)

    qg = q.astype(jnp.float32).reshape(B, K, H // K, nb, bq, d)
    s = jnp.einsum("bkgnqd,bknsd->bkgnqs", qg, bands(k),
                   preferred_element_type=jnp.float32) * (1.0 / math.sqrt(d))
    i = jnp.arange(bq)[:, None]                                  # query in its block
    j = jnp.arange(span)[None, :]                                # key in the block's span
    first = window - jnp.arange(nb)[:, None, None] * bq          # span index of key 0
    sees = (j > i) & (j <= i + window) & (j >= first)            # (nb, bq, span)
    s = jnp.where(sees[None, None, None], s, -1e30)
    out = jnp.einsum("bkgnqs,bknsd->bkgnqd", jax.nn.softmax(s, axis=-1), bands(v))
    return out.reshape(B, H, S, d).astype(q.dtype)


def decode_attention_step(
    p: AttnParams,
    x: jax.Array,                 # (B, 1, d) current token activations
    k_cache: jax.Array,           # (B, K, S, hd), or (L, B, K, S, hd) with layer
    v_cache: jax.Array,
    lengths: jax.Array,           # (B,) current valid length (position of new tok)
    *,
    rope_theta: float,
    eps: float,
    window: Optional[int] = None,
    use_rope: bool = True,
    update_cache: bool = True,
    layer: Optional[jax.Array] = None,
    yarn=None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One decode step. Returns (out (B,1,d), new_k_cache, new_v_cache).

    With ``window``, the cache has size S == window and new entries are
    written at position ``lengths % window`` (ring buffer); attention masks
    to the min(lengths, window) most recent entries. RoPE uses absolute
    positions so rotations stay consistent in the ring.

    With ``layer`` (an int32 scalar), the caches are the whole stack of
    layers: the new row is written into layer ``layer`` in place, that
    layer is attended over, and the stack is returned, so a layer scan can
    carry it instead of slicing and re-stacking it every step. Where the
    kernels serve (``_serves_kernel``) the decode kernel attends over the
    layer where it lies in the stack, reading only its valid prefix.
    """
    B, _, d = x.shape
    S = k_cache.shape[-2]
    positions = lengths[:, None]  # (B, 1) absolute position of the new token
    q, k_new, v_new = _project_qkv(p, x, positions, rope_theta, eps, use_rope, yarn)
    qh = q.transpose(0, 2, 1, 3)              # (B, H, 1, hd)
    k_new = k_new.transpose(0, 2, 1, 3)       # (B, K, 1, hd)
    v_new = v_new.transpose(0, 2, 1, 3)
    if update_cache:
        slot = lengths % S if window is not None else lengths
        with jax.named_scope("kv_write"):
            k_cache = _write_cache_row(k_cache, k_new, slot, layer)
            v_cache = _write_cache_row(v_cache, v_new, slot, layer)
        valid = jnp.minimum(lengths + 1, S)
    else:
        valid = jnp.minimum(lengths, S)
    out = ops.decode_attention(qh, k_cache, v_cache, valid, layer=layer,
                               use_pallas=layer is not None and _serves_kernel(qh))
    out = out.transpose(0, 2, 1, 3)
    y = jnp.einsum("bshk,hkd->bsd", out, p.wo)
    return shard(y, "batch", None, None), k_cache, v_cache
