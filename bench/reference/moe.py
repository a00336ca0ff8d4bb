"""Plain float32 reference of a mixture-of-experts decoder with window and
full attention layers, written from the published equations (Hugging Face
``modeling_qwen3_moe``, its sliding-window mask, and
``_compute_yarn_parameters`` for YaRN), and importing nothing of the
program.

    x = embed[tokens]
    per layer:  h = RMSNorm(x) ; q, k, v = h Wq, h Wk, h Wv
                q, k = RMSNorm over head_dim ; RoPE of the layer's kind
                (window: default; full: YaRN's frequencies, cos and sin
                times attention_factor)
                x += softmax(q k^T / sqrt(hd) + mask) v Wo
                    mask: query i sees key j when i - window < j <= i
                    (window layers), j <= i (full layers)
                h = RMSNorm(x) ; p = softmax(h W_router) over all experts
                top-k of p, renormalised to sum to 1
                x += sum over the top-k picks e that this chip holds of
                     p_e * W_down_e(silu(h W_gate_e) * h W_up_e)
    logits = RMSNorm(x) W_unembed

The chip's share is the program's: experts E_first .. E_first + E_held - 1
of each layer, routed over all E; the absent experts' part is left out of
both. Layer by layer, one layer's weights upcast at a time, every product
at ``Precision.HIGHEST``, attention a block of queries at a time: so it
fits beside the served bf16 weights. ``quant="fp8"`` is the control, the
same arithmetic computed in float8 e4m3 with float32 accumulation: both
operands of every product rounded to e4m3, weights with one scale per
tensor (per expert for the experts) and activations with one scale per row
(absmax -> 448). The embedding, the head and the arithmetic of both are
``reference/dense.py``'s.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.dense import PAD, _embed, _fp8, _head, _mm, _rms

HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 256  # queries per block of attention scores


def _q(w: jax.Array, quant: str | None, axes=None) -> jax.Array:
    """A weight in float32, rounded to fp8 first for the control (one scale
    per slice over ``axes``, the whole tensor by default)."""
    w = w.astype(jnp.float32)
    if quant is None:
        return w
    if quant != "fp8":
        raise ValueError(f"unknown quant {quant!r}")
    return _fp8(w, axes)


def _inv_freq(hd: int, r: dict) -> np.ndarray:
    """Inverse frequencies of RoPE, or of YaRN where ``r["yarn"]``."""
    theta = r["theta"]
    plain = 1.0 / theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd)
    if not r["yarn"]:
        return plain.astype(np.float32)

    def dim(rot):  # dimension that turns `rot` times over the original positions
        return hd * math.log(r["orig"] / (rot * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(dim(r["beta_fast"])), 0)
    high = min(math.ceil(dim(r["beta_slow"])), hd - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(hd // 2) - low) / (high - low), 0.0, 1.0)
    extrapolation = 1.0 - ramp
    inv = plain / r["factor"] * (1.0 - extrapolation) + plain * extrapolation
    return inv.astype(np.float32)


def _rope(x, inv, scale):
    """x: (S, heads, hd); rotate-half RoPE at positions 0..S-1."""
    S, _, hd = x.shape
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None]
    ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]
    rot = jnp.concatenate([-x[..., hd // 2:], x[..., : hd // 2]], axis=-1)
    return x * (jnp.cos(ang) * scale) + rot * (jnp.sin(ang) * scale)


def _attend(q, k, v, window, quant):
    """q: (S, H, hd), k/v: (S, H, hd) -> (S, H, hd), Q_BLOCK queries at a time."""
    S, H, hd = q.shape
    nb = S // Q_BLOCK
    kpos = jnp.arange(S)

    def block(args):
        qb, b = args
        s = _mm("qhk,shk->hqs", qb, k, quant) / np.sqrt(hd)
        qpos = b * Q_BLOCK + jnp.arange(Q_BLOCK)
        sees = kpos[None, :] <= qpos[:, None]
        if window is not None:
            sees &= kpos[None, :] > qpos[:, None] - window
        p = jax.nn.softmax(jnp.where(sees[None], s, -jnp.inf), axis=-1)
        return _mm("hqs,shk->qhk", p, v, quant)

    out = jax.lax.map(block, (q.reshape(nb, Q_BLOCK, H, hd), jnp.arange(nb)))
    return out.reshape(S, H, hd)


@functools.partial(jax.jit, static_argnames=(
    "eps", "qk_norm", "rope", "window", "top_k", "held", "quant"))
def _layer(x, W, l, *, eps, qk_norm, rope, window, top_k, held, quant):
    """One layer; ``rope`` is its kind's RoPE as sorted (key, value) pairs,
    ``window`` None for a full layer, ``held`` (first, count) of experts."""
    g = lambda name: _q(W[name][l], quant)  # noqa: E731
    r = dict(rope)
    h = _rms(x, W["ln1"][l], eps)
    q = _mm("sd,dhk->shk", h, g("wq"), quant)
    k = _mm("sd,dhk->shk", h, g("wk"), quant)
    v = _mm("sd,dhk->shk", h, g("wv"), quant)
    if qk_norm:
        q = _rms(q, W["q_norm"][l], eps)
        k = _rms(k, W["k_norm"][l], eps)
    inv = jnp.asarray(_inv_freq(q.shape[-1], r))
    scale = r["attention_factor"] if r["yarn"] else 1.0
    q, k = _rope(q, inv, scale), _rope(k, inv, scale)
    H, K = q.shape[1], k.shape[1]
    k = jnp.repeat(k, H // K, axis=1)
    v = jnp.repeat(v, H // K, axis=1)
    if quant is not None:  # keys and values: one scale per position
        k, v = _fp8(k, (1, 2)), _fp8(v, (1, 2))
    o = _attend(q, k, v, window, quant)
    x = x + _mm("qhk,hkd->qd", o, g("wo"), quant)

    h = _rms(x, W["ln2"][l], eps)
    probs = jax.nn.softmax(_mm("sd,de->se", h, g("router"), quant), axis=-1)
    top, ids = jax.lax.top_k(probs, top_k)
    top = top / jnp.sum(top, axis=-1, keepdims=True)
    experts = jnp.arange(held[0], held[0] + held[1])
    # (S, E_held): each held expert's gate for each token, 0 where not picked
    gate = jnp.sum(jnp.where(ids[:, :, None] == experts[None, None], top[:, :, None], 0.0),
                   axis=1)
    we = lambda name: _q(W[name][l], quant, axes=(1, 2))  # noqa: E731  one scale per expert
    a = _mm("sd,edf->esf", h, we("w_gate"), quant)
    b = _mm("sd,edf->esf", h, we("w_up"), quant)
    y = _mm("esf,efd->esd", jax.nn.silu(a) * b, we("w_down"), quant)
    return x + jnp.einsum("se,esd->sd", gate, y, precision=HI)


def logits_at(W: dict, s: dict, tokens: np.ndarray, rows: np.ndarray, *,
              quant: str | None = None) -> np.ndarray:
    """Float32 logits (len(rows), V) of ``tokens`` at positions ``rows``.

    ``s`` is ``adapters/moe.sizes(cfg)``: plain numbers, no program object.
    """
    S = len(tokens)
    Sp = -(-S // PAD) * PAD
    tok = np.zeros(Sp, np.int32)
    tok[:S] = tokens
    x = _embed(W["embed"], jnp.asarray(tok), quant=quant)
    for l, kind in enumerate(s["kinds"]):
        x = _layer(x, W, jnp.int32(l), eps=s["eps"], qk_norm=s["qk_norm"],
                   rope=tuple(sorted(s["rope"][kind].items())),
                   window=s["window"] if kind == "window" else None, top_k=s["top_k"],
                   held=(s["E_first"], s["E_held"]), quant=quant)
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
