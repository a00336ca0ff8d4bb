"""Recurrent sequence mixers, TPU-adapted: Mamba2 SSD (arXiv:2405.21060 as
used by Zamba2) and xLSTM's mLSTM/sLSTM cells (arXiv:2405.04517).

Hardware adaptation (see DESIGN.md §2): the reference CUDA kernels for these
papers are warp-level scans; the TPU-native formulation is the *chunked*
(block-parallel) scan — quadratic attention-like matmuls inside an
MXU-aligned chunk, a `lax.scan` carrying the recurrent state across chunks.
This turns the recurrence into dense (L×L)·(L×P) matmuls the MXU executes at
full throughput, with state materialized once per chunk instead of per step.

All cells expose:
  init_*        — parameter init
  *_chunked     — full-sequence (training/prefill) form
  *_step        — single-token decode form (the long_500k path)
and are validated against a naive per-step recurrence in tests/test_ssm.py.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.distributed.sharding import shard
from .layers import normal_init, rms_norm

# ---------------------------------------------------------------------------
# Mamba2 SSD: H_t = a_t · H_{t-1} + B_t ⊗ (Δ_t x_t);  y_t = C_t·H_t + D·x_t
#   a_t = exp(Δ_t · A) with A < 0 scalar per head (scalar-identity SSD).
# ---------------------------------------------------------------------------


def ssd_chunked(
    x: jax.Array,       # (B, S, H, P)  inputs (already Δ-scaled NOT applied)
    dt: jax.Array,      # (B, S, H)     Δ_t (positive)
    A: jax.Array,       # (H,)          negative decay rates
    Bm: jax.Array,      # (B, S, N)     input maps (shared across heads, 1 group)
    Cm: jax.Array,      # (B, S, N)
    D: jax.Array,       # (H,)          skip connection
    *,
    chunk: int,
    h0: jax.Array | None = None,  # (B, H, N, P) initial state
):
    """Chunked SSD scan. Returns (y (B,S,H,P), h_final (B,H,N,P))."""
    Bsz, S, H, P = x.shape
    N = Bm.shape[-1]
    L = min(chunk, S)
    pad = (-S) % L
    if pad:
        # padding is a no-op: dt=0 -> decay exp(0)=1 (state kept), input 0
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0)))
    S_orig, S = S, S + pad
    nC = S // L

    loga = dt * A[None, None, :]                       # (B, S, H) log decay, <=0
    xdt = x * dt[..., None]                            # Δ_t x_t

    # reshape into chunks
    def ch(t, trailing):  # (B, S, ...) -> (B, nC, L, ...)
        return t.reshape((Bsz, nC, L) + trailing)

    loga_c = ch(loga, (H,))
    xdt_c = ch(xdt, (H, P))
    B_c = ch(Bm, (N,))
    C_c = ch(Cm, (N,))
    csum = jnp.cumsum(loga_c, axis=2)                  # (B, nC, L, H) inclusive

    if h0 is None:
        h0 = jnp.zeros((Bsz, H, N, P), jnp.float32)

    def body(h_prev, inp):
        csum_i, x_i, B_i, C_i = inp                    # per-chunk slices
        # decay from position j (exclusive) to i: exp(csum_i - csum_j), j<=i
        # intra-chunk scores: S_ij = (C_i · B_j) * exp(csum_i - csum_j)
        gap = csum_i[:, :, None, :] - csum_i[:, None, :, :]   # (B, L, L, H)
        mask = jnp.tril(jnp.ones((L, L), bool))
        # mask BEFORE exp: masked (j>i) entries have gap>0; exp(large) is
        # inf and inf*0 in the backward pass poisons every gradient
        gap = jnp.where(mask[None, :, :, None], gap, -1e30)
        dec = jnp.exp(gap)
        cb = jnp.einsum("bin,bjn->bij", C_i.astype(jnp.float32), B_i.astype(jnp.float32))
        scores = cb[..., None] * dec                    # (B, L, L, H)
        y_intra = jnp.einsum("bijh,bjhp->bihp", scores, x_i.astype(jnp.float32))
        # inter-chunk: y_i += C_i · (exp(csum_i) * H_prev)
        y_inter = jnp.einsum(
            "bin,bhnp->bihp", C_i.astype(jnp.float32), h_prev
        ) * jnp.exp(csum_i)[..., None]
        # state update: H_new = exp(csum_L) H_prev + sum_j exp(csum_L - csum_j) B_j x_j
        tail = jnp.exp(csum_i[:, -1:, :] - csum_i)      # (B, L, H)
        h_new = h_prev * jnp.exp(csum_i[:, -1])[..., None, None]  # (B,H,1,1) bcast
        h_new = h_new + jnp.einsum(
            "bjn,bjh,bjhp->bhnp", B_i.astype(jnp.float32), tail, x_i.astype(jnp.float32)
        )
        return h_new, y_intra + y_inter

    inputs = (
        csum.transpose(1, 0, 2, 3),
        xdt_c.transpose(1, 0, 2, 3, 4),
        B_c.transpose(1, 0, 2, 3),
        C_c.transpose(1, 0, 2, 3),
    )
    h_final, y = jax.lax.scan(body, h0, inputs)
    y = y.transpose(1, 0, 2, 3, 4).reshape(Bsz, S, H, P)
    y = y + x.astype(jnp.float32) * D[None, None, :, None]
    return y[:, :S_orig].astype(x.dtype), h_final


def ssd_step(
    x: jax.Array,   # (B, H, P) one token (Δ not applied)
    dt: jax.Array,  # (B, H)
    A: jax.Array,   # (H,)
    Bm: jax.Array,  # (B, N)
    Cm: jax.Array,  # (B, N)
    D: jax.Array,   # (H,)
    h: jax.Array,   # (B, H, N, P) state
):
    """Single-token SSD recurrence (decode)."""
    a = jnp.exp(dt * A[None, :])                       # (B, H)
    xdt = (x * dt[..., None]).astype(jnp.float32)
    h = h * a[..., None, None] + jnp.einsum("bn,bhp->bhnp", Bm.astype(jnp.float32), xdt)
    y = jnp.einsum("bn,bhnp->bhp", Cm.astype(jnp.float32), h)
    y = y + x.astype(jnp.float32) * D[None, :, None]
    return y.astype(x.dtype), h


# ---------------------------------------------------------------------------
# mLSTM (xLSTM): matrix memory C_t (P_k x P_v per head), exp input gating
# with max-stabilizer m; chunked form carries (C, n, m).
# ---------------------------------------------------------------------------


def mlstm_chunked(
    q: jax.Array,   # (B, S, H, P)
    k: jax.Array,
    v: jax.Array,
    i_gate: jax.Array,  # (B, S, H) pre-activation (exp gate)
    f_gate: jax.Array,  # (B, S, H) pre-activation (sigmoid gate)
    *,
    chunk: int,
    state: tuple[jax.Array, jax.Array, jax.Array] | None = None,
):
    """Returns (h (B,S,H,P), (C, n, m) final state).

    State convention: stored C/n are scaled by exp(-m) (m is the running
    log-stabilizer), i.e. C_true = C_stored * exp(m).
    """
    Bsz, S, H, P = q.shape
    L = min(chunk, S)
    pad = (-S) % L
    if pad:
        # padding is a no-op: i_gate -> -inf (no input), f_gate -> +inf
        # (forget gate 1: state kept)
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        i_gate = jnp.pad(i_gate, ((0, 0), (0, pad), (0, 0)), constant_values=-1e30)
        f_gate = jnp.pad(f_gate, ((0, 0), (0, pad), (0, 0)), constant_values=60.0)
    S_orig, S = S, S + pad
    nC = S // L
    scale = P**-0.5

    logf = -jax.nn.softplus(-f_gate).astype(jnp.float32)   # log sigmoid(f)
    i_g = i_gate.astype(jnp.float32)

    def ch(t, trailing):
        return t.reshape((Bsz, nC, L) + trailing)

    q_c, k_c, v_c = ch(q, (H, P)), ch(k, (H, P)), ch(v, (H, P))
    logf_c, i_c = ch(logf, (H,)), ch(i_g, (H,))

    if state is None:
        C0 = jnp.zeros((Bsz, H, P, P), jnp.float32)
        n0 = jnp.zeros((Bsz, H, P), jnp.float32)
        m0 = jnp.full((Bsz, H), -1e30, jnp.float32)
    else:
        C0, n0, m0 = state

    def body(carry, inp):
        C_prev, n_prev, m_prev = carry
        q_i, k_i, v_i, logf_i, ig_i = inp
        b = jnp.cumsum(logf_i, axis=1)                  # (B, L, H) inclusive
        # source log-gain within chunk: a_j = i_j - b_j
        a = ig_i - b
        # per-position stabilizer: m_i = max(b_i + cummax_j<=i(a_j), b_i + m_prev)
        acum = jax.lax.cummax(a, axis=1)
        m_pos = b + jnp.maximum(acum, m_prev[:, None, :])   # (B, L, H)
        # intra scores: D_ij = exp(b_i - b_j + i_j - m_i) for j <= i
        gap = b[:, :, None, :] - b[:, None, :, :] + ig_i[:, None, :, :]  # (B,L,L,H)
        gap = gap - m_pos[:, :, None, :]
        mask = jnp.tril(jnp.ones((L, L), bool))
        gap = jnp.where(mask[None, :, :, None], gap, -1e30)  # pre-exp mask
        dmat = jnp.exp(gap)
        qk = jnp.einsum("bihp,bjhp->bijh", q_i.astype(jnp.float32),
                        k_i.astype(jnp.float32)) * scale
        S_ij = qk * dmat
        num = jnp.einsum("bijh,bjhp->bihp", S_ij, v_i.astype(jnp.float32))
        den = jnp.sum(S_ij, axis=2)                     # (B, L, H)
        # inter-chunk: factor exp(b_i + m_prev - m_i)
        inter_f = jnp.exp(b + m_prev[:, None, :] - m_pos)   # (B, L, H)
        qC = jnp.einsum("bihp,bhpr->bihr", q_i.astype(jnp.float32), C_prev) * scale
        qn = jnp.einsum("bihp,bhp->bih", q_i.astype(jnp.float32), n_prev) * scale
        num = num + qC * inter_f[..., None]
        den = den + qn * inter_f
        h = num / jnp.maximum(jnp.abs(den), jnp.exp(-m_pos))[..., None]
        # ---- state update to chunk end ----
        b_L = b[:, -1, :]                               # (B, H)
        m_new = jnp.maximum(b_L + m_prev, b_L + acum[:, -1, :])
        src = jnp.exp(b_L[:, None, :] - b + ig_i - m_new[:, None, :])  # (B, L, H)
        C_new = C_prev * jnp.exp(b_L + m_prev - m_new)[..., None, None] + jnp.einsum(
            "bjh,bjhp,bjhr->bhpr", src, k_i.astype(jnp.float32), v_i.astype(jnp.float32)
        )
        n_new = n_prev * jnp.exp(b_L + m_prev - m_new)[..., None] + jnp.einsum(
            "bjh,bjhp->bhp", src, k_i.astype(jnp.float32)
        )
        return (C_new, n_new, m_new), h

    inputs = tuple(
        t.transpose(1, 0, 2, 3, 4) if t.ndim == 5 else t.transpose(1, 0, 2, 3)
        for t in (q_c, k_c, v_c, logf_c, i_c)
    )
    (C, n, m), h = jax.lax.scan(body, (C0, n0, m0), inputs)
    h = h.transpose(1, 0, 2, 3, 4).reshape(Bsz, S, H, P)
    return h[:, :S_orig].astype(q.dtype), (C, n, m)


def mlstm_step(
    q: jax.Array,  # (B, H, P)
    k: jax.Array,
    v: jax.Array,
    i_gate: jax.Array,  # (B, H)
    f_gate: jax.Array,  # (B, H)
    state: tuple[jax.Array, jax.Array, jax.Array],
):
    """One mLSTM recurrence step (decode)."""
    C, n, m = state
    P = q.shape[-1]
    scale = P**-0.5
    logf = -jax.nn.softplus(-f_gate).astype(jnp.float32)
    ig = i_gate.astype(jnp.float32)
    m_new = jnp.maximum(logf + m, ig)
    f_s = jnp.exp(logf + m - m_new)
    i_s = jnp.exp(ig - m_new)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    C = C * f_s[..., None, None] + i_s[..., None, None] * kf[..., :, None] * vf[..., None, :]
    n = n * f_s[..., None] + i_s[..., None] * kf
    qf = q.astype(jnp.float32) * scale
    num = jnp.einsum("bhp,bhpr->bhr", qf, C)
    den = jnp.einsum("bhp,bhp->bh", qf, n)
    h = num / jnp.maximum(jnp.abs(den), jnp.exp(-m_new))[..., None]
    return h.astype(q.dtype), (C, n, m_new)


# ---------------------------------------------------------------------------
# sLSTM: scalar memory with true recurrence (h_{t-1} feeds the gates) —
# inherently sequential; lax.scan over time. Block-diagonal recurrent
# matrices per head (the paper's design for parallelizable heads).
# ---------------------------------------------------------------------------


def slstm_scan(
    x_gates: jax.Array,  # (B, S, 4, H, P) pre-activations from input (z,i,f,o)
    R: jax.Array,        # (4, H, P, P) recurrent block-diagonal weights
    *,
    state: tuple | None = None,
):
    """Returns (h (B,S,H,P), final (c,n,h,m)). Gate order: z, i, f, o."""
    Bsz, S, _, H, P = x_gates.shape
    if state is None:
        z0 = jnp.zeros((Bsz, H, P), jnp.float32)
        state = (z0, z0, z0, jnp.full((Bsz, H, P), -1e30, jnp.float32))

    def body(carry, xg):
        c, n, h_prev, m = carry
        # gate pre-activations: input part + recurrent part
        rec = jnp.einsum("bhp,ghpr->gbhr", h_prev, R.astype(jnp.float32))
        zt = jnp.tanh(xg[:, 0].astype(jnp.float32) + rec[0])
        it = xg[:, 1].astype(jnp.float32) + rec[1]           # exp gate (log-space)
        ft = xg[:, 2].astype(jnp.float32) + rec[2]           # sigmoid gate
        ot = jax.nn.sigmoid(xg[:, 3].astype(jnp.float32) + rec[3])
        logf = -jax.nn.softplus(-ft)
        m_new = jnp.maximum(logf + m, it)
        i_s = jnp.exp(it - m_new)
        f_s = jnp.exp(logf + m - m_new)
        c = f_s * c + i_s * zt
        n = f_s * n + i_s
        h = ot * c / jnp.maximum(jnp.abs(n), 1e-6)
        return (c, n, h, m_new), h

    (c, n, h_last, m), hs = jax.lax.scan(body, state, x_gates.transpose(1, 0, 2, 3, 4))
    return hs.transpose(1, 0, 2, 3).astype(x_gates.dtype), (c, n, h_last, m)
