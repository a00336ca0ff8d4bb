"""Mixture-of-Experts FFN: token-choice top-k routing, optional always-on
shared experts (DeepSeekMoE's fine-grained + shared design,
arXiv:2401.06066), router z-loss and load-balance auxiliary loss.

Expert parallelism: a layer holds experts ``first_held .. first_held +
held - 1`` of the ``n_experts`` its router scores (all of them unless the
config says otherwise), and computes their share of the result: the sum,
over the top-k picks that land on a held expert, of gate times that
expert's SwiGLU. Under pjit the expert dim of the expert weights and of the
capacity dispatch is sharded on the logical "expert" axis (mesh "model"),
and the dispatch einsum lowers to an all-to-all across the model axis.

Two dispatches, chosen by the caller:

- ``apply_moe`` (training forward and loss): capacity dispatch, GShard /
  Switch style. Each expert takes at most C = ceil(S·top_k/E · cf) tokens
  per sequence-row group; overflow tokens fall through (the residual
  passes them unchanged), and the auxiliary losses are returned.
- ``apply_moe_dropless`` (prefill and decode): no pick is dropped however
  uneven the routing. Many tokens (prefill): the held (token, pick) pairs
  sorted by expert and multiplied by ``jax.lax.ragged_dot`` over the held
  experts, on twice the rows that even routing fills, or on every pair
  where more are held. Fewer picks than held experts (decode): a loop over the held
  pairs, each reading only its own expert's weights.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.distributed.sharding import shard
from .layers import normal_init


def init_moe(key, cfg: ArchConfig):
    m = cfg.moe
    k_r, k_e, k_s = jax.random.split(key, 3)
    d, de, E = cfg.d_model, m.d_expert, m.held
    s_in, s_out = d**-0.5, de**-0.5
    p = {
        "router": normal_init(k_r, (d, m.n_experts), s_in, jnp.float32),
        "w_gate": normal_init(k_e, (E, d, de), s_in, cfg.jax_dtype),
        "w_up": normal_init(jax.random.fold_in(k_e, 1), (E, d, de), s_in, cfg.jax_dtype),
        "w_down": normal_init(jax.random.fold_in(k_e, 2), (E, de, d), s_out, cfg.jax_dtype),
    }
    if m.n_shared:
        p["shared"] = {
            "w_gate": normal_init(k_s, (d, m.n_shared * de), s_in, cfg.jax_dtype),
            "w_up": normal_init(
                jax.random.fold_in(k_s, 1), (d, m.n_shared * de), s_in, cfg.jax_dtype
            ),
            "w_down": normal_init(
                jax.random.fold_in(k_s, 2), (m.n_shared * de, d), s_out, cfg.jax_dtype
            ),
        }
    return p


def _capacity(tokens: int, top_k: int, n_experts: int, cf: float) -> int:
    return max(4, int(tokens * top_k * cf / n_experts))


def _route(m, x: jax.Array, router: jax.Array):
    """Router over all n_experts in float32: (logits, probs, top-k gates
    renormalised to sum to 1, top-k expert ids)."""
    logits = x.astype(jnp.float32) @ router.astype(jnp.float32)  # (B, S, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, m.top_k)            # (B, S, K)
    gate_vals = gate_vals / jnp.maximum(jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)
    return logits, probs, gate_vals, gate_idx


def _shared(sp, x: jax.Array) -> jax.Array:
    return (jax.nn.silu(x @ sp["w_gate"]) * (x @ sp["w_up"])) @ sp["w_down"]


def apply_moe(cfg: ArchConfig, p, x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Capacity dispatch. x: (B, S, d) -> (y, aux_loss)."""
    m = cfg.moe
    B, S, d = x.shape
    E, K = m.n_experts, m.top_k
    C = _capacity(S, K, E, m.capacity_factor)

    logits, probs, gate_vals, gate_idx = _route(m, x, p["router"])

    # --- aux losses (computed on the full distribution) ---
    # load balance (Switch): E * sum_e f_e * p_e
    top1 = jnp.argmax(probs, axis=-1)
    f = jnp.mean(jax.nn.one_hot(top1, E, dtype=jnp.float32), axis=(0, 1))
    pbar = jnp.mean(probs, axis=(0, 1))
    lb = E * jnp.sum(f * pbar)
    z = jnp.mean(jax.scipy.special.logsumexp(logits, axis=-1) ** 2)
    aux = m.load_balance_weight * lb + m.router_z_weight * z

    # --- top-k dispatch with capacity, over the held experts (a pick of
    # another expert one-hots to zeros) ---
    E = m.held
    onehot = jax.nn.one_hot(gate_idx - m.first_held, E, dtype=jnp.float32)  # (B, S, K, E)
    # position of each (token, k) within its expert queue
    pos_in_e = jnp.cumsum(onehot.reshape(B, S * K, E), axis=1).reshape(B, S, K, E)
    pos_in_e = (pos_in_e - 1.0) * onehot                     # 0-based, only where routed
    keep = (pos_in_e < C) & (onehot > 0)
    pos = jnp.sum(pos_in_e * onehot, axis=-1).astype(jnp.int32)  # (B, S, K)
    cap_oh = jax.nn.one_hot(pos, C, dtype=jnp.float32) * keep.max(-1, keepdims=False)[
        ..., None
    ].astype(jnp.float32)  # (B, S, K, C)

    # dispatch mask (B, S, E, C)
    dispatch = jnp.einsum("bske,bskc->bsec", onehot * keep.astype(jnp.float32), cap_oh)
    combine = jnp.einsum("bsk,bske,bskc->bsec", gate_vals, onehot * keep.astype(jnp.float32), cap_oh)
    dispatch = shard(dispatch, "batch", None, "expert", None)
    combine = shard(combine, "batch", None, "expert", None)

    xe = jnp.einsum("bsec,bsd->becd", dispatch.astype(x.dtype), x)  # (B, E, C, d)
    xe = shard(xe, "batch", "expert", None, None)
    h = jnp.einsum("becd,edf->becf", xe, p["w_gate"])
    u = jnp.einsum("becd,edf->becf", xe, p["w_up"])
    h = jax.nn.silu(h) * u
    ye = jnp.einsum("becf,efd->becd", h, p["w_down"])               # (B, E, C, d)
    ye = shard(ye, "batch", "expert", None, None)
    y = jnp.einsum("bsec,becd->bsd", combine.astype(x.dtype), ye)

    if m.n_shared:
        y = y + _shared(p["shared"], x)
    return shard(y, "batch", None, None), aux


def apply_moe_dropless(cfg: ArchConfig, p, x: jax.Array, layer=None) -> jax.Array:
    """Every pick of a held expert computed, none dropped. x: (B, S, d) -> y.
    With ``layer`` (decode), ``p`` holds every layer's weights stacked and
    layer ``layer``'s are used, each expert read where it lies."""
    m = cfg.moe

    def at(a):
        return a if layer is None else jax.lax.dynamic_index_in_dim(a, layer, 0, False)

    with jax.named_scope("moe_route"):
        _, _, gate_vals, gate_idx = _route(m, x, at(p["router"]))
        local = gate_idx - m.first_held                   # (B, S, K) ids among the held
        held = (local >= 0) & (local < m.held)
    if x.shape[0] * x.shape[1] * m.top_k <= m.held:
        y = _held_picks(m, p, x, local, held, gate_vals, layer)
    else:
        y = _held_grouped(m, jax.tree.map(at, p), x, local, held, gate_vals)
    if m.n_shared:
        with jax.named_scope("moe_combine"):
            y = y + _shared(jax.tree.map(at, p["shared"]), x)
    return y


def _expert(w: jax.Array, layer, e) -> jax.Array:
    """Expert ``e``'s matrix of ``w`` (E, ...), or of layer ``layer`` of the
    stack (L, E, ...): one slice where it lies, which the dot reads."""
    lead = (e,) if layer is None else (layer, e)
    rest = w.shape[len(lead):]
    return jax.lax.dynamic_slice(w, (*lead, *(0,) * len(rest)),
                                 (*(1,) * len(lead), *rest)).reshape(rest)


def _held_picks(m, p, x, local, held, gate_vals, layer) -> jax.Array:
    """Fewer picks than held experts (decode): a loop over the held (token,
    pick) pairs alone, each reading its expert's weights and no other, so a
    step reads the experts its tokens picked here (two a token, on average,
    for 8 of 64 with 16 held) and not all the held."""
    B, S, d = x.shape
    TK = B * S * m.top_k
    with jax.named_scope("moe_route"):
        held = held.reshape(TK)
        order = jnp.argsort(jnp.where(held, 0, 1), stable=True)   # the held pairs first
        n = jnp.sum(held.astype(jnp.int32))
        expert = local.reshape(TK)[order]
        token = order // m.top_k
        gate = gate_vals.reshape(TK)[order]
    xf = x.reshape(B * S, d)

    def pick(t, y):
        xt = jax.lax.dynamic_index_in_dim(xf, token[t], 0, keepdims=False)
        e = expert[t]
        with jax.named_scope("moe_experts"):
            h = jax.nn.silu(xt @ _expert(p["w_gate"], layer, e)) * (
                xt @ _expert(p["w_up"], layer, e))
            yt = h @ _expert(p["w_down"], layer, e)
        with jax.named_scope("moe_combine"):
            return y.at[token[t]].add(gate[t] * yt.astype(jnp.float32))

    y = jax.lax.fori_loop(0, n, pick, jnp.zeros((B * S, d), jnp.float32))
    return y.reshape(B, S, d).astype(x.dtype)


def _held_rows(m, pairs: int) -> int:
    """Rows the grouped product runs on first: twice the held pairs expected
    when routing is even (``pairs``·held/n_experts), in whole tiles of 128,
    and never more than ``pairs``."""
    return min(pairs, -(-2 * pairs * m.held // m.n_experts // 128) * 128)


def _held_grouped(m, p, x, local, held, gate_vals) -> jax.Array:
    """The B·S·top_k (token, pick) pairs sorted by held expert, the picks of
    other experts last; ``ragged_dot`` multiplies each held expert's run of
    rows by its weights, and the rows are put back in (token, pick) order
    and summed with their gates. Shapes are fixed: the product runs on the
    first ``_held_rows`` sorted rows when the held pairs fit in them, and
    on all B·S·top_k (every pick could be held) when they do not, so no
    routing, however uneven, drops a pick."""
    B, S, d = x.shape
    TK, E = B * S * m.top_k, m.held
    with jax.named_scope("moe_route"):
        held = held.reshape(TK)
        group = jnp.where(held, local.reshape(TK), E)
        order = jnp.argsort(group, stable=True)
        back = jnp.zeros_like(order).at[order].set(jnp.arange(TK, dtype=order.dtype))
        sizes = jnp.sum(jax.nn.one_hot(group, E, dtype=jnp.int32), axis=0)
        w = jnp.where(held, gate_vals.reshape(TK), 0.0)

    def grouped(rows: int):
        def run():
            with jax.named_scope("moe_route"):
                xs = jnp.take(x.reshape(-1, d), order[:rows] // m.top_k, axis=0)  # (rows, d)
            with jax.named_scope("moe_experts"):
                h = jax.nn.silu(jax.lax.ragged_dot(xs, p["w_gate"], sizes)) * (
                    jax.lax.ragged_dot(xs, p["w_up"], sizes))
                ys = jax.lax.ragged_dot(h, p["w_down"], sizes)                # (rows, d)
            with jax.named_scope("moe_combine"):
                # (token, pick) order; a pick that is not held reads a zero
                # row after the product's (its rows past the held runs are unset)
                ys = jnp.concatenate([ys, jnp.zeros((1, d), ys.dtype)])
                yk = jnp.take(ys, jnp.where(held, back, rows), axis=0).astype(jnp.float32)
                yk = yk * w[:, None]
                return jnp.sum(yk.reshape(B, S, m.top_k, d), axis=2).astype(x.dtype)
        return run

    rows = _held_rows(m, TK)
    if rows == TK:
        return grouped(TK)()
    return jax.lax.cond(jnp.sum(sizes) <= rows, grouped(rows), grouped(TK))
