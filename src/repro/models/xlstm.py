"""xLSTM language model (arXiv:2405.04517): a stack of mLSTM blocks with an
sLSTM block every ``cfg.slstm_every`` layers (the paper's mixed-block
design). d_ff == 0: blocks carry their own up/down projections (expand 2x),
no separate FFN.

Layer layout (n_layers=48, slstm_every=8):
  [7x mLSTM, 1x sLSTM] x 6  — the mLSTM run of each group is a scanned
stack (one compiled body), the sLSTM block is applied unscanned (it is the
sequential cell; there are only n_layers/slstm_every of them).
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.distributed.sharding import shard
from .layers import cross_entropy, normal_init, rms_norm, unembed
from .ssm import mlstm_chunked, mlstm_step, slstm_scan

EXPAND = 2


def _dims(cfg: ArchConfig):
    d_inner = EXPAND * cfg.d_model
    H = cfg.n_heads
    P = d_inner // H
    return d_inner, H, P


def init_mlstm_block(cfg: ArchConfig, key) -> dict[str, Any]:
    d, (d_inner, H, P) = cfg.d_model, _dims(cfg)
    ks = jax.random.split(key, 8)
    s = d**-0.5
    si = d_inner**-0.5
    dt = cfg.jax_dtype
    return {
        "ln": jnp.ones((d,), dt),
        "w_up": normal_init(ks[0], (d, 2 * d_inner), s, dt),
        # per-head block-diagonal projections (xLSTM's multi-head design):
        # (H, P, P) instead of dense (d_inner, d_inner)
        "wq": normal_init(ks[1], (H, P, P), P**-0.5, dt),
        "wk": normal_init(ks[2], (H, P, P), P**-0.5, dt),
        "wv": normal_init(ks[3], (H, P, P), P**-0.5, dt),
        "w_i": normal_init(ks[4], (d_inner, H), si, dt),
        "w_f": normal_init(ks[5], (d_inner, H), si, dt),
        "b_f": jnp.full((H,), 3.0, dt),  # open forget gates at init
        "b_i": jnp.full((H,), -2.0, dt),
        "hnorm": jnp.ones((d_inner,), dt),
        "w_down": normal_init(ks[6], (d_inner, d), si, dt),
    }


def _mlstm_qkv(cfg, p, x):
    d_inner, H, P = _dims(cfg)
    xn = rms_norm(x, p["ln"], cfg.norm_eps)
    u = xn @ p["w_up"]
    a, z = jnp.split(u, 2, axis=-1)
    a = shard(a, "batch", None, "ff")
    B, S = x.shape[:2]
    ah = a.reshape(B, S, H, P)
    q = jnp.einsum("bshp,hpr->bshr", ah, p["wq"])
    k = jnp.einsum("bshp,hpr->bshr", ah, p["wk"])
    v = jnp.einsum("bshp,hpr->bshr", ah, p["wv"])
    ig = a @ p["w_i"] + p["b_i"].astype(jnp.float32)
    fg = a @ p["w_f"] + p["b_f"].astype(jnp.float32)
    return q, k, v, ig, fg, z


def mlstm_block(cfg: ArchConfig, p, x, *, chunk: int, state=None):
    """x: (B,S,d). Returns (y, new_state)."""
    q, k, v, ig, fg, z = _mlstm_qkv(cfg, p, x)
    h, new_state = mlstm_chunked(q, k, v, ig, fg, chunk=chunk, state=state)
    B, S = x.shape[:2]
    h = h.reshape(B, S, -1)
    h = rms_norm(h, p["hnorm"], cfg.norm_eps) * jax.nn.silu(z)
    return x + h @ p["w_down"], new_state


def mlstm_block_step(cfg: ArchConfig, p, x, state):
    """x: (B,1,d); single-token decode."""
    q, k, v, ig, fg, z = _mlstm_qkv(cfg, p, x)
    h, new_state = mlstm_step(q[:, 0], k[:, 0], v[:, 0], ig[:, 0], fg[:, 0], state)
    h = h.reshape(x.shape[0], 1, -1)
    h = rms_norm(h, p["hnorm"], cfg.norm_eps) * jax.nn.silu(z)
    return x + h @ p["w_down"], new_state


def init_slstm_block(cfg: ArchConfig, key) -> dict[str, Any]:
    d = cfg.d_model
    H = cfg.n_heads
    P = d // H
    ks = jax.random.split(key, 3)
    dt = cfg.jax_dtype
    return {
        "ln": jnp.ones((d,), dt),
        "w_gates": normal_init(ks[0], (d, 4 * d), d**-0.5, dt),
        "b_gates": jnp.concatenate(
            [jnp.zeros((2 * d,), dt), jnp.full((d,), 3.0, dt), jnp.zeros((d,), dt)]
        ),
        "R": normal_init(ks[1], (4, H, P, P), P**-0.5, dt),
        "hnorm": jnp.ones((d,), dt),
        "w_out": normal_init(ks[2], (d, d), d**-0.5, dt),
    }


def _slstm_gates(cfg, p, x):
    B, S, d = x.shape
    H = cfg.n_heads
    P = d // H
    xn = rms_norm(x, p["ln"], cfg.norm_eps)
    xg = xn @ p["w_gates"] + p["b_gates"].astype(jnp.float32)
    return xg.reshape(B, S, 4, H, P)


def slstm_block(cfg: ArchConfig, p, x, *, state=None):
    xg = _slstm_gates(cfg, p, x)
    h, new_state = slstm_scan(xg, p["R"], state=state)
    B, S = x.shape[:2]
    h = h.reshape(B, S, -1).astype(x.dtype)
    h = rms_norm(h, p["hnorm"], cfg.norm_eps)
    return x + h @ p["w_out"], new_state


# ---------------------------------------------------------------------------
# Model
# ---------------------------------------------------------------------------


def _plan(cfg: ArchConfig) -> list[tuple[str, int]]:
    """[(kind, count)] groups: runs of mLSTM followed by one sLSTM."""
    if not cfg.slstm_every:
        return [("mlstm", cfg.n_layers)]
    groups = []
    n_groups = cfg.n_layers // cfg.slstm_every
    for _ in range(n_groups):
        groups.append(("mlstm", cfg.slstm_every - 1))
        groups.append(("slstm", 1))
    rem = cfg.n_layers - n_groups * cfg.slstm_every
    if rem:
        groups.append(("mlstm", rem))
    return groups


def init_params(cfg: ArchConfig, key) -> dict[str, Any]:
    k_emb, k_blocks, k_out = jax.random.split(key, 3)
    params: dict[str, Any] = {
        "embed": normal_init(k_emb, (cfg.vocab, cfg.d_model), 1.0, cfg.jax_dtype),
        "final_norm": jnp.ones((cfg.d_model,), cfg.jax_dtype),
        "unembed": normal_init(
            k_out, (cfg.d_model, cfg.vocab), cfg.d_model**-0.5, cfg.jax_dtype
        ),
        "groups": [],
    }
    init_mlstm_stack = jax.vmap(functools.partial(init_mlstm_block, cfg))
    for gi, (kind, count) in enumerate(_plan(cfg)):
        gk = jax.random.fold_in(k_blocks, gi)
        if kind == "mlstm":
            keys = jax.random.split(gk, count)
            params["groups"].append(init_mlstm_stack(keys))
        else:
            params["groups"].append(init_slstm_block(cfg, gk))
    return params


def _apply(cfg: ArchConfig, params, x, *, chunk: int, states=None, remat: bool = True):
    """Returns (x, new_states). states: list aligned with _plan groups —
    for mlstm groups a stacked (C,n,m) tuple, for slstm the (c,n,h,m)."""
    plan = _plan(cfg)
    new_states = []
    for gi, (kind, count) in enumerate(plan):
        p = params["groups"][gi]
        st = states[gi] if states is not None else None
        if kind == "mlstm":

            def body(x, inp):
                pl, s = inp
                y, ns = mlstm_block(cfg, pl, x, chunk=chunk, state=s)
                return y, ns

            if remat:
                body = jax.checkpoint(body)
            if st is None:
                B = x.shape[0]
                d_inner, H, P = _dims(cfg)
                st = (
                    jnp.zeros((count, B, H, P, P), jnp.float32),
                    jnp.zeros((count, B, H, P), jnp.float32),
                    jnp.full((count, B, H), -1e30, jnp.float32),
                )
            x, ns = jax.lax.scan(body, x, (p, st))
            new_states.append(ns)
        else:
            x, ns = slstm_block(cfg, p, x, state=st)
            new_states.append(ns)
    return x, new_states


def forward(cfg: ArchConfig, params, tokens, *, remat: bool = True):
    x = jnp.take(params["embed"], tokens, axis=0)
    x = shard(x, "batch", "seq", None)
    chunk = cfg.ssm.chunk if cfg.ssm else 256
    x, _ = _apply(cfg, params, x, chunk=chunk, remat=remat)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return unembed(x, params["unembed"]), 0.0


def loss_fn(cfg: ArchConfig, params, batch, *, remat: bool = True):
    logits, aux = forward(cfg, params, batch["tokens"], remat=remat)
    ce, nll = cross_entropy(logits, batch["labels"])
    return ce + aux, {"ce": ce, "nll": nll, "aux": aux}


def init_cache(cfg: ArchConfig, batch: int, max_len: int, **_):
    """Recurrent state; O(1) in max_len — the xLSTM long-context advantage."""
    d_inner, H, P = _dims(cfg)
    d = cfg.d_model
    Hs, Ps = cfg.n_heads, d // cfg.n_heads
    states = []
    for kind, count in _plan(cfg):
        if kind == "mlstm":
            states.append(
                (
                    jnp.zeros((count, batch, H, P, P), jnp.float32),
                    jnp.zeros((count, batch, H, P), jnp.float32),
                    jnp.full((count, batch, H), -1e30, jnp.float32),
                )
            )
        else:
            z = jnp.zeros((batch, Hs, Ps), jnp.float32)
            states.append((z, z, z, jnp.full((batch, Hs, Ps), -1e30, jnp.float32)))
    return {"states": states, "lengths": jnp.zeros((batch,), jnp.int32)}


def prefill(cfg: ArchConfig, params, tokens, cache):
    x = jnp.take(params["embed"], tokens, axis=0)
    x = shard(x, "batch", "seq", None)
    chunk = cfg.ssm.chunk if cfg.ssm else 256
    x, states = _apply(cfg, params, x, chunk=chunk, states=cache["states"], remat=False)
    x = rms_norm(x[:, -1:], params["final_norm"], cfg.norm_eps)
    logits = unembed(x, params["unembed"])
    return logits, {"states": states, "lengths": cache["lengths"] + tokens.shape[1]}


def decode_step(cfg: ArchConfig, params, cache, tokens):
    x = jnp.take(params["embed"], tokens, axis=0)
    x = shard(x, "batch", "seq", None)
    new_states = []
    for gi, (kind, count) in enumerate(_plan(cfg)):
        p = params["groups"][gi]
        st = cache["states"][gi]
        if kind == "mlstm":

            def body(x, inp):
                pl, s = inp
                y, ns = mlstm_block_step(cfg, pl, x, s)
                return y, ns

            x, ns = jax.lax.scan(body, x, (p, st))
            new_states.append(ns)
        else:
            x, ns = slstm_block(cfg, p, x, state=st)
            new_states.append(ns)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = unembed(x, params["unembed"])
    return logits, {"states": new_states, "lengths": cache["lengths"] + 1}
