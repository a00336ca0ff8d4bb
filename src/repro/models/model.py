"""Unified model API: every assigned architecture exposes the same surface.

    model = build_model(cfg)
    params = model.init(key)
    loss, metrics = model.loss(params, batch)          # training
    cache = model.init_cache(batch_size, max_len)
    logits, cache = model.prefill(params, batch, cache)  # inference prefill
    logits, cache = model.decode_step(params, cache, tokens)  # serve_step

Serving additionally uses the **jitted** surface:

    logits, cache = model.prefill_jit(params, batch, cache)
    tokens, cache = model.decode_tokens(params, cache, tok, n_steps)

``decode_tokens`` rolls the whole greedy decode loop into ONE compiled
program (``jax.lax.scan`` over ``decode_step``) instead of ``n_steps``
un-jitted Python dispatches — the difference between seconds and
milliseconds per request on the serving path (ROADMAP: "JIT the serving
decode path"). ``n_steps`` is static: each distinct step count compiles
once and is cached by jax; callers that want few compilations bucket it
(see ``serving/backend.py``). Because step ``t`` depends only on steps
``< t``, running extra (bucket-padding) steps never changes the first
``n`` tokens — callers slice the prefix they asked for.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from . import encdec, hybrid, transformer, xlstm


@dataclasses.dataclass(frozen=True)
class Model:
    cfg: ArchConfig
    init: Callable[[jax.Array], Any]
    loss: Callable[..., tuple[jax.Array, dict]]
    forward: Callable[..., tuple[jax.Array, jax.Array]]
    init_cache: Callable[..., Any]
    prefill: Callable[..., tuple[Optional[jax.Array], Any]]
    decode_step: Callable[..., tuple[jax.Array, Any]]
    # jitted serving surface (same semantics, compiled)
    prefill_jit: Callable[..., tuple[Optional[jax.Array], Any]]
    decode_tokens: Callable[..., tuple[jax.Array, Any]]


def build_model(cfg: ArchConfig) -> Model:
    fam = cfg.family
    if fam in ("dense", "moe", "vlm"):
        mod = transformer
    elif fam == "xlstm":
        mod = xlstm
    elif fam == "hybrid":
        mod = hybrid
    elif fam == "encdec":
        mod = encdec
    else:
        raise ValueError(f"unknown family {fam!r}")

    def init(key):
        return mod.init_params(cfg, key)

    def loss(params, batch, *, remat: bool = True):
        return mod.loss_fn(cfg, params, batch, remat=remat)

    def forward(params, batch, *, remat: bool = False):
        if fam == "encdec":
            return mod.forward(cfg, params, batch, remat=remat)
        return mod.forward(cfg, params, batch["tokens"], remat=remat)

    def init_cache(batch_size: int, max_len: int):
        # eager, once per request: a host span on the profiler's clock
        with jax.profiler.TraceAnnotation("minos.init_cache"):
            return mod.init_cache(cfg, batch_size, max_len)

    def prefill(params, batch, cache):
        if fam == "encdec":
            return mod.prefill(cfg, params, batch, cache)
        return mod.prefill(cfg, params, batch["tokens"], cache)

    def decode_step(params, cache, tokens):
        return mod.decode_step(cfg, params, cache, tokens)

    @functools.partial(jax.jit, static_argnames=("n_steps",))
    def decode_tokens(params, cache, tokens, n_steps: int):
        """Greedy-decode ``n_steps`` tokens from ``tokens`` (B, 1) in one
        compiled program. Returns ((B, n_steps) int32 tokens, final cache)."""

        def step(carry, _):
            tok, cache = carry
            logits, cache = mod.decode_step(cfg, params, cache, tok)
            with jax.named_scope("lm_head"):
                tok = greedy_token(logits)
            return (tok, cache), tok

        (_, cache), toks = jax.lax.scan(
            step, (tokens, cache), None, length=n_steps
        )
        return jnp.swapaxes(toks[:, :, 0], 0, 1), cache  # (T,B,1) -> (B,T)

    return Model(
        cfg=cfg, init=init, loss=loss, forward=forward,
        init_cache=init_cache, prefill=prefill, decode_step=decode_step,
        prefill_jit=jax.jit(prefill), decode_tokens=decode_tokens,
    )


def greedy_token(logits: jax.Array) -> jax.Array:
    """(B, 1, V) -> (B, 1) argmax token."""
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)
