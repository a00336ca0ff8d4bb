"""Training loop: jit'd train_step factory + a simple host loop with
checkpointing. Used by launch/train.py, examples/train_lm.py and the
per-arch smoke tests.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models.model import Model, build_model
from repro.optim.adamw import AdamW, AdamWState
from repro.optim.schedule import warmup_cosine


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    peak_lr: float = 3e-4
    warmup_steps: int = 100
    total_steps: int = 1000
    weight_decay: float = 0.1
    clip_norm: float = 1.0
    remat: bool = True


def make_optimizer(tc: TrainConfig) -> AdamW:
    return AdamW(
        learning_rate=warmup_cosine(tc.peak_lr, tc.warmup_steps, tc.total_steps),
        weight_decay=tc.weight_decay,
        clip_norm=tc.clip_norm,
    )


def make_train_step(model: Model, opt: AdamW, *, remat: bool = True) -> Callable:
    """Returns train_step(params, opt_state, batch) -> (params, opt_state, metrics)."""

    def train_step(params, opt_state: AdamWState, batch):
        (loss, metrics), grads = jax.value_and_grad(
            lambda p: model.loss(p, batch, remat=remat), has_aux=True
        )(params)
        params, opt_state, opt_metrics = opt.update(grads, opt_state, params)
        metrics = dict(metrics)
        metrics.update(opt_metrics)
        metrics["loss"] = loss
        return params, opt_state, metrics

    return train_step


def train(
    cfg: ArchConfig,
    data_iter,
    tc: TrainConfig,
    *,
    steps: int,
    seed: int = 0,
    log_every: int = 10,
    log_fn: Optional[Callable[[int, dict], None]] = None,
) -> tuple[Any, list[dict]]:
    """Host-side loop (single device). Returns (params, history)."""
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(seed))
    opt = make_optimizer(tc)
    opt_state = opt.init(params)
    step_fn = jax.jit(make_train_step(model, opt, remat=tc.remat))
    history = []
    t0 = time.perf_counter()
    for step in range(steps):
        batch = {k: jnp.asarray(v) for k, v in next(data_iter).items()}
        params, opt_state, metrics = step_fn(params, opt_state, batch)
        if step % log_every == 0 or step == steps - 1:
            m = {k: float(v) for k, v in metrics.items()}
            m["step"] = step
            m["wall_s"] = time.perf_counter() - t0
            history.append(m)
            if log_fn:
                log_fn(step, m)
    return params, history
