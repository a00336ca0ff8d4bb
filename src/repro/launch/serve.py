"""Serving launcher: ``python -m repro.launch.serve --arch <id> [--full] [...]``
— requests through the Minos-gated serving engine (the paper's technique as
a first-class framework feature).

The CPU default serves the reduced smoke variant of the architecture;
``--full`` serves its published widths in bfloat16, which wants a TPU.
:func:`serve` is the same path as a function, for scripts such as
``chip_smoke.py``.
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Any, Optional

import numpy as np

from repro.configs.base import ArchConfig
from repro.configs.registry import ARCH_IDS, get_config, get_smoke_config
from repro.core.cost import Pricing
from repro.core.elysium import pretest_threshold
from repro.core.policy import MinosPolicy
from repro.launch.compile_cache import enable_compile_cache
from repro.serving.engine import MinosServingEngine, ServeRequest, ServeResult

PROBE_WORK_MS = 200.0


def make_requests(
    cfg: ArchConfig, n: int, *, prompt_len: int, max_new_tokens: int, seed: int = 0,
) -> list[ServeRequest]:
    """``n`` requests with uniform random prompt ids drawn from ``seed``."""
    rs = np.random.RandomState(seed)
    return [
        ServeRequest(prompt=rs.randint(0, cfg.vocab, prompt_len).astype(np.int32),
                     max_new_tokens=max_new_tokens, request_id=i)
        for i in range(n)
    ]


def minos_policy(*, pass_fraction: float, speed_sigma: float) -> MinosPolicy:
    """Fixed gate at the pretest threshold of 128 replica speeds drawn from
    the engine's lognormal variation."""
    rs = np.random.RandomState(0)
    thr = pretest_threshold(
        PROBE_WORK_MS / np.exp(rs.normal(0, speed_sigma, 128)),
        pass_fraction=pass_fraction,
    )
    return MinosPolicy(elysium_threshold=thr, max_retries=5)


@dataclasses.dataclass
class ServeRun:
    engine: MinosServingEngine
    results: list[ServeResult]
    compile_s: float  # warm-up of every request shape: set-up, not serving time


def serve(
    cfg: ArchConfig,
    requests: list[ServeRequest],
    *,
    gated: bool = True,
    pass_fraction: float = 0.4,
    speed_sigma: float = 0.15,
    seed: int = 1,
    model: Any = None,
    params: Any = None,
) -> ServeRun:
    """Serve ``requests`` through a :class:`MinosServingEngine`, behind the
    Minos gate unless ``gated`` is False.

    Every distinct (prompt length, new tokens) shape is compiled first, so
    the per-request ``wall_ms`` of the results is steady-state serving time.
    ``model``/``params`` reuse another engine's weights and compiled
    functions; by default they are built from ``seed``.
    """
    policy = (minos_policy(pass_fraction=pass_fraction, speed_sigma=speed_sigma)
              if gated else MinosPolicy(elysium_threshold=0.0, enabled=False))
    eng = MinosServingEngine(cfg, policy, Pricing.tpu_chip_seconds(4), seed=seed,
                             speed_sigma=speed_sigma, probe_work_ms=PROBE_WORK_MS,
                             model=model, params=params)
    shapes = {(len(r.prompt), r.max_new_tokens): r for r in requests}
    t0 = time.perf_counter()
    for req in shapes.values():
        eng.backend.run_model(req)  # returns numpy: waits for the device
    compile_s = time.perf_counter() - t0
    return ServeRun(engine=eng, results=eng.serve(requests), compile_s=compile_s)


def main(argv: Optional[list[str]] = None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="qwen3-0.6b")
    ap.add_argument("--full", action="store_true",
                    help="published widths in bfloat16 (wants a TPU; the CPU "
                         "default is the reduced smoke variant)")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--max-new-tokens", type=int, default=8)
    ap.add_argument("--pass-fraction", type=float, default=0.4)
    ap.add_argument("--no-minos", action="store_true")
    ap.add_argument("--speed-sigma", type=float, default=0.15)
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfg = get_config(args.arch) if args.full else get_smoke_config(args.arch)
    reqs = make_requests(cfg, args.requests, prompt_len=16,
                         max_new_tokens=args.max_new_tokens)
    run = serve(cfg, reqs, gated=not args.no_minos,
                pass_fraction=args.pass_fraction, speed_sigma=args.speed_sigma)
    eng, res = run.engine, run.results
    lat = [r.sim_duration_ms for r in res]
    wall = [r.wall_ms for r in res]
    print(f"served {len(res)} requests | replicas started {eng.replicas_started}, "
          f"terminated {eng.replicas_terminated} | pool speed "
          f"{eng.pool_mean_speed:.3f} | mean simulated latency {np.mean(lat):.0f}ms | "
          f"cost ${eng.cost.total:.4f} | compile {run.compile_s:.2f}s | "
          f"wall per request median {np.median(wall):.2f}ms")


if __name__ == "__main__":
    main()
