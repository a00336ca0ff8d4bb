#!/usr/bin/env python3
"""Chip smoke test: the system's main path, once, on one TPU chip.

    python3 chip_smoke.py [--seed N]

Run it from the root of a checkout; it needs no install, no network and no
other process on the chip. Phases, in order:

1. device      -- name the platform, device kind and device count; anything
                  but a TPU ends the run here, with a non-zero exit.
2. serve       -- qwen3-0.6b at its published widths (bf16, 28 layers)
                  through ``repro.launch.serve.serve``: 8 requests of 128
                  prompt tokens and 32 new tokens behind the Minos gate, then
                  the same requests ungated. The tokens must be identical and
                  no request may take the eager decode path. Compile time is
                  set-up, reported apart from the per-request wall time.
3. correctness -- bf16 last-token prefill logits and one decode step through
                  the cache, against a float32 forward pass of the same
                  seeded weights at matmul precision "highest".
4. probe       -- the Pallas matmul probe (512x512, f32), compiled, against
                  a plain jnp product.
5. scan        -- one ``simulate_arms`` batch at the size of
                  ``benchmarks/grid_sweep.py --smoke``; per-lane conservation.

A failed phase raises, and the script exits non-zero. Only when every phase
passed is the last line of stdout the JSON object
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks import grid_sweep  # noqa: E402
from repro.analysis import sanitizer  # noqa: E402
from repro.configs.base import ArchConfig  # noqa: E402
from repro.configs.registry import get_config  # noqa: E402
from repro.core.benchmark import MatmulProbe  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.serve import ServeRun, make_requests, serve  # noqa: E402
from repro.models.model import build_model  # noqa: E402
from repro.sim.vectorized import simulate_arms  # noqa: E402

ARCH = "qwen3-0.6b"

# bf16 against float32, measured as a share of the reference logits' spread
# (std ~32 with tied N(0, 1) embeddings). bf16 rounding (2^-8) compounds
# over the layers: at full width the relative RMS error was 0.005 / 0.008 /
# 0.011 at 2 / 4 / 8 layers (CPU), so ~0.02 at 28. Weights rounded to fp8
# (e4m3) instead gave 0.06 / 0.07 / 0.10, so 0.05 fails a lower precision.
# The largest error over the vocabulary is ~5x the RMS one (0.05 of the
# std at 8 layers, 0.24-0.42 with fp8 weights); 0.25 leaves bf16 room and
# still fails fp8.
REL_RMS_TOL = 0.05
MAX_ABS_TOL_PER_STD = 0.25


class SmokeFailure(RuntimeError):
    pass


def _require(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def _say(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


def phase_device() -> dict:
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    _say("device", f"platform={info['platform']} kind={info['kind']} "
                   f"count={info['count']}")
    return info


def phase_serve(cfg: ArchConfig, *, n_requests: int = 8, prompt_len: int = 128,
                max_new_tokens: int = 32, seed: int = 0) -> ServeRun:
    """Gated, then ungated on the same weights; returns the gated run."""
    reqs = make_requests(cfg, n_requests, prompt_len=prompt_len,
                         max_new_tokens=max_new_tokens, seed=seed)
    gated = serve(cfg, reqs, gated=True, seed=seed)
    eng = gated.engine
    ungated = serve(cfg, reqs, gated=False, seed=seed, model=eng.model,
                    params=eng.params)
    for name, run in (("gated", gated), ("ungated", ungated)):
        wall = np.asarray([r.wall_ms for r in run.results])
        stats = run.engine.jit_stats
        _say("serve", f"{name}: {len(run.results)} requests | compile (set-up) "
                      f"{run.compile_s:.3f} s | wall per request ms median "
                      f"{np.median(wall):.3f} min {wall.min():.3f} max "
                      f"{wall.max():.3f} | replicas started "
                      f"{run.engine.replicas_started} terminated "
                      f"{run.engine.replicas_terminated} | jit_stats {stats}")
        _require(stats["eager_calls"] == 0, f"{name}: eager decode calls {stats}")
        _require(all(len(r.tokens) == max_new_tokens for r in run.results),
                 f"{name}: a request returned the wrong number of tokens")
    same = all(np.array_equal(a.tokens, b.tokens)
               for a, b in zip(gated.results, ungated.results))
    _say("serve", f"gate judged {len(eng.probe_observations)} probes | "
                  f"gated and ungated tokens identical: {same}")
    _require(same, "gated and ungated runs produced different tokens")
    return gated


def phase_correctness(run: ServeRun, prompt: np.ndarray, *,
                      max_new_tokens: int = 32) -> dict:
    """Served-precision prefill and one cached decode step against a float32
    forward pass over the prompt plus the decoded token."""
    eng = run.engine
    model, params, cfg = eng.model, eng.params, eng.cfg
    tokens = jnp.asarray(prompt, jnp.int32)[None]
    S = tokens.shape[1]
    # the cache the serving path allocates for this shape: same executable
    cache = model.init_cache(1, eng.backend.cache_len(S, max_new_tokens))
    logits, cache = model.prefill_jit(params, {"tokens": tokens}, cache)
    nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    dec_logits, _ = jax.jit(model.decode_step)(params, cache, nxt)

    model32 = build_model(dataclasses.replace(cfg, dtype="float32"))
    params32 = jax.tree.map(lambda x: x.astype(jnp.float32), params)
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(lambda p, t: model32.forward(p, {"tokens": t})[0])(
            params32, jnp.concatenate([tokens, nxt], axis=1))
    ref = np.asarray(ref, np.float32)[0]
    out = {}
    for name, got, want in (("prefill", logits, ref[S - 1]),
                            ("decode", dec_logits, ref[S])):
        got = np.asarray(got, np.float32).reshape(-1)
        _require(bool(np.isfinite(got).all()), f"{name} logits not finite")
        err = np.abs(got - want)
        std = float(want.std())
        rel_rms = float(np.sqrt(np.mean(err ** 2))) / std
        max_tol = MAX_ABS_TOL_PER_STD * std
        _say("correctness", f"{name} ({cfg.dtype} vs float32, V={got.size}): "
                            f"max abs err {err.max():.4f} (tol {max_tol:.4f}) | "
                            f"rel rms err {rel_rms:.5f} (tol {REL_RMS_TOL}) | "
                            f"ref std {std:.3f}")
        _require(err.max() <= max_tol and rel_rms <= REL_RMS_TOL,
                 f"{name} logits outside tolerance")
        out[name] = {"max_abs_err": float(err.max()), "rel_rms_err": rel_rms}
    return out


def phase_probe(n: int = 512, *, seed: int = 0) -> dict:
    probe = MatmulProbe(n=n)
    a = jnp.full((n, n), 0.5, jnp.float32)
    compiled = not ops.runs_interpreted(a)
    t0 = time.perf_counter()
    probe.run()
    first_ms = (time.perf_counter() - t0) * 1e3
    wall_ms = probe.run()
    got = np.asarray(probe.compute())
    want = a
    for _ in range(probe.repeats):
        want = jnp.dot(want, jnp.full((n, n), 0.25, jnp.float32),
                       precision=jax.lax.Precision.HIGHEST)
    _require(np.array_equal(got, np.asarray(want)),
             "probe chain differs from jnp (exact in f32)")
    # random operands: the constant chain above cannot see a misplaced tile
    rs = np.random.RandomState(seed)
    x = jnp.asarray(rs.randn(n, n), jnp.float32)
    y = jnp.asarray(rs.randn(n, n), jnp.float32)
    ref = np.asarray(jnp.dot(x, y, precision=jax.lax.Precision.HIGHEST))
    err = float(np.abs(np.asarray(ops.matmul(x, y)) - ref).max())
    # one bf16 MXU pass errs by ~0.015 of the std at most here; a misplaced
    # tile errs by about the std itself
    tol = 0.05 * float(ref.std())
    _say("probe", f"MatmulProbe(n={n}) compiled={compiled} first call "
                  f"{first_ms:.3f} ms (set-up) | wall {wall_ms:.3f} ms for "
                  f"{probe.repeats} matmuls | random-operand max abs err "
                  f"{err:.5f} (tol {tol:.5f})")
    _require(err <= tol, "Pallas matmul differs from jnp on random operands")
    return {"compiled": compiled, "wall_ms": wall_ms}


def phase_scan(*, seed: int = 0) -> dict:
    fracs, sigmas, profiles, gates, n_steps, seeds = grid_sweep.grid_sizes(
        smoke=True, seed=seed)
    arms, meta = grid_sweep.build_grid(fracs, sigmas, profiles, gates)
    t0 = time.perf_counter()
    res = simulate_arms(arms, seeds=seeds, n_steps=n_steps)
    first_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    res = simulate_arms(arms, seeds=seeds, n_steps=n_steps)
    wall_s = time.perf_counter() - t0
    sanitizer.check_closed_summary(res.summary, where="chip_smoke")
    lanes = len(meta) * len(seeds)
    _say("scan", f"simulate_arms arms={len(meta)} seeds={len(seeds)} "
                 f"steps={n_steps}: first call {first_s:.3f} s (set-up) | "
                 f"wall {wall_s:.4f} s ({lanes * n_steps / wall_s:.0f} simulated "
                 f"requests/s) | per-lane conservation holds")
    return {"lanes": lanes, "wall_s": wall_s}


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the weights, prompts and scan lanes")
    args = ap.parse_args(argv)

    device = phase_device()
    if device["platform"] != "tpu":
        print(f"chip_smoke: needs a TPU, found {device['platform']!r}",
              file=sys.stderr)
        return 2
    _say("setup", f"compile cache {enable_compile_cache()}")
    cfg = get_config(ARCH)
    _say("serve", f"{ARCH}: {cfg.n_layers} layers d_model {cfg.d_model} "
                  f"heads {cfg.n_heads}/{cfg.n_kv_heads} vocab {cfg.vocab} "
                  f"{cfg.dtype}")
    run = phase_serve(cfg, seed=args.seed)
    prompt = make_requests(cfg, 1, prompt_len=128, max_new_tokens=32,
                           seed=args.seed)[0].prompt
    phase_correctness(run, prompt)
    del run
    probe = phase_probe(seed=args.seed)
    _require(probe["compiled"], "the matmul probe ran in the interpreter")
    phase_scan(seed=args.seed)
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
