#!/usr/bin/env python3
"""Why the cells drive the model API and not ``MinosServingEngine.serve``.

    python3 bench/witness.py --workload NAME --seed N [--requests K]

``ModelServingBackend.run_model`` prefills the whole prompt, drops the
prefill's logits, and starts the decode scan from the last prompt token
again, at position S. The engine's tokens are then the greedy continuation
of ``prompt + [prompt[-1]]``, not of ``prompt``. For K requests of the cell's
own backlog, on the cell's weights, this serves each through the gated
engine (built as ``repro.launch.serve.serve`` builds it) and through the
harness's ``serve_model``, and prints per request:

    gap_engine      widest gap (per std) of the engine's tokens against the
                    float32 reference over the prompt (what the check reads)
    gap_dup         the same against the reference over prompt + [prompt[-1]]
    gap_model       the harness's tokens (the same model and weights, first
                    token from the prefill) against the reference
    first_*         the first token by the reference, by the program's own
                    ``prefill_jit`` logits, and as the engine served it

Runs on a TPU, or on the CPU with ``--cpu`` at the file's sizes.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

T0 = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402

from bench import harness, spec, traffic  # noqa: E402


def build_engine(pcfg, model, params, seed: int):
    """The gated engine as ``repro.launch.serve.serve`` builds it."""
    from repro.core.cost import Pricing
    from repro.launch.serve import PROBE_WORK_MS, minos_policy
    from repro.serving.engine import MinosServingEngine

    policy = minos_policy(pass_fraction=0.4, speed_sigma=0.15)
    return MinosServingEngine(pcfg, policy, Pricing.tpu_chip_seconds(4), seed=seed % 2**31,
                              speed_sigma=0.15, probe_work_ms=PROBE_WORK_MS,
                              model=model, params=params)


def witness(workload: str, seed: int, n: int, *, root: str = ROOT,
            allow_cpu: bool = False) -> list[dict]:
    bench = spec.Bench(root)
    cell = bench.workload(workload)
    cfg = bench.config(cell["config"])
    mix = bench.traffic(cell["traffic"])
    harness.device_info(bench, 1, allow_cpu=allow_cpu)
    harness.enable_cache(root)
    sys.path.insert(0, os.path.join(root, "src"))
    import jax.numpy as jnp

    from repro.configs.registry import get_config
    from repro.models.model import build_model
    from repro.serving.engine import ServeRequest

    adapter, ref = bench.adapter(cfg["family"]), bench.reference(cfg["family"])
    sizes = adapter.sizes(cfg)
    pcfg = adapter.program_config(cfg, get_config(cfg["arch_id"]))
    W = adapter.make_weights(cfg, harness.key_from_seed(seed), dtype=pcfg.jax_dtype)
    model, params = build_model(pcfg), adapter.to_program(W)
    eng = build_engine(pcfg, model, params, seed)
    rows = []
    for req in traffic.schedule(mix, seed=seed, vocab=sizes["V"])[:n]:
        p, T = req.prompt, req.max_new_tokens
        toks = np.asarray(eng.serve([ServeRequest(prompt=p, max_new_tokens=T)])[0].tokens)
        direct = harness.serve_model(model, params, p, T)
        cache = model.init_cache(1, harness.bucket(len(p) + harness.bucket(T)))
        logits, _ = model.prefill_jit(params, {"tokens": jnp.asarray(p)[None]}, cache)
        dup = np.concatenate([p, p[-1:]])
        rows.append({
            "S": req.prompt_len, "T": T,
            "gap_engine": float(ref.gaps(W, sizes, p, toks).max()),
            "gap_dup": float(ref.gaps(W, sizes, dup, toks).max()),
            "gap_model": float(ref.gaps(W, sizes, p, direct).max()),
            "first_ref": int(ref.logits_at(W, sizes, p, np.array([len(p) - 1]))[0].argmax()),
            "first_prefill": int(np.asarray(logits).reshape(-1).argmax()),
            "first_served": int(toks[0]),
        })
        print(json.dumps(rows[-1]), flush=True)
    return rows


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args()
    rows = witness(args.workload, args.seed, args.requests, allow_cpu=args.cpu)
    import jax

    print(json.dumps({
        "workload": args.workload, "seed": args.seed, "device": jax.devices()[0].device_kind,
        "gap_engine_max": max(r["gap_engine"] for r in rows),
        "gap_dup_max": max(r["gap_dup"] for r in rows),
        "gap_model_max": max(r["gap_model"] for r in rows),
        "prefill_agrees_with_ref": sum(r["first_prefill"] == r["first_ref"] for r in rows),
        "served_first_agrees_with_ref": sum(r["first_served"] == r["first_ref"] for r in rows),
        "requests": len(rows), "seconds": time.perf_counter() - T0}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
