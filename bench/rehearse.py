#!/usr/bin/env python3
"""Compile each cell's warmed shapes at full width for a described TPU v5e,
without a chip, and print what the chip's compiler says about memory.

    JAX_PLATFORMS=cpu python3 bench/rehearse.py [--workload NAME ...]

For every (prompt length, new tokens) shape of the cell's traffic mix it
lowers the program's two executables that ``harness.serve_model`` drives,
``prefill_jit`` and ``decode_tokens``, at the shapes it drives them, on one
chip of a described ``v5e:2x2`` and prints ``memory_analysis()``. A shape that the compiler refuses, or that does not
fit, fails here at no chip time.
"""
from __future__ import annotations

import argparse
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from bench import harness, spec, traffic  # noqa: E402


def rehearse(bench: spec.Bench, workload: str, one_chip) -> None:
    from repro.configs.registry import get_config
    from repro.models.model import build_model

    cell = bench.workload(workload)
    cfg = bench.config(cell["config"])
    mix = bench.traffic(cell["traffic"])
    adapter = bench.adapter(cfg["family"])
    pcfg = adapter.program_config(cfg, get_config(cfg["arch_id"]))
    model = build_model(pcfg)
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731
    W = {k: sds(v, pcfg.jax_dtype) for k, v in adapter.shapes(cfg).items()}
    params = adapter.to_program(W)
    for S, T in traffic.shapes(mix):
        Tb = harness.bucket(T)
        cache_len = harness.bucket(S + Tb)
        cache = jax.tree.map(lambda a: sds(a.shape, a.dtype),
                             jax.eval_shape(lambda: model.init_cache(1, cache_len)))
        batch = adapter.prefill_inputs(cfg, S, sds)
        pre = model.prefill_jit.lower(params, batch, cache).compile()
        dec = model.decode_tokens.lower(params, cache, sds((1, 1), jnp.int32),
                                        n_steps=Tb).compile()
        for name, c in (("prefill", pre), ("decode", dec)):
            m = c.memory_analysis()
            print(f"{workload} S={S} T={T} cache={cache_len} {name}: "
                  f"args {m.argument_size_in_bytes / 2**30:.3f} GiB, "
                  f"temp {m.temp_size_in_bytes / 2**30:.3f} GiB, "
                  f"out {m.output_size_in_bytes / 2**30:.3f} GiB", flush=True)


def main() -> int:
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", action="append")
    args = ap.parse_args()
    jax.config.update("jax_enable_compilation_cache", False)
    bench = spec.Bench(ROOT)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])
    for w in args.workload or [w["name"] for w in bench.doc["workloads"]]:
        rehearse(bench, w, one_chip)
    return 0


if __name__ == "__main__":
    sys.exit(main())
