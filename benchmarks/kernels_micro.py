"""Kernel microbenchmarks: wall time per call. On a TPU the kernels run
compiled; elsewhere they run in the Pallas interpreter, and the numbers only
validate plumbing and give the ref-vs-kernel overhead picture. The headline
says which."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops, ref


def _time(fn, *args, reps=3) -> float:
    fn(*args)  # warm up / compile
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / reps * 1e6  # us


def kernel_micro(quick=True):
    rows = []
    rs = np.random.RandomState(0)
    a = jnp.asarray(rs.randn(256, 512), jnp.float32)
    b = jnp.asarray(rs.randn(512, 256), jnp.float32)
    rows.append({"kernel": "matmul_probe", "us_per_call": round(_time(ops.matmul, a, b), 1),
                 "ref_us": round(_time(lambda x, y: ref.matmul_ref(x, y), a, b), 1)})
    q = jnp.asarray(rs.randn(1, 4, 256, 64), jnp.float32)
    k = jnp.asarray(rs.randn(1, 2, 256, 64), jnp.float32)
    v = jnp.asarray(rs.randn(1, 2, 256, 64), jnp.float32)
    rows.append({
        "kernel": "flash_attention",
        "us_per_call": round(_time(lambda *x: ops.flash_attention(*x), q, k, v), 1),
        "ref_us": round(_time(lambda *x: ref.attention_ref(*x), q, k, v), 1),
    })
    q1 = jnp.asarray(rs.randn(2, 4, 1, 64), jnp.float32)
    kc = jnp.asarray(rs.randn(2, 2, 512, 64), jnp.float32)
    vc = jnp.asarray(rs.randn(2, 2, 512, 64), jnp.float32)
    ln = jnp.array([512, 300], jnp.int32)
    rows.append({
        "kernel": "decode_attention",
        "us_per_call": round(_time(lambda *x: ops.decode_attention(*x), q1, kc, vc, ln), 1),
        "ref_us": round(_time(lambda *x: ref.decode_attention_ref(*x), q1, kc, vc, ln), 1),
    })
    return rows, "interpret_mode" if ops.runs_interpreted(a) else "compiled"
