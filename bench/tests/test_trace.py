"""The trace -> metric reduction, on a small trace whose answers are worked
out by hand.

The trace is in ``bench/trace.py``'s reduced form (what ``trace.load``
keeps of a profiler trace): two requests of ``qwen3-0.6b``, each a
``bench.serve`` span on the host holding a ``jit_prefill`` and a
``jit_decode_tokens`` executable on the device, with overlapping device
operations, and host time between them outside any span. Times in ns.
"""
import json
import os
import types

import pytest

from bench import flops, spec, trace

MS = 1e6

# request 1: serve 0-10 ms; prefill 1-3 ms (ops 1-2 and 1.5-3, overlapping),
# decode 4-8 ms (ops 4-6 and 6-8); host-only 0-1, 3-4, 8-10
# 10-12 ms: between requests, the host sleeps
# request 2: serve 12-20 ms; prefill 13-14 ms (op 13-14), decode 15-19 ms
# (op 15-19)
TRACE = {
    "devices": 1,
    "spans": [["bench.serve", 0 * MS, 10 * MS], ["bench.serve", 12 * MS, 8 * MS]],
    "modules": [["jit_prefill(1)", 1 * MS, 2 * MS], ["jit_decode_tokens(2)", 4 * MS, 4 * MS],
                ["jit_prefill(1)", 13 * MS, 1 * MS], ["jit_decode_tokens(2)", 15 * MS, 4 * MS]],
    "ops": [["fusion.1", 1 * MS, 1 * MS], ["fusion.2", 1.5 * MS, 1.5 * MS],
            ["while.3", 4 * MS, 2 * MS], ["while.3", 6 * MS, 2 * MS],
            ["fusion.1", 13 * MS, 1 * MS], ["while.3", 15 * MS, 4 * MS]],
    "host": [["$numpy asarray", 8 * MS, 1.5 * MS], ["PjitFunction(prefill)", 0.2 * MS, 0.6 * MS],
             ["sleep", 10.1 * MS, 1.8 * MS]],
}
REQUESTS = [{"S": 1024, "T": 32}, {"S": 2048, "T": 64}]
BUSY = 2 + 4 + 1 + 4  # ms: union of the device operations
WINDOW = 20  # ms: first span start to last span end


@pytest.fixture(scope="module")
def ctx():
    bench = spec.Bench(spec.ROOT)
    cfg = json.load(open(os.path.join(bench.dir, "configs", "qwen3-0.6b.json")))
    sizes = bench.adapter(cfg["family"]).sizes(cfg)
    return types.SimpleNamespace(
        trace=TRACE, window=trace.window(TRACE), requests=REQUESTS, sizes=sizes,
        peak=bench.peaks()["TPU v5 lite"], counts=flops.for_config(cfg["family"], sizes),
        config=cfg, run=None, bench=bench)


def read(ctx, name):
    return ctx.bench.metric_reader(name).read(ctx)


def test_window_and_busy(ctx):
    assert ctx.window == (0.0, WINDOW * MS)
    assert trace.busy_ns(TRACE, *ctx.window) == BUSY * MS
    assert trace.busy_ns(TRACE, 1.2 * MS, 2.5 * MS) == 1.3 * MS  # clipped to the window
    assert read(ctx, "device_idle_share.batch") == pytest.approx(100 * (1 - BUSY / WINDOW))


def test_module_times(ctx):
    assert trace.module_ns(TRACE, "jit_prefill", *ctx.window) == (3 * MS, 2)
    assert read(ctx, "prefill_ms_per_ktok") == pytest.approx(3 / 3.072)  # 1,024 + 2,048 tokens
    assert read(ctx, "decode_step_ms") == pytest.approx(8 / (32 + 64))


def test_roofline_and_mfu_by_hand(ctx):
    c, peak = ctx.counts, ctx.peak
    floor = c.decode_floor_s(1024, 32, peak) + c.decode_floor_s(2048, 64, peak)
    assert read(ctx, "decode_roofline") == pytest.approx(100 * floor / 8e-3)
    work = c.request_flops(1024, 32) + c.request_flops(2048, 64)
    assert read(ctx, "serve_mfu") == pytest.approx(100 * work / (20e-3 * 197e12))


def test_breakdown(ctx):
    ops = trace.top_ops(TRACE, *ctx.window)
    assert ops == [["while.3", 0.008], ["fusion.1", 0.002], ["fusion.2", 0.0015]]
    gaps = trace.idle_gaps(TRACE, *ctx.window)
    # idle: 0-1 (serve, host pjit), 3-4, 8-13 (serve, then between requests), 14-15, 19-20
    assert [round(g[1] * 1e3, 6) for g in gaps] == [5.0, 1.0, 1.0, 1.0, 1.0]
    assert gaps[0][0] == "outside benchmark spans > sleep"
    assert "bench.serve > PjitFunction(prefill)" in [g[0] for g in gaps]


def test_a_trace_without_device_events_reads_nothing(ctx):
    empty = types.SimpleNamespace(**{**vars(ctx), "trace": {**TRACE, "ops": [], "modules": []}})
    for name in ("device_idle_share.batch", "decode_step_ms", "decode_roofline", "serve_mfu",
                 "prefill_ms_per_ktok"):
        assert read(empty, name) is None


def test_load_reads_a_recorded_profile(tmp_path):
    """``trace.load`` on a profile recorded here (the CPU has no device
    plane, so only the benchmark's spans and their host thread come back)."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.serve"):
        f(x).block_until_ready()
    with jax.profiler.TraceAnnotation("bench.serve"):
        pass
    jax.profiler.stop_trace()
    tr = trace.load(str(tmp_path))
    assert [s[0] for s in tr["spans"]] == ["bench.serve", "bench.serve"]
    assert tr["devices"] == 0 and tr["ops"] == [] and tr["host"]
    lo, hi = trace.window(tr)
    assert hi > lo and trace.busy_ns(tr, lo, hi) == 0
