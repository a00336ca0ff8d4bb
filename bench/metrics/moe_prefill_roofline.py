"""Share (%) of its roofline that a MoE cell's prefill executable
(``jit_prefill``) reaches: the least time the chip could take for the traced
requests' prompts (``prefill_floor_s`` of ``bench/flops_moe.py``: the larger
of operations over peak FLOP/s and bytes over HBM bandwidth), over the
executable's measured device time."""
from bench import trace


def read(ctx):
    if ctx.window is None or not ctx.requests:
        return None
    ns, n = trace.module_ns(ctx.trace, "jit_prefill", *ctx.window)
    if n != len(ctx.requests) or ns <= 0:
        return None
    floor = sum(ctx.counts.prefill_floor_s(r["S"], ctx.peak) for r in ctx.requests)
    return 100.0 * floor / (ns / 1e9)
