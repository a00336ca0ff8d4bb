"""Share (%) of its roofline that the decode executable reaches: the least
time the chip could take for the decode steps the traced requests needed
(per step the larger of operations over peak FLOP/s and bytes over HBM
bandwidth: all weights once and the valid cache prefix, so bound by HBM),
over the executable's measured device time."""
from bench import trace


def read(ctx):
    if ctx.window is None or not ctx.requests:
        return None
    ns, n = trace.module_ns(ctx.trace, "jit_decode_tokens", *ctx.window)
    if n != len(ctx.requests) or ns <= 0:
        return None
    floor = sum(ctx.counts.decode_floor_s(r["S"], r["T"], ctx.peak) for r in ctx.requests)
    return 100.0 * floor / (ns / 1e9)
