"""Model FLOP/s utilization (%) of the whole serving path: the operations
that the prompt and output tokens of the requests served in the traced
window need, over the window's length times the chip's bf16 peak."""


def read(ctx):
    if ctx.window is None or not ctx.requests or not ctx.trace["ops"]:
        return None
    lo, hi = ctx.window
    work = sum(ctx.counts.request_flops(r["S"], r["T"]) for r in ctx.requests)
    return 100.0 * work / ((hi - lo) / 1e9 * ctx.peak["bf16_flops_per_s"])
