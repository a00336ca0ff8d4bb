"""Device time of the prefill executable (``jit_prefill``) per thousand
prompt tokens of the traced requests."""
from bench import trace


def read(ctx):
    if ctx.window is None or not ctx.requests:
        return None
    ns, n = trace.module_ns(ctx.trace, "jit_prefill", *ctx.window)
    if n != len(ctx.requests) or ns <= 0:
        return None
    return ns / 1e6 / (sum(ctx.counts.prefill_positions(r["S"]) for r in ctx.requests) / 1000.0)
