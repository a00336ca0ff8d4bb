"""Device-idle time inside the program's ``minos.init_cache`` host spans
(the eager allocation of each request's KV cache), per traced request."""
from bench import trace

SPAN = "minos.init_cache"


def read(ctx):
    if ctx.window is None or not ctx.requests or not ctx.trace["ops"]:
        return None
    lo, hi = ctx.window
    spans = [(s, s + d) for name, s, d in ctx.trace["host"] if name == SPAN and lo <= s < hi]
    if not spans:
        return None
    idle = sum((b - a) - trace.busy_ns(ctx.trace, a, b) for a, b in spans)
    return idle / 1e6 / len(ctx.requests)
