"""Share (%) of the traced window in which no operation ran on the device."""
from bench import trace


def read(ctx):
    if ctx.window is None or not ctx.trace["ops"]:
        return None
    lo, hi = ctx.window
    return 100.0 * (1.0 - trace.busy_ns(ctx.trace, lo, hi) / (hi - lo))
