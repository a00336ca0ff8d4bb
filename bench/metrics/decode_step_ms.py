"""Device time of the decode executable (``jit_decode_tokens``, one
``lax.scan``) per scan step it ran, bucket padding included: T new tokens
run a scan of T rounded up to a power of two (at least 8) steps."""
from bench import trace
from bench.harness import bucket


def read(ctx):
    if ctx.window is None or not ctx.requests:
        return None
    ns, n = trace.module_ns(ctx.trace, "jit_decode_tokens", *ctx.window)
    if n != len(ctx.requests) or ns <= 0:
        return None
    return ns / 1e6 / sum(bucket(r["T"]) for r in ctx.requests)
