"""One run of one benchmark cell: set-up, a measured window, the check.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Set-up (``setup_s``, from the start of ``bench/run.py``): name the device
(anything but a chip in ``peaks.json`` ends the run), make the weights on the
device from ``--seed``, build the program's model, and serve one request of
every shape the traffic mix can send, so every program the window runs is
compiled (or read from the persistent cache under ``<checkout>/.jax_cache``).

Window: the backlog is served in order, one request at a time, through the
program's model API (``serve_model``: cache, ``prefill_jit``, the first
token from the prefill, ``decode_tokens``), until the first request that
ends at or after ``--seconds``; the window ends with it. Any compilation
inside the window is counted and fails the run.

With ``--trace 1`` a profiler trace covers a steady stretch of the window,
from one request boundary to another, and the per-layer metrics
(``bench/metrics/<name>.py``) are read from it.

Check, after the window, the memory reading and freeing the program's
model: a sample of the answered requests drawn from the seed, with the
longest in it, runs once through the float32 reference
(``bench/reference/<family>.py``) over its prompt and served tokens; the
widest gap by which a served token's logit lies below the reference's best is
held to the cell's limit (``bench/limits/<workload>.json``), beside the
counts. The numbers compared, each with its limit, are the last lines of
stderr and the last key of the result line, which is the last line of stdout.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import shutil
import sys
import time
import types

import numpy as np

from bench import flops, spec, trace, traffic

SAMPLE_POOL = 40        # the check draws its sample among this many first answers
TRACE_LEAD = 0.2        # share of the window before the traced stretch starts
TRACE_SPAN = (2.0, 6.0)  # traced stretch: 20% of the window, within these seconds
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/core/compile/jaxpr_trace_duration",
                  "/jax/compilation_cache/cache_hits",
                  "/jax/compilation_cache/cache_misses")


class RunError(RuntimeError):
    """The run cannot give a result (no chip, missing file, bad spec)."""


def log(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# set-up
# ---------------------------------------------------------------------------


def device_info(bench: spec.Bench, chips: int, *, allow_cpu: bool) -> tuple[dict, dict]:
    import jax

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    log(f"[device] platform={info['platform']} kind={info['kind']} count={info['count']}")
    peaks = bench.peaks()
    if info["platform"] != "tpu" and not allow_cpu:
        raise RunError(f"needs a TPU, found {info['platform']!r}")
    if info["kind"] not in peaks and not allow_cpu:
        raise RunError(f"device kind {info['kind']!r} is not in bench/peaks.json")
    if info["count"] < chips:
        raise RunError(f"the cell needs {chips} chips, found {info['count']}")
    # a CPU run (tests only) borrows the first chip's peaks
    return info, peaks.get(info["kind"], next(iter(peaks.values())))


def enable_cache(root: str) -> str:
    import jax

    path = os.path.join(root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def key_from_seed(seed: int):
    import jax

    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF), (seed >> 32) & 0x7FFFFFFF)


class CompileCounter:
    """Counts JAX's compile and trace events while armed."""

    def __init__(self):
        import jax.monitoring as mon

        self.armed = False
        self.events: list[str] = []
        mon.register_event_listener(self._event)
        mon.register_event_duration_secs_listener(self._duration)

    def _event(self, name, **_):
        if self.armed and name in COMPILE_EVENTS:
            self.events.append(name)

    def _duration(self, name, _secs, **_):
        self._event(name)


# ---------------------------------------------------------------------------
# window
# ---------------------------------------------------------------------------


def bucket(n: int, base: int = 8) -> int:
    """``n`` rounded up to a power of two, at least ``base``: the serving
    backend's bucket for decode steps and cache length."""
    b = base
    while b < n:
        b <<= 1
    return b


def serve_model(model, params, prompt: np.ndarray, T: int) -> np.ndarray:
    """T greedy tokens for ``prompt`` through the program's model API: cache,
    ``prefill_jit``, the first token from the prefill's logits, then
    ``decode_tokens`` from it. Shapes are bucketed as the serving backend
    buckets them (decode steps and cache length to powers of two)."""
    import jax.numpy as jnp

    from repro.models.model import greedy_token

    S, Tb = len(prompt), bucket(T)
    cache = model.init_cache(1, bucket(S + Tb))
    logits, cache = model.prefill_jit(params, {"tokens": jnp.asarray(prompt)[None]}, cache)
    first = greedy_token(logits)
    toks, _ = model.decode_tokens(params, cache, first, Tb)
    return np.concatenate([np.asarray(first)[0], np.asarray(toks)[0, :T - 1]]).astype(np.int32)


def serve_one(model, params, req: traffic.Request, rec: dict) -> None:
    try:
        toks = serve_model(model, params, req.prompt, req.max_new_tokens)
        rec["tokens"] = toks
        rec["ok"] = len(toks) == req.max_new_tokens
        if not rec["ok"]:
            rec["error"] = f"{len(toks)} tokens for {req.max_new_tokens}"
    except Exception as e:  # a request that fails is counted, not fatal
        rec["ok"] = False
        rec["error"] = repr(e)
        log(f"[window] request {req.index} failed: {e!r}")


class Tracer:
    """Profiler trace over one stretch of the window, request to request."""

    def __init__(self, on: bool, seconds: float, out_dir: str):
        import jax

        self.jax = jax
        self.on = on
        lead = TRACE_LEAD * seconds
        self.start_at = lead
        self.stop_at = lead + min(max(TRACE_SPAN[0], 0.2 * seconds), TRACE_SPAN[1])
        self.dir = out_dir
        self.state = "before"
        self.traced: list[dict] = []

    def boundary(self, elapsed: float) -> None:
        """Called between requests, with seconds since the window opened."""
        if not self.on:
            return
        if self.state == "before" and elapsed >= self.start_at:
            shutil.rmtree(self.dir, ignore_errors=True)
            self.jax.profiler.start_trace(self.dir)
            self.state = "tracing"
        elif self.state == "tracing" and elapsed >= self.stop_at:
            self.stop()

    def stop(self) -> None:
        if self.state == "tracing":
            self.jax.profiler.stop_trace()
            self.state = "done"

    def note(self, rec: dict) -> None:
        if self.state == "tracing":
            self.traced.append(rec)

    def span(self, name: str):
        return self.jax.profiler.TraceAnnotation("bench." + name)


def run_window(model, params, reqs: list[traffic.Request], *, seconds: float,
               tracer: Tracer) -> dict:
    recs = []
    t_open = time.perf_counter()
    for req in reqs:
        now = time.perf_counter() - t_open
        if now >= seconds and recs:
            break
        tracer.boundary(now)
        rec = {"index": req.index, "S": req.prompt_len, "T": req.max_new_tokens,
               "prompt": req.prompt}
        with tracer.span("serve"):
            serve_one(model, params, req, rec)
        rec["end"] = time.perf_counter() - t_open
        tracer.note(rec)
        recs.append(rec)
    tracer.stop()
    if recs and recs[-1]["end"] < seconds:
        raise RunError(f"the backlog of {len(reqs)} requests ran out before {seconds} s")
    return {"records": recs, "window_s": recs[-1]["end"] if recs else 0.0}


# ---------------------------------------------------------------------------
# check
# ---------------------------------------------------------------------------


def sample_for_check(recs: list[dict], seed: int, tokens: int) -> list[dict]:
    """The longest answered request, then others drawn from the seed among
    the first SAMPLE_POOL answered, until ``tokens`` served tokens are
    covered. Drawing from a fixed prefix keeps the sample of a seed the same
    however many requests a window answers beyond it."""
    ok = [r for r in recs if r.get("ok")]
    if not ok:
        return []
    longest = max(ok, key=lambda r: (r["S"] + r["T"], -r["index"]))
    rest = [r for r in ok[:SAMPLE_POOL] if r is not longest]
    order = traffic._rng(seed, 0xC4).permutation(len(rest))
    out, n = [longest], longest["T"]
    for i in order:
        if n >= tokens:
            break
        out.append(rest[i])
        n += rest[i]["T"]
    return out


def check(bench, cfg, sizes, W, run: dict, limits: dict, seed: int, compiles: int, *,
          control: bool = False) -> dict:
    """The numbers compared, each with its limit. With ``control`` the gap
    read is that of the token the reference computed in float8 puts first:
    the control, which has to come out not correct."""
    ref = bench.reference(cfg["family"])
    recs = run["records"]
    picked = sample_for_check(recs, seed, limits["sample_tokens"])
    t0 = time.perf_counter()
    gaps = [float(np.max(ref.gaps(W, sizes, r["prompt"], r["tokens"], control=control)))
            for r in picked]
    n_tok = sum(r["T"] for r in picked)
    log(f"[check] {'control' if control else 'reference'} over {len(picked)} requests, "
        f"{n_tok} served tokens, {time.perf_counter() - t0:.2f} s")
    return {
        "served_gap_per_std": {"value": max(gaps) if gaps else None,
                               "limit": limits["served_gap_per_std"]},
        "tokens_checked": {"value": n_tok, "limit": limits["sample_tokens"], "at_least": True},
        "failed_requests": {"value": sum(1 for r in recs if not r.get("ok")), "limit": 0},
        "compiles_in_window": {"value": compiles, "limit": 0},
    }


def passes(checks: dict) -> bool:
    for c in checks.values():
        if c["value"] is None:
            return False
        ok = c["value"] >= c["limit"] if c.get("at_least") else c["value"] <= c["limit"]
        if not ok:
            return False
    return True


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------


def read_metrics(bench, names: list[dict], arg) -> dict:
    out = {}
    for m in names:
        v = bench.metric_reader(m["name"]).read(arg) if "moves" in m else \
            bench.e2e_reader(m["name"]).read(arg)
        if v is None:
            log(f"[metrics] {m['name']}: nothing to read")
            continue
        out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------


def setup(workload: str, seed: int, *, root: str = spec.ROOT, allow_cpu: bool = False,
          fault=None) -> types.SimpleNamespace:
    """Everything before the window: device, weights, model, warm-up.
    ``fault`` (tests) takes the program's model and returns a broken one."""
    bench = spec.Bench(root)
    cell = bench.workload(workload)
    cfg = bench.config(cell["config"])
    mix = bench.traffic(cell["traffic"])
    limits = bench.limits(workload)
    device, peak = device_info(bench, cell["chips"], allow_cpu=allow_cpu)
    cache_dir = enable_cache(root)
    sys.path.insert(0, os.path.join(root, "src"))
    import jax

    from repro.configs.registry import get_config
    from repro.models.model import build_model

    adapter = bench.adapter(cfg["family"])
    sizes = adapter.sizes(cfg)
    pcfg = adapter.program_config(cfg, get_config(cfg["arch_id"]))
    W = adapter.make_weights(cfg, key_from_seed(seed), dtype=pcfg.jax_dtype)
    jax.block_until_ready(W)
    model, params = build_model(pcfg), adapter.to_program(W)
    if fault is not None:
        model = fault(model)
    t_w = time.perf_counter()
    for r in traffic.warmup(mix, seed=seed, vocab=sizes["V"]):
        serve_one(model, params, r, {})
    log(f"[setup] weights, model and {len(traffic.shapes(mix))} shapes warmed "
        f"({time.perf_counter() - t_w:.2f} s); compile cache {cache_dir}")
    return types.SimpleNamespace(bench=bench, cell=cell, cfg=cfg, mix=mix, limits=limits,
                                 device=device, peak=peak, sizes=sizes, W=W, model=model,
                                 params=params, root=root)


def run_cell(workload: str, seed: int, seconds: float, trace_on: bool, *, t0: float,
             root: str = spec.ROOT, allow_cpu: bool = False, fault=None,
             keep_trace: str | None = None, control: bool = False) -> dict:
    import jax

    c = setup(workload, seed, root=root, allow_cpu=allow_cpu, fault=fault)
    bench, cfg, sizes, W, device = c.bench, c.cfg, c.sizes, c.W, c.device
    reqs = traffic.schedule(c.mix, seed=seed, vocab=sizes["V"])
    counter = CompileCounter()
    tracer = Tracer(trace_on, seconds, os.path.join(c.root, ".bench_trace"))
    counter.armed = True
    setup_s = time.perf_counter() - t0
    run = run_window(c.model, c.params, reqs, seconds=seconds, tracer=tracer)
    counter.armed = False
    run["setup_s"] = setup_s
    stats = jax.devices()[0].memory_stats() or {}
    device["memory_peak_bytes"] = int(stats.get("peak_bytes_in_use", 0))
    recs = run["records"]
    log(f"[window] {len(recs)} requests, {sum(r['T'] for r in recs)} tokens "
        f"in {run['window_s']:.3f} s")
    if counter.events:
        log(f"[window] compile events inside the window: {counter.events}")
    del c.model, c.params
    gc.collect()

    result = {"correct": False, "attempted": len(recs),
              "failed": sum(1 for r in recs if not r.get("ok"))}
    if trace_on:
        tr = trace.load(tracer.dir)
        shutil.rmtree(tracer.dir, ignore_errors=True)
        if keep_trace:
            with open(keep_trace, "w") as f:
                json.dump({"trace": tr, "requests": [{"S": r["S"], "T": r["T"]}
                                                     for r in tracer.traced]}, f)
        ctx = types.SimpleNamespace(trace=tr, window=trace.window(tr), requests=tracer.traced,
                                    sizes=sizes, peak=c.peak,
                                    counts=flops.for_config(cfg["family"], sizes))
        result["metrics"] = read_metrics(bench, bench.per_layer(workload), ctx)
        lo, hi = ctx.window
        device["busy_s"] = trace.busy_ns(tr, lo, hi) / 1e9
        device["window_s"] = (hi - lo) / 1e9
        result["device"] = device
        result["breakdown"] = {"device_ops": trace.top_ops(tr, lo, hi),
                               "idle_gaps": trace.idle_gaps(tr, lo, hi)}
    else:
        result["metrics"] = read_metrics(bench, bench.end_to_end(workload), run)
        result["device"] = device
    checks = check(bench, cfg, sizes, W, run, c.limits, seed, len(counter.events))
    if control:  # the same requests and served tokens, judged by the control's picks
        result["program_checks"] = checks
        checks = check(bench, cfg, sizes, W, run, c.limits, seed, len(counter.events),
                       control=True)
    result["correct"] = passes(checks)
    result["checks"] = checks
    return result


def report(res: dict) -> None:
    """The numbers compared, each beside its limit, as the last lines of
    stderr; then the result as the last line of stdout."""
    for name, c in res["checks"].items():
        rel = ">=" if c.get("at_least") else "<="
        log(f"[check] {name} {c['value']} (limit {rel} {c['limit']})")
    print(json.dumps(res), flush=True)


def main(argv=None, *, t0: float) -> int:
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="write the reduced trace of a --trace 1 run to this JSON file")
    args = ap.parse_args(argv)
    try:
        res = run_cell(args.workload, args.seed, args.seconds, bool(args.trace), t0=t0,
                       keep_trace=args.keep_trace)
    except (RunError, spec.SpecError) as e:
        log(f"[error] {e}")
        return 3
    report(res)
    return 0
