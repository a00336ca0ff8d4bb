"""Benchmark harness: one entry per paper figure/table + kernel micro +
the vectorized grid sweep. Prints
``name,us_per_call,derived`` CSV rows per the repo convention, then
detailed per-figure tables.

Every run also persists machine-readable timings to
``benchmarks/BENCH_substrate.json`` (per-sweep wall-clock, plus the grid
sweep's events/sec + arms/sec), so the repo carries a perf trajectory
across PRs; when a previous file exists a one-line delta is printed.

Usage: PYTHONPATH=src python -m benchmarks.run [--quick] [--only fig4,...]
"""
import argparse
import json
import os
import sys
import time

_BENCH_DIR = os.path.dirname(os.path.abspath(__file__))


def _sanitized() -> bool:
    return os.environ.get("REPRO_SANITIZE", "") not in ("", "0")


def _bench_json_path(quick: bool) -> str:
    """Quick runs use shorter windows, so their wall-clocks are not
    comparable to full runs — each mode keeps its own baseline file (the
    committed perf trajectory is the full one). REPRO_SANITIZE runs get a
    third file: their wall-clocks carry the sanitizer's checking overhead,
    and the delta against the matching plain file IS the overhead
    measurement (target <=2x, DESIGN.md §13)."""
    name = "BENCH_substrate.quick.json" if quick else "BENCH_substrate.json"
    if _sanitized():
        name = name.replace(".json", ".sanitize.json")
    return os.path.join(_BENCH_DIR, name)


def _print_sanitize_overhead(quick: bool, cur: dict) -> None:
    """Compare a sanitized run to the matching plain baseline file."""
    plain_name = ("BENCH_substrate.quick.json" if quick
                  else "BENCH_substrate.json")
    plain = _load_previous(os.path.join(_BENCH_DIR, plain_name))
    plain_r, cur_r = plain.get("results", {}), cur.get("results", {})
    common = [n for n in cur_r if n in plain_r
              and plain_r[n].get("wall_clock_s", 0) > 0]
    if not common:
        return
    base = sum(plain_r[n]["wall_clock_s"] for n in common)
    san = sum(cur_r[n]["wall_clock_s"] for n in common)
    print(f"SANITIZE overhead vs {plain_name} ({len(common)} sweeps): "
          f"{base:.1f}s->{san:.1f}s ({san / base:.2f}x)")


def _load_previous(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError):
        return {}


def _print_delta(prev: dict, cur: dict) -> None:
    """One line comparing this run to the previous BENCH_substrate.json."""
    prev_r, cur_r = prev.get("results", {}), cur.get("results", {})
    common = [n for n in cur_r if n in prev_r]
    if not common:
        return
    old = sum(prev_r[n]["wall_clock_s"] for n in common)
    new = sum(cur_r[n]["wall_clock_s"] for n in common)
    parts = [f"total {old:.1f}s->{new:.1f}s ({(new - old) / old * 100:+.0f}%)"
             if old > 0 else f"total {new:.1f}s"]
    for sweep, short in (("grid_sweep", "grid"),
                         ("loadaware_sweep", "loadaware"),
                         ("vec_admission_sweep", "vec-admission")):
        g_old = prev_r.get(sweep, {}).get("events_per_sec")
        g_new = cur_r.get(sweep, {}).get("events_per_sec")
        if g_old and g_new:
            parts.append(f"{short} {g_old:.0f}->{g_new:.0f} events/s "
                         f"({(g_new - g_old) / g_old * 100:+.0f}%)")
        elif g_new:
            parts.append(f"{short} {g_new:.0f} events/s (new)")
    print(f"BENCH delta vs previous ({len(common)} sweeps): "
          + ", ".join(parts))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", help="shorter sim windows")
    ap.add_argument("--only", default="", help="comma-separated benchmark names")
    ap.add_argument("--no-bench-json", action="store_true",
                    help="skip writing benchmarks/BENCH_substrate.json")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    from . import (diurnal_sweep, fault_sweep, figs, fleet_sweep,
                   grid_sweep, kernels_micro, openloop_sweep,
                   pipeline_sweep, workflow_sweep)

    benches = {
        "workflow_sweep": workflow_sweep.workflow_sweep,
        "pipeline_sweep": pipeline_sweep.pipeline_sweep,
        "diurnal_sweep": diurnal_sweep.diurnal_sweep,
        # control-plane arms (DESIGN.md §10): rows carry a `decisions`
        # column naming which controller handled each decision point
        "diurnal_controllers": diurnal_sweep.controller_sweep,
        "pipeline_admission": pipeline_sweep.admission_sweep,
        # vectorized Monte-Carlo fast path (DESIGN.md §11)
        "grid_sweep": grid_sweep.grid_sweep,
        # n-streams-per-lane slot pool: concurrency-4 load**alpha arms on
        # the scan (ISSUE 7; DESIGN.md §11)
        "loadaware_sweep": grid_sweep.loadaware_sweep,
        # open-loop arrival traffic: rate × burstiness × gate (DESIGN.md §12)
        "openloop_sweep": openloop_sweep.openloop_sweep,
        # in-scan admission pipeline: defer/drop arms on the open scan
        "vec_admission_sweep": openloop_sweep.vec_admission_sweep,
        # fleet meta-scheduler: routing policies over heterogeneous
        # Minos-gated fleets on one clock (DESIGN.md §14)
        "fleet_sweep": fleet_sweep.fleet_sweep,
        # fault-injection ladder × recovery ladder × gate on/off: crash
        # misattribution + retry-storm questions (DESIGN.md §15)
        "fault_sweep": fault_sweep.fault_sweep,
        "fig4_regression_duration": figs.fig4_regression_duration,
        "fig5_successful_requests": figs.fig5_successful_requests,
        "fig6_cost_per_day": figs.fig6_cost_per_day,
        "fig7_cost_over_time": figs.fig7_cost_over_time,
        "ablation_pass_fraction": figs.ablation_pass_fraction,
        "ablation_stale_threshold": figs.ablation_stale_threshold,
        "ablation_online_controller": figs.ablation_online_controller,
        "kernel_micro": kernels_micro.kernel_micro,
    }
    selected = [s for s in args.only.split(",") if s] or list(benches)
    unknown = [s for s in selected if s not in benches]
    if unknown:
        sys.exit(f"unknown benchmark(s): {', '.join(unknown)}; "
                 f"available: {', '.join(benches)}")

    print("name,us_per_call,derived")
    details = []
    bench_results = {}
    failures = 0
    for name in selected:
        fn = benches[name]
        t0 = time.perf_counter()
        try:
            rows, headline, *extra = fn(quick=args.quick)
            wall = time.perf_counter() - t0
            print(f"{name},{wall * 1e6:.0f},{headline}")
            details.append((name, rows))
            record = {"wall_clock_s": round(wall, 3), "headline": headline}
            if extra and isinstance(extra[0], dict):
                record.update(extra[0])  # grid_sweep perf numbers
            bench_results[name] = record
        except Exception as e:  # pragma: no cover
            failures += 1
            print(f"{name},0,FAILED:{type(e).__name__}:{e}")
    for name, rows in details:
        print(f"\n== {name} ==")
        if rows:
            cols = list(rows[0].keys())
            print(",".join(cols))
            for r in rows:
                print(",".join(str(r[c]) for c in cols))

    if bench_results and not args.no_bench_json:
        path = _bench_json_path(args.quick)
        prev = _load_previous(path)
        cur = {
            "schema": 1,
            "quick": bool(args.quick),
            "sanitized": _sanitized(),
            "results": bench_results,
        }
        _print_delta(prev, cur)
        if _sanitized():
            _print_sanitize_overhead(args.quick, cur)
        # merge: a --only (or partially failed) run must not wipe the
        # baselines of sweeps it did not execute
        merged = dict(prev.get("results", {}))
        merged.update(bench_results)
        cur["results"] = merged
        with open(path, "w") as f:
            json.dump(cur, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"wrote {path}")
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
