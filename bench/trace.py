"""From a JAX profiler trace to the few event lists the metrics read.

``load(dir)`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote and keeps,
on one clock in nanoseconds:

    ops      [name, start, dur]   device operations (the "XLA Ops" lines)
    modules  [name, start, dur]   device executables ("XLA Modules" lines),
                                  named as jitted: ``jit_prefill(…)``
    spans    [name, start, dur]   the benchmark's own host spans
    host     [name, start, dur]   everything else on the thread that holds
                                  the benchmark's spans

and the number of device planes that ran programs. The metrics work on
that dict alone, so a small one built by hand (``tests/test_trace.py``)
checks the reduction without a chip.
"""
from __future__ import annotations

import glob
import os

SPAN_PREFIX = "bench."


def _events(line) -> list[list]:
    return [[e.name, float(e.start_ns), float(e.duration_ns)] for e in line.events]


def load(trace_dir: str) -> dict:
    import jax

    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = jax.profiler.ProfileData.from_file(files[-1])
    out = {"ops": [], "modules": [], "spans": [], "host": [], "devices": 0}
    for plane in data.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            lines = {line.name: line for line in plane.lines}
            if "XLA Ops" not in lines:  # not a core that runs programs
                continue
            out["devices"] += 1
            out["ops"] += _events(lines["XLA Ops"])
            if "XLA Modules" in lines:
                out["modules"] += _events(lines["XLA Modules"])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                evs = _events(line)
                if any(e[0].startswith(SPAN_PREFIX) for e in evs):
                    out["spans"] += [e for e in evs if e[0].startswith(SPAN_PREFIX)]
                    out["host"] += [e for e in evs if not e[0].startswith(SPAN_PREFIX)]
    for k in ("ops", "modules", "spans", "host"):
        out[k].sort(key=lambda e: e[1])
    return out


def window(tr: dict) -> tuple[float, float] | None:
    """From the first benchmark span's start to the last one's end."""
    if not tr["spans"]:
        return None
    return tr["spans"][0][1], max(s[1] + s[2] for s in tr["spans"])


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy(tr: dict, lo: float, hi: float) -> list[tuple[float, float]]:
    """Intervals in [lo, hi] in which some device operation ran (the union
    over the device planes, so the time of one chip when there is one)."""
    iv = [(max(s, lo), min(s + d, hi)) for _, s, d in tr["ops"] if s < hi and s + d > lo]
    return union([(a, b) for a, b in iv if b > a])


def busy_ns(tr: dict, lo: float, hi: float) -> float:
    return sum(b - a for a, b in busy(tr, lo, hi))


def module_ns(tr: dict, prefix: str, lo: float, hi: float) -> tuple[float, int]:
    """Summed device time and count of executables named ``prefix…`` that
    start in [lo, hi]."""
    ev = [e for e in tr["modules"] if e[0].startswith(prefix) and lo <= e[1] < hi]
    return sum(e[2] for e in ev) / max(tr["devices"], 1), len(ev) // max(tr["devices"], 1)


def top_ops(tr: dict, lo: float, hi: float, n: int = 10) -> list[list]:
    """The ``n`` device operations that took most time, by name, seconds."""
    tot: dict[str, float] = {}
    for name, s, d in tr["ops"]:
        if lo <= s < hi:
            tot[name] = tot.get(name, 0.0) + d
    top = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9] for k, v in top]


def idle_gaps(tr: dict, lo: float, hi: float, n: int = 10) -> list[list]:
    """The ``n`` longest device-idle gaps in [lo, hi], each named by what the
    host thread was doing at its middle: the innermost host event there, under
    the benchmark span that holds it."""
    b = busy(tr, lo, hi)
    edges = [lo] + [x for iv in b for x in iv] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2) if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for a, z in gaps[:n]:
        mid = (a + z) / 2
        span = [s[0] for s in tr["spans"] if s[1] <= mid < s[1] + s[2]]
        inner = [h for h in tr["host"] if h[1] <= mid < h[1] + h[2]]
        label = span[-1] if span else "outside benchmark spans"
        if inner:
            label += " > " + min(inner, key=lambda h: h[2])[0]
        out.append([label, (z - a) / 1e9])
    return out
