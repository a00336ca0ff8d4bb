#!/usr/bin/env python3
"""Device self time by the program's named scopes, from a raw profile.

    python3 bench/scopes.py <.xplane.pb or .xplane.pb.gz> [--steps N] [--ktok K]

The program names its layers with ``jax.named_scope`` (``SCOPES``); XLA
keeps the scope path in each instruction's ``op_name``. On a TPU the "XLA
Ops" event's name is the instruction's text without its metadata, and the
``op_name`` is the ``tf_op`` stat of the event's metadata, which
``jax.profiler.ProfileData`` does not expose (an event's ``stats`` are its
own). So ``op_names`` reads the metadata from the XSpace protobuf itself.

Self time is the time in which an operation is the innermost one running on
its device: a ``while`` op spans its body's operations and keeps only what
they leave uncovered, so each nanosecond counts once.

Prints, for the prefill and the decode executable, their device time and
the self time of each scope and of the unscoped rest (XLA's copies, loop
bookkeeping, the embedding gather); with ``--steps`` (decode scan steps
run) and ``--ktok`` (thousands of prompt tokens) also per step and per
thousand tokens. ``bench/trace.py``'s reduced trace keeps no ``op_name``,
so a run's per-layer metrics cannot read this split.
"""
from __future__ import annotations

import argparse
import gzip
import os
import shutil
import sys
import tempfile

SCOPES = ("kv_write", "attn", "mlp", "lm_head")


def scope_of(op_name: str) -> str:
    """The innermost of ``SCOPES`` in an ``op_name`` path, or ""."""
    for part in reversed(op_name.split("/")):
        if part in SCOPES:
            return part
    return ""


def self_times(ops: list[list]) -> list[float]:
    """Self time of each ``[name, start, dur]`` of one device: the time in
    which it is the innermost operation running, the one started last."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][1], -ops[i][2]))
    out = [0.0] * len(ops)
    stack: list[int] = []  # running operations, by start
    t = ops[order[0]][1] if ops else 0.0

    def advance(to: float) -> None:
        nonlocal t
        while stack and t < to:
            end = ops[stack[-1]][1] + ops[stack[-1]][2]
            if end <= t:
                stack.pop()
                continue
            step = min(to, end)
            out[stack[-1]] += step - t
            t = step
        t = max(t, to)

    for i in order:
        advance(ops[i][1])
        stack.append(i)
    advance(float("inf"))
    return out


def split(tr: dict, op_names: dict[str, str], prefix: str) -> tuple[dict[str, float], float, int]:
    """Self time (ns) by scope of the device operations that start inside an
    executable named ``prefix…``, those executables' device time and their
    count, over the whole of ``tr`` (``bench/trace.py``'s form, one device)."""
    if tr["devices"] != 1:
        raise ValueError(f"one device's operations expected, the trace has {tr['devices']}")
    mods = sorted((s, s + d) for name, s, d in tr["modules"] if name.startswith(prefix))
    tot: dict[str, float] = {}
    i = 0
    for (name, s, _), t in sorted(zip(tr["ops"], self_times(tr["ops"])), key=lambda e: e[0][1]):
        while i < len(mods) and mods[i][1] <= s:
            i += 1
        if i < len(mods) and mods[i][0] <= s:
            scope = scope_of(op_names.get(name, ""))
            tot[scope] = tot.get(scope, 0.0) + t
    return tot, sum(b - a for a, b in mods), len(mods)


# --- the XSpace protobuf, read by its wire format ----------------------------


def _varint(buf: bytes, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return out, i


def _fields(buf: bytes):
    """(field number, value) of one message: an int, or the bytes of a
    length-delimited or fixed-size field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif kind in (1, 5):
            size = 8 if kind == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {kind} at byte {i}")
        yield key >> 3, value


def op_names(xspace: bytes) -> dict[str, str]:
    """Event name -> ``op_name``, from the ``tf_op`` stat of the event
    metadata of the device planes of a serialized XSpace (xplane.proto:
    XSpace.planes 1; XPlane.name 2, event_metadata 4, stat_metadata 5, each
    map entry's value 2; XEventMetadata.name 2, stats 5; XStatMetadata.name
    2; XStat.metadata_id 1, str_value 5)."""
    out: dict[str, str] = {}
    for no, plane in _fields(xspace):
        if no != 1:
            continue
        fields = list(_fields(plane))
        if not any(f == 2 and v.startswith(b"/device:") for f, v in fields):
            continue
        tf_op = None
        for f, entry in fields:
            if f == 5:
                e = dict(_fields(entry))
                if dict(_fields(e.get(2, b""))).get(2) == b"tf_op":
                    tf_op = e.get(1)
        if tf_op is None:
            continue
        for f, entry in fields:
            if f != 4:
                continue
            name, op = "", None
            for g, v in _fields(dict(_fields(entry)).get(2, b"")):
                if g == 2:
                    name = v.decode("utf-8", "replace")
                elif g == 5:
                    stat = dict(_fields(v))
                    if stat.get(1) == tf_op and 5 in stat:
                        op = stat[5].decode("utf-8", "replace").rstrip(":")
            if op is not None:
                out[name] = op
    return out


def read_profile(path: str) -> tuple[dict, dict[str, str]]:
    """``bench/trace.py``'s reduced trace of one profile file, and its op
    names."""
    from bench import trace

    with (gzip.open(path) if path.endswith(".gz") else open(path, "rb")) as f:
        raw = f.read()
    tmp = tempfile.mkdtemp()
    try:
        with open(os.path.join(tmp, "profile.xplane.pb"), "wb") as f:
            f.write(raw)
        tr = trace.load(tmp)
    finally:
        shutil.rmtree(tmp)
    return tr, op_names(raw)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="Device self time by named scope.")
    ap.add_argument("profile", help="an .xplane.pb, or an .xplane.pb.gz")
    ap.add_argument("--steps", type=float, default=None, help="decode scan steps run")
    ap.add_argument("--ktok", type=float, default=None, help="thousands of prompt tokens")
    args = ap.parse_args(argv)
    tr, names = read_profile(args.profile)
    for prefix, per, base in (("jit_prefill", "ktok", args.ktok),
                              ("jit_decode_tokens", "step", args.steps)):
        by_scope, module_ns, n = split(tr, names, prefix)
        total = sum(by_scope.values())
        print(f"{prefix}: {n} runs, {module_ns / 1e6:.3f} ms on the device, "
              f"{total / 1e6:.3f} ms of op self time")
        for scope in SCOPES + ("",):
            ns = by_scope.get(scope, 0.0)
            line = f"  {scope or '(unscoped)':11s} {ns / 1e6:10.3f} ms {100 * ns / max(total, 1):6.2f} %"
            if base:
                line += f" {ns / 1e6 / base:9.4f} ms/{per}"
            print(line)
    return 0


if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    sys.exit(main())
