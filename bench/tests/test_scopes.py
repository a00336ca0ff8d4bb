"""Self time by named scope (``bench/scopes.py``) and the ``init_cache_idle_ms``
reader, on a small trace whose answers are worked out by hand and on one
request recorded on the chip.

The hand-built trace is in ``bench/trace.py``'s reduced form, times in ms
scaled to ns: one request of 1,024 prompt and 16 output tokens. Serve 0-20;
the eager cache allocation (``minos.init_cache``) 0.5-1.5, with one device
op (its zero fill) 1.2-1.4 in it. Prefill 2-6: the layer loop (while.5,
2-5.5) holds an attn fusion (2.5-4) and an mlp fusion (4-5); the lm_head
fusion 5.5-6. Decode 7-17: the step loop (while.50) holds the layer loop
(while.51, 7.5-15), which holds attn (8-10), the kv_write slice update
(10-10.5), mlp (10.5-13) and a copy of the whole cache with no scope
(13-15); the lm_head fusion (15-16.5) follows in the step loop.
"""
import gzip
import json
import os
import sys
import types

import pytest

from bench import flops, scopes, spec, trace

MS = 1e6
ATTN = "jit(decode_tokens)/while/body/closed_call/while/body/closed_call/attn"
MLP = "jit(decode_tokens)/while/body/closed_call/while/body/closed_call/mlp"
HEAD = "jit(decode_tokens)/while/body/closed_call/lm_head"
OPS = [  # name, start ms, dur ms, op_name, self ms worked out by hand
    ("%broadcast.1 = bf16[8]", 1.2, 0.2, "jit(broadcast_in_dim)/broadcast_in_dim", 0.2),
    ("%while.5 = (s32[])", 2, 3.5, "jit(prefill)/while", 1.0),
    ("%fusion.7 = bf16[8]", 2.5, 1.5, ATTN + "/dot_general", 1.5),
    ("%fusion.8 = bf16[8]", 4, 1, MLP + "/dot_general", 1.0),
    ("%fusion.9 = bf16[8]", 5.5, 0.5, HEAD + "/dot_general", 0.5),
    ("%while.50 = (s32[])", 7, 10, "jit(decode_tokens)/while", 1.0),
    ("%while.51 = (s32[])", 7.5, 7.5, "jit(decode_tokens)/while/body/closed_call/while", 0.5),
    ("%fusion.65 = bf16[8]", 8, 2, ATTN + "/dot_general", 2.0),
    ("%dynamic-update-slice.4 = bf16[8]", 10, 0.5, ATTN + "/kv_write/vmap(vmap())/scatter", 0.5),
    ("%fusion.66 = bf16[8]", 10.5, 2.5, MLP + "/dot_general", 2.5),
    ("%copy.64 = bf16[32,1,32,2048,96]", 13, 2, None, 2.0),
    ("%fusion.67 = bf16[8]", 15, 1.5, HEAD + "/argmax", 1.5),
]
TRACE = {
    "devices": 1,
    "spans": [["bench.serve", 0.0, 20 * MS]],
    "modules": [["jit_prefill(1)", 2 * MS, 4 * MS], ["jit_decode_tokens(2)", 7 * MS, 10 * MS]],
    "ops": [[n, s * MS, d * MS] for n, s, d, _, _ in OPS],
    "host": [["minos.init_cache", 0.5 * MS, 1 * MS], ["PjitFunction(prefill)", 1.8 * MS, 0.1 * MS]],
}
OP_NAMES = {n: o for n, _, _, o, _ in OPS if o is not None}
FIXTURE = os.path.join(os.path.dirname(__file__), "data", "qwen3-docs-one-request.json.gz")


def test_self_time_sweep_by_hand():
    ops = TRACE["ops"]
    assert [t / MS for t in scopes.self_times(ops)] == pytest.approx([o[4] for o in OPS])
    # each nanosecond once: the self times add up to the union of the ops
    union = trace.union([(s, s + d) for _, s, d in ops])
    assert sum(scopes.self_times(ops)) == pytest.approx(sum(b - a for a, b in union))
    # an op that outlasts the one it started in is innermost until it ends
    assert scopes.self_times([["a", 0, 10], ["b", 2, 2], ["c", 3, 2]]) == [7, 1, 2]
    assert scopes.self_times([]) == []


@pytest.mark.parametrize("op_name,want", [
    (ATTN + "/kv_write/vmap(vmap())/scatter", "kv_write"),
    (ATTN + "/dot_general", "attn"),
    (HEAD + "/argmax", "lm_head"),
    ("jit(decode_tokens)/while", ""),
    ("", ""),
])
def test_scope_is_the_innermost_named_scope(op_name, want):
    assert scopes.scope_of(op_name) == want


def test_split_by_hand():
    by_scope, module_ns, n = scopes.split(TRACE, OP_NAMES, "jit_decode_tokens")
    assert (n, module_ns) == (1, 10 * MS)
    assert by_scope == pytest.approx(
        {"": 3.5 * MS, "attn": 2 * MS, "kv_write": 0.5 * MS, "mlp": 2.5 * MS, "lm_head": 1.5 * MS})
    by_scope, module_ns, n = scopes.split(TRACE, OP_NAMES, "jit_prefill")
    assert (n, module_ns) == (1, 4 * MS)
    assert by_scope == pytest.approx({"": 1.0 * MS, "attn": 1.5 * MS, "mlp": 1.0 * MS,
                                      "lm_head": 0.5 * MS})
    # a program without the scopes: all of it unscoped
    assert scopes.split(TRACE, {}, "jit_decode_tokens")[0] == pytest.approx({"": 10 * MS})
    with pytest.raises(ValueError):
        scopes.split({**TRACE, "devices": 2}, OP_NAMES, "jit_prefill")


def _pb(no: int, value) -> bytes:
    """One protobuf field: an int as a varint, bytes or str length-delimited."""
    def varint(n):
        out = b""
        while True:
            out += bytes([(n & 0x7F) | (0x80 if n > 0x7F else 0)])
            n >>= 7
            if not n:
                return out
    if isinstance(value, int):
        return varint(no << 3) + varint(value)
    value = value.encode() if isinstance(value, str) else value
    return varint(no << 3 | 2) + varint(len(value)) + value


def _xspace(plane_name: str, events: dict[int, tuple[str, str | None]],
            lines: dict[str, list[tuple[int, int, int]]] | None = None) -> bytes:
    """An XSpace of one plane whose event metadata carry ``tf_op`` stats,
    with ``lines`` of events (metadata id, start ps, duration ps)."""
    stat_meta = {7: "tf_op", 8: "hlo_category"}
    plane = _pb(2, plane_name)
    for i, (line, evs) in enumerate((lines or {}).items()):
        plane += _pb(3, _pb(1, i) + _pb(2, line) + _pb(3, 0) + b"".join(
            _pb(4, _pb(1, eid) + _pb(2, start) + _pb(3, dur)) for eid, start, dur in evs))
    for sid, name in stat_meta.items():
        plane += _pb(5, _pb(1, sid) + _pb(2, _pb(1, sid) + _pb(2, name)))
    for eid, (name, op_name) in events.items():
        meta = _pb(1, eid) + _pb(2, name) + _pb(5, _pb(1, 8) + _pb(5, "loop fusion"))
        if op_name is not None:
            meta += _pb(5, _pb(1, 7) + _pb(5, op_name + ":"))
        plane += _pb(4, _pb(1, eid) + _pb(2, meta))
    return _pb(1, plane)


def test_op_names_from_the_event_metadata():
    events = {3: ("%fusion.65 = bf16[8]{0} fusion(%p)", ATTN + "/dot_general"),
              4: ("%copy.64 = bf16[8]{0} copy(%p)", None)}
    assert scopes.op_names(_xspace("/device:TPU:0", events)) == {
        "%fusion.65 = bf16[8]{0} fusion(%p)": ATTN + "/dot_general"}
    assert scopes.op_names(_xspace("/host:CPU", events)) == {}


def test_the_split_of_a_profile_file(tmp_path, capsys):
    events = {1: ("jit_decode_tokens(1)", None), 2: ("%while.50 = (s32[])", "jit(decode_tokens)/while"),
              3: ("%fusion.65 = bf16[8]", ATTN + "/dot_general"), 4: ("%copy.64 = bf16[8]", None)}
    lines = {"XLA Modules": [(1, 0, 10_000_000)],  # ps: a 10 us decode executable
             "XLA Ops": [(2, 0, 10_000_000), (3, 1_000_000, 4_000_000), (4, 6_000_000, 3_000_000)]}
    path = tmp_path / "p.xplane.pb.gz"
    with gzip.open(path, "wb") as f:
        f.write(_xspace("/device:TPU:0", events, lines))
    tr, names = scopes.read_profile(str(path))
    assert tr["devices"] == 1 and len(tr["ops"]) == 3 and not list(tmp_path.glob("*.xplane.pb"))
    by_scope, module_ns, n = scopes.split(tr, names, "jit_decode_tokens")
    assert (n, module_ns) == (1, 10_000) and by_scope == {"attn": 4_000, "": 6_000}
    assert scopes.main([str(path), "--steps", "2"]) == 0
    out = capsys.readouterr().out
    assert "jit_decode_tokens: 1 runs, 0.010 ms" in out and "0.0020 ms/step" in out


def test_a_recorded_chip_request():
    """One qwen3-0.6b request of 1,024 prompt and 16 output tokens, recorded
    on a TPU v5 lite, in the form ``--keep-trace`` writes, cut to its
    ``bench.serve`` span, with the op names of its profile."""
    with gzip.open(FIXTURE, "rt") as f:
        rec = json.load(f)
    tr, names = rec["trace"], rec["op_names"]
    assert rec["requests"] == [{"S": 1024, "T": 16}]
    mods = [(s, s + d) for n, s, d in tr["modules"] if n.startswith("jit_decode_tokens")]
    inside = [i for i, (_, s, _) in enumerate(tr["ops"]) if any(a <= s < b for a, b in mods)]
    self_ns = scopes.self_times(tr["ops"])
    by_scope, module_ns, n = scopes.split(tr, names, "jit_decode_tokens")
    assert n == 1 and set(scopes.SCOPES) <= set(by_scope)
    # the four scopes and the unscoped rest hold the decode ops' self time
    assert sum(by_scope.values()) == pytest.approx(sum(self_ns[i] for i in inside), rel=1e-12)
    # and that is the executable's time, less its idle inside
    assert 0.99 * module_ns <= sum(by_scope.values()) <= module_ns
    by_prefill, _, _ = scopes.split(tr, names, "jit_prefill")
    assert {"attn", "mlp", "lm_head"} <= set(by_prefill) and "kv_write" not in by_prefill


# --- init_cache_idle_ms ------------------------------------------------------


def _ctx(tr, requests):
    bench = spec.Bench(spec.ROOT)
    cfg = json.load(open(os.path.join(bench.dir, "configs", "qwen3-0.6b.json")))
    sizes = bench.adapter(cfg["family"]).sizes(cfg)
    return bench, types.SimpleNamespace(
        trace=tr, window=trace.window(tr), requests=requests, sizes=sizes,
        peak=bench.peaks()["TPU v5 lite"], counts=flops.for_config(cfg["family"], sizes))


def test_init_cache_idle_by_hand():
    bench, ctx = _ctx(TRACE, [{"S": 1024, "T": 16}])
    reader = bench.metric_reader("init_cache_idle_ms")
    assert reader.read(ctx) == pytest.approx(1.0 - 0.2)
    # per traced request
    _, ctx2 = _ctx(TRACE, [{"S": 1024, "T": 16}] * 2)
    assert reader.read(ctx2) == pytest.approx((1.0 - 0.2) / 2)
    # the program before the span, and a trace with no device ops, read nothing
    for tr in ({**TRACE, "host": TRACE["host"][1:]}, {**TRACE, "ops": [], "modules": []}):
        assert reader.read(_ctx(tr, [{"S": 1024, "T": 16}])[1]) is None


def test_init_cache_idle_on_the_recorded_request():
    with gzip.open(FIXTURE, "rt") as f:
        rec = json.load(f)
    bench, ctx = _ctx(rec["trace"], rec["requests"])
    (span,) = [h for h in rec["trace"]["host"] if h[0] == "minos.init_cache"]
    idle_ms = bench.metric_reader("init_cache_idle_ms").read(ctx)
    assert 0 < idle_ms <= span[2] / 1e6


def test_load_keeps_the_program_cache_span(tmp_path):
    """The program's ``minos.init_cache`` span lands in ``host``, on the
    thread that holds the benchmark's span, where its reader looks."""
    import jax

    sys.path.insert(0, os.path.join(spec.ROOT, "src"))
    from repro.configs.registry import get_smoke_config
    from repro.models.model import build_model

    model = build_model(get_smoke_config("qwen3-0.6b"))
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.serve"):
        jax.block_until_ready(model.init_cache(1, 16))
    jax.profiler.stop_trace()
    tr = trace.load(str(tmp_path))
    (span,) = tr["spans"]
    inner = [h for h in tr["host"] if h[0] == "minos.init_cache"]
    assert len(inner) == 1 and span[1] <= inner[0][1] <= span[1] + span[2]
