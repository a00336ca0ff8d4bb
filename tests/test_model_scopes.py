"""The named scopes of the serving executables, which the benchmark's trace
reduction reads from each device op's HLO ``op_name`` to split device time
into attention, KV-cache write, MLP and LM head (``bench/trace.py``).

Inside them, each layer's attention names its kind ("window" or "full")
and a MoE names its routing, expert products and combine.

At smoke size on the CPU, for two dense families and two MoE, one of them
with window and full layers. The compiled
CPU text drops the metadata of some batched dots (a backend rewrite makes
new instructions), so every matmul is checked in the lowered HLO, and the
ones the compiled text still names are checked there too.
"""
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs.registry import get_smoke_config
from repro.models.model import build_model

ARCHS = ["qwen3-0.6b", "phi3-mini-3.8b", "granite-moe-1b-a400m", "mellum2-12b-a2.5b"]
LAYERS = ("attn", "mlp", "lm_head")
KINDS = ("window", "full")                              # inside "attn"
MOE = ("moe_route", "moe_experts", "moe_combine")       # inside "mlp"
MATMUL = re.compile(r"= \S+ (?:dot|convolution)\(")
OP_NAME = re.compile(r'op_name="([^"]*)"')


@pytest.fixture(scope="module", params=ARCHS)
def lowered(request):
    """(arch, {"prefill": Lowered, "decode": Lowered}) at the shapes the
    serving path drives: an 8-token prompt, a 32-slot cache, 8 steps."""
    model = build_model(get_smoke_config(request.param))
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: model.init_cache(1, 32))
    tok = jax.ShapeDtypeStruct((1, 1), jnp.int32)
    prompt = {"tokens": jax.ShapeDtypeStruct((1, 8), jnp.int32)}
    return request.param, {
        "prefill": model.prefill_jit.lower(params, prompt, cache),
        "decode": model.decode_tokens.lower(params, cache, tok, n_steps=8),
    }


def _op_names(text: str, pattern: re.Pattern) -> list[str | None]:
    out = []
    for line in text.splitlines():
        if pattern.search(line):
            m = OP_NAME.search(line)
            out.append(m.group(1) if m else None)
    return out


def _layers(op_name: str) -> list[str]:
    return [p for p in op_name.split("/") if p in LAYERS]


@pytest.mark.parametrize("step", ["prefill", "decode"])
def test_every_matmul_carries_one_layer_scope(lowered, step):
    arch, low = lowered
    names = _op_names(low[step].as_text(dialect="hlo", debug_info=True), MATMUL)
    assert names, f"{arch} {step}: no matmul found"
    for name in names:
        assert name is not None and len(_layers(name)) == 1, (arch, step, name)
    compiled = _op_names(low[step].compile().as_text(), MATMUL)
    for name in filter(None, compiled):
        assert len(_layers(name)) == 1, (arch, step, name)
    assert any(compiled), f"{arch} {step}: the compiled text names no matmul"


@pytest.mark.parametrize("step", ["prefill", "decode"])
def test_attention_kinds_and_moe_scopes(lowered, step):
    """Every kind of layer in the period names its attention, inside "attn";
    a MoE's three stages are named inside "mlp", and only a MoE's."""
    arch, low = lowered
    cfg = get_smoke_config(arch)
    paths = [n.split("/") for n in
             OP_NAME.findall(low[step].as_text(dialect="hlo", debug_info=True))]
    for parent, inner in (("attn", KINDS), ("mlp", MOE)):
        for p in paths:
            for name in set(inner) & set(p):
                assert parent in p and p.index(parent) < p.index(name), (arch, step, p)
    assert {k for p in paths for k in KINDS if k in p} == set(cfg.period), arch
    assert {m for p in paths for m in MOE if m in p} == (set(MOE) if cfg.moe else set()), arch


def test_decode_cache_write_carries_kv_write(lowered):
    arch, low = lowered
    text = low["decode"].compile().as_text()
    writes = [n for n in _op_names(text, re.compile(r"dynamic-update-slice")) if n]
    kv = [n for n in writes if "kv_write" in n.split("/")]
    assert kv, f"{arch}: no cache write under kv_write in {writes}"
    for name in kv:  # nested inside the attention layer
        parts = name.split("/")
        assert _layers(name) == ["attn"] and parts.index("attn") < parts.index("kv_write")


def test_executables_keep_their_names(lowered):
    arch, low = lowered
    for step, module in (("prefill", "jit_prefill"), ("decode", "jit_decode_tokens")):
        text = low[step].compile().as_text()
        assert re.match(rf"HloModule {module}\b", text), (arch, text[:80])


CACHE_SLOTS = 32  # the fixture's cache length
DEFINES = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = \w+\[([\d,]*)\]")
DUS = re.compile(r"dynamic-update-slice\(%([\w.\-]+), %([\w.\-]+)")


def _dynamic_update_slices(text: str) -> list[tuple[tuple[int, ...], tuple[int, ...], str | None]]:
    """(operand shape, update shape, op_name) of every dynamic-update-slice."""
    shapes = {}
    for line in text.splitlines():
        m = DEFINES.match(line)
        if m:
            shapes[m.group(1)] = tuple(int(d) for d in m.group(2).split(",") if d)
    out = []
    for line in text.splitlines():
        m = DUS.search(line)
        if m:
            name = OP_NAME.search(line)
            out.append((shapes[m.group(1)], shapes[m.group(2)], name and name.group(1)))
    return out


def test_decode_writes_only_rows_into_the_cache(lowered):
    """The layer scan carries the stacked cache and writes each new row in
    place: no write copies a layer's whole cache (the length-S axis), and
    every write under kv_write is one slot of the stacked buffer."""
    arch, low = lowered
    writes = _dynamic_update_slices(low["decode"].compile().as_text())
    for operand, update, name in writes:
        assert CACHE_SLOTS not in update, (arch, operand, update, name)
    kv = [(o, u) for o, u, n in writes if n and "kv_write" in n.split("/")]
    assert kv, f"{arch}: no cache write under kv_write"
    for operand, update in kv:
        assert len(operand) == 5 and operand[-2] == CACHE_SLOTS, (arch, operand)
        assert update[-2] == 1 and update[:2] == (1, 1), (arch, operand, update)
