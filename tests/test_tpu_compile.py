"""Compile the main path's device programs at real widths for a TPU v5e
that is described, not attached: the three Pallas kernels compiled (not
interpreted), the serving steps at published widths on one chip, and the
serving and training steps sharded by ``launch/shardings.py``'s rules over
the four chips of a v5e:2x2. What the chip's compiler would refuse
(unaligned tiles, too much fast memory, a program that does not fit the
device) fails here, at no chip time. Nothing runs, so this says nothing
about results or times.

The topology is described only inside the module fixture: one process at a
time may load the TPU compiler's library, and it keeps it until it exits.
"""
import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, Mesh, SingleDeviceSharding

from repro.configs.registry import get_config
from repro.distributed.sharding import use_mesh
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.matmul_probe import matmul
from repro.launch.shardings import make_rules, shard_inputs, shard_params_like
from repro.models.model import build_model

V5E_HBM_BYTES = 16 * 2**30
QWEN = get_config("qwen3-0.6b")


@pytest.fixture(scope="module")
def v5e_2x2():
    """The four devices of a described v5e:2x2."""
    from jax.experimental import compilation_cache, topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler can be loaded here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.compilation_cache.reset_cache()
    yield topo.devices
    jax.config.update("jax_enable_compilation_cache", was_on)


@pytest.fixture(scope="module")
def one_chip(v5e_2x2):
    return SingleDeviceSharding(v5e_2x2[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_matmul_probe_compiles(one_chip):
    a = _spec((512, 512), jnp.float32, one_chip)
    text = matmul.lower(a, a, interpret=False).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("batch,seq", [(1, 512), (4, 128)])
def test_flash_attention_compiles_at_qwen3_heads(one_chip, batch, seq):
    hd = QWEN.head_dim
    q = _spec((batch, QWEN.n_heads, seq, hd), jnp.bfloat16, one_chip)
    kv = _spec((batch, QWEN.n_kv_heads, seq, hd), jnp.bfloat16, one_chip)
    text = flash_attention.lower(q, kv, kv, causal=True,
                                 interpret=False).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("arch,seq", [("phi3-mini-3.8b", 1024), ("mellum2-12b-a2.5b", 4096)])
def test_flash_attention_compiles_at_served_heads(one_chip, arch, seq):
    """phi3's 32 heads of 96 and mellum2's 32/4 heads of 128, with the
    blocks the kernel picks from the shapes."""
    cfg = get_config(arch)
    q = _spec((1, cfg.n_heads, seq, cfg.head_dim), jnp.bfloat16, one_chip)
    kv = _spec((1, cfg.n_kv_heads, seq, cfg.head_dim), jnp.bfloat16, one_chip)
    text = flash_attention.lower(q, kv, kv, causal=True,
                                 interpret=False).compile().as_text()
    assert re.search(r"%prefill_flash\S* = .*custom_call_target=\"tpu_custom_call\"", text)


def test_qwen3_prefill_runs_the_flash_kernel(one_chip, monkeypatch):
    """qwen3-0.6b's prefill of 2,048 tokens as the chip's compiler builds
    it, with the kernel compiled as on the chip: one ``prefill_flash`` call
    (in the layer scan's body) and no f32 (..., S, S) scores."""
    from repro.kernels import ops

    monkeypatch.setattr(ops, "runs_interpreted", lambda x: False)
    model = build_model(QWEN)

    def on_chip(tree):
        return jax.tree.map(lambda s: _spec(s.shape, s.dtype, one_chip), tree)

    params = on_chip(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    cache = on_chip(jax.eval_shape(lambda: model.init_cache(1, 4096)))
    tokens = _spec((1, 2048), jnp.int32, one_chip)
    text = model.prefill_jit.lower(params, {"tokens": tokens}, cache).compile().as_text()
    assert len(re.findall(r"%prefill_flash\S* = .*custom-call\(", text)) == 1
    assert not re.search(r"f32\[[0-9,]*2048,2048\]", text)


def test_qwen3_training_step_compiles_without_the_kernel(one_chip, monkeypatch):
    """The gradient of qwen3-0.6b's loss, 256 tokens, as the chip's compiler
    builds it: the training forward is differentiated, so it keeps the
    reference attention and no ``prefill_flash`` call."""
    from repro.kernels import ops

    monkeypatch.setattr(ops, "runs_interpreted", lambda x: False)
    model = build_model(QWEN)

    def on_chip(tree):
        return jax.tree.map(lambda s: _spec(s.shape, s.dtype, one_chip), tree)

    params = on_chip(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    tokens = _spec((1, 256), jnp.int32, one_chip)
    grad = jax.jit(jax.grad(lambda p, b: model.loss(p, b)[0]))
    text = grad.lower(params, {"tokens": tokens, "labels": tokens}).compile().as_text()
    assert "prefill_flash" not in text


@pytest.mark.parametrize("cache_len", [2048, 1000])
def test_decode_attention_compiles_at_qwen3_heads(one_chip, cache_len):
    """A batch of four over a layer of qwen3-0.6b's stacked cache, of a
    length its block divides and of one that ends in a partial block."""
    batch, hd = 4, QWEN.head_dim
    q = _spec((batch, QWEN.n_heads, 1, hd), jnp.bfloat16, one_chip)
    kv = _spec((QWEN.n_layers, batch, QWEN.n_kv_heads, cache_len, hd), jnp.bfloat16, one_chip)
    lengths = _spec((batch,), jnp.int32, one_chip)
    layer = _spec((), jnp.int32, one_chip)
    text = decode_attention.lower(q, kv, kv, lengths, layer,
                                  interpret=False).compile().as_text()
    assert re.search(r"%decode_attention\S* = .*custom_call_target=\"tpu_custom_call\"", text)


@pytest.mark.parametrize("step", ["prefill_jit", "decode_tokens"])
def test_qwen3_serving_step_fits_one_chip(one_chip, step):
    """The shapes the serving path compiles for one 128-token prompt and 32
    new tokens (cache bucket 256), at qwen3-0.6b's published widths."""
    model = build_model(QWEN)

    def on_chip(tree):
        return jax.tree.map(lambda s: _spec(s.shape, s.dtype, one_chip), tree)

    params = on_chip(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    cache = on_chip(jax.eval_shape(lambda: model.init_cache(1, 256)))
    if step == "prefill_jit":
        tokens = _spec((1, 128), jnp.int32, one_chip)
        compiled = model.prefill_jit.lower(params, {"tokens": tokens},
                                           cache).compile()
    else:
        tok = _spec((1, 1), jnp.int32, one_chip)
        compiled = model.decode_tokens.lower(params, cache, tok,
                                             n_steps=32).compile()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    weights = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))
    assert weights > 1.1e9  # bf16 published widths, not the smoke variant
    assert used < V5E_HBM_BYTES


# (batch, data × model): one sequence over four model shards, and a batch
# of four split over two data shards, whose decode writes its rows by the
# batched scatter
LAYOUTS = {"b1_model4": (1, (1, 4)), "b4_data2_model2": (4, (2, 2))}


def _sharded(cfg, devices, batch, mesh_shape, seq, cache_len=None):
    """``cfg``'s params, and a batch of ``seq`` tokens with its cache of
    ``cache_len`` slots, sharded by ``make_rules`` over a data × model mesh
    of ``devices``; and the mesh."""
    mesh = Mesh(np.array(devices).reshape(mesh_shape), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    model = build_model(cfg)
    params = shard_params_like(jax.eval_shape(model.init, jax.random.PRNGKey(0)),
                               cfg, mesh)
    tokens = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
    cache = (jax.eval_shape(lambda: model.init_cache(batch, cache_len))
             if cache_len else None)
    inputs, cache = shard_inputs(cfg, mesh, {"batch": {"tokens": tokens}, "cache": cache})
    return model, params, inputs["tokens"], cache, mesh


def _fits_a_chip(compiled, params) -> None:
    """Each chip holds a share of the weights, and its arguments and temps
    fit its memory."""
    mem = compiled.memory_analysis()
    weights = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))
    assert mem.argument_size_in_bytes < weights
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < V5E_HBM_BYTES


@pytest.mark.parametrize("layout", list(LAYOUTS))
@pytest.mark.parametrize("step", ["prefill_jit", "decode_tokens"])
@pytest.mark.parametrize("arch", ["qwen3-0.6b", "phi3-mini-3.8b"])
def test_serving_step_compiles_sharded(v5e_2x2, arch, step, layout):
    """A 1,024-token prefill, or 16 decode steps over 2,048 cache slots, at
    published widths, with params and cache sharded by ``make_rules`` over
    the four chips: each compiles and fits a chip."""
    cfg = get_config(arch)
    batch, mesh_shape = LAYOUTS[layout]
    seq = 1024 if step == "prefill_jit" else 1
    model, params, tokens, cache, mesh = _sharded(cfg, v5e_2x2, batch, mesh_shape,
                                                  seq, cache_len=2048)
    with use_mesh(mesh, make_rules(cfg, mesh)):
        if step == "prefill_jit":
            lowered = model.prefill_jit.lower(params, {"tokens": tokens}, cache)
        else:
            lowered = model.decode_tokens.lower(params, cache, tokens, n_steps=16)
        compiled = lowered.compile()
    _fits_a_chip(compiled, params)
    if step == "decode_tokens" and batch > 1:
        assert " scatter(" in compiled.as_text()  # the batched row write


def test_qwen3_training_step_compiles_sharded(v5e_2x2):
    """The gradient of qwen3-0.6b's loss over 4 × 1,024 tokens, sharded by
    ``make_rules`` over data 2 × model 2: it compiles and fits a chip."""
    model, params, tokens, _, mesh = _sharded(QWEN, v5e_2x2, 4, (2, 2), 1024)
    grad = jax.jit(jax.grad(lambda p, b: model.loss(p, b)[0]))
    with use_mesh(mesh, make_rules(QWEN, mesh)):
        compiled = grad.lower(params, {"tokens": tokens, "labels": tokens}).compile()
    _fits_a_chip(compiled, params)


COMPUTATION = re.compile(r"^(?:ENTRY )?%([\w.\-]+) .*\{$")
CALLED = re.compile(r"\b(?:calls|body|condition|to_apply)=%([\w.\-]+)")
WHILE_BODY = re.compile(r"\bwhile\(.*\bbody=%([\w.\-]+)")


def _computations(text: str) -> dict[str, list[str]]:
    comps, name = {}, None
    for line in text.splitlines():
        m = COMPUTATION.match(line)
        if m:
            name = m.group(1)
            comps[name] = []
        elif line == "}":
            name = None
        elif name is not None:
            comps[name].append(line)
    return comps


def _decode_text(cfg, one_chip, cache_len, kernel=True):
    """The decode executable of ``cfg`` for 16 steps, as the chip's compiler
    builds it, and its cache's shapes: with the kernels compiled as on one
    chip, or (``kernel=False``) with the jnp reference that serves a mesh of
    several devices."""
    from repro.kernels import ops

    model = build_model(cfg)

    def on_chip(tree):
        return jax.tree.map(lambda s: _spec(s.shape, s.dtype, one_chip), tree)

    params = on_chip(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    cache = on_chip(jax.eval_shape(lambda: model.init_cache(1, cache_len)))
    tok = _spec((1, 1), jnp.int32, one_chip)
    with pytest.MonkeyPatch.context() as m:
        if kernel:
            m.setattr(ops, "runs_interpreted", lambda x: False)
        lowered = model.decode_tokens.lower(params, cache, tok, n_steps=16)
    return lowered.compile().as_text(), cache


def _shape(a) -> str:
    return "bf16[%s]" % ",".join(map(str, a.shape))


@pytest.mark.parametrize("path", ["kernel", "reference"])
def test_decode_loop_copies_no_whole_cache(one_chip, path):
    """qwen3-0.6b's decode at 2,048 cache slots, as the chip's compiler
    builds it: the layer scan carries the stacked cache, so no while body
    (nor anything it calls) copies the whole stacked cache; with the kernel
    none slices a layer out of it either. The copies at the executable's
    entry and exit run once per request."""
    text, cache = _decode_text(QWEN, one_chip, 2048, kernel=path == "kernel")
    stacked = _shape(cache["k"]["full"])
    assert stacked == "bf16[28,1,8,2048,128]"
    _assert_no_loop_copies(text, [stacked], parts=path == "kernel")


def _mellum2_one_chip():
    """mellum2-12b-a2.5b at its published widths with one chip's 16 of 64
    experts."""
    full = get_config("mellum2-12b-a2.5b")
    return dataclasses.replace(full, moe=dataclasses.replace(full.moe, n_held=16))


@pytest.mark.parametrize("path", ["kernel", "reference"])
def test_mellum2_decode_loop_copies_no_cache_stack(one_chip, path):
    """mellum2-12b-a2.5b's decode with one chip's experts, at 4,096 cache
    slots: the period scan carries both cache stacks, the ring of the 21
    window layers and the 7 full layers' stack, and no loop body copies
    either whole; with the kernel none copies or slices a part of either,
    into another memory space neither."""
    text, cache = _decode_text(_mellum2_one_chip(), one_chip, 4096, kernel=path == "kernel")
    stacks = [_shape(cache["k"][kind]) for kind in ("window", "full")]
    assert stacks == ["bf16[21,1,4,1024,128]", "bf16[7,1,4,4096,128]"]
    _assert_no_loop_copies(text, stacks, parts=path == "kernel")


def _assert_no_loop_copies(text: str, stacked: list[str], parts: bool = True) -> None:
    """No loop body, nor anything it calls, copies a stack of ``stacked``
    whole; with ``parts``, none copies or slices a part of one either: not a
    layer, not into another memory space. (The reference's attention reads
    a layer by a slice fused into its scores: no copy, but a slice.)"""
    comps = _computations(text)
    todo = [m.group(1) for line in text.splitlines() if (m := WHILE_BODY.search(line))]
    assert len(todo) >= 2, "the step loop and the layer loop"
    moves = re.compile(r"(?<![\w-])(?:copy|copy-start|slice|slice-start|dynamic-slice)\(")
    # a stack's dims after its layer axis, under any count of layers
    part = re.compile("|".join(r"bf16\[\d+," + re.escape(s.split(",", 1)[1]) for s in stacked))
    whole = re.compile("|".join(re.escape(s) + r"\{[^}]*\} copy(?:-start)?\(" for s in stacked))
    seen = set()
    while todo:
        name = todo.pop()
        if name in seen:
            continue
        seen.add(name)
        for line in comps[name]:
            todo += CALLED.findall(line)
            assert not whole.search(line), (name, line[:200])
            if parts:
                assert not (moves.search(line) and part.search(line)), (name, line[:200])


# (config, cache slots): each served configuration at a long cache of its mix
DECODE_KERNEL = {
    "qwen3-0.6b": (lambda: QWEN, 8192),
    "phi3-mini-3.8b": (lambda: get_config("phi3-mini-3.8b"), 2048),
    "mellum2-12b-a2.5b": (lambda: _mellum2_one_chip(), 4096),
}


@pytest.mark.parametrize("arch", list(DECODE_KERNEL))
def test_decode_runs_the_kernel_in_the_layer_loop(one_chip, arch):
    """The decode executable of each served configuration as the chip runs
    it: each attention layer of a period calls the decode kernel in the
    layer loop's body, with its kind's whole stack as K and V, and no loop
    body copies or slices a stack or a layer of it."""
    make, cache_len = DECODE_KERNEL[arch]
    cfg = make()
    text, cache = _decode_text(cfg, one_chip, cache_len)
    comps = _computations(text)
    bodies = {m.group(1) for line in text.splitlines() if (m := WHILE_BODY.search(line))}
    calls = [line for name in bodies for line in comps[name]
             if re.search(r"%decode_attention\S* = .*custom-call\(", line)]
    stacks = [_shape(cache["k"][kind]) for kind in cfg.period]
    assert len(calls) == len(stacks)
    for line in calls:
        # operands: layer, lengths, q, then the K and V stacks
        operands = re.findall(r"\w+\[[\d,]*\]", line.split("operand_layout_constraints=")[1])
        assert operands[3] == operands[4] in stacks
    _assert_no_loop_copies(text, sorted(set(stacks)))


def test_mellum2_decode_reads_each_expert_where_it_lies(one_chip):
    """The decode's loop over the held picks reads each picked expert's
    matrices from the stack of every layer's experts: no loop body, nor a
    computation it runs (fused computations aside: their values are not
    written out), makes a layer's 16 experts or one expert's matrix."""
    text, _ = _decode_text(_mellum2_one_chip(), one_chip, 4096)
    comps = _computations(text)
    ran = re.compile(r"\b(?:body|condition|to_apply)=%([\w.\-]+)")
    todo = [m.group(1) for line in text.splitlines() if (m := WHILE_BODY.search(line))]
    assert len(todo) >= 2, "the step loop and the layer loop"
    experts = re.compile(r"= bf16\[(?:16,)?(?:2304,896|896,2304)\]")
    seen = set()
    while todo:
        name = todo.pop()
        if name in seen:
            continue
        seen.add(name)
        for line in comps[name]:
            todo += ran.findall(line)
            assert not experts.search(line), (name, line[:200])
    assert any("moe_experts" in line for name in seen for line in comps[name]), \
        "the loop over the held picks runs inside the decode loops"
