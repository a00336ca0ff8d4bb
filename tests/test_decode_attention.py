"""Which path each decode attention takes (``models/attention.py``).

A decode step over a stacked cache runs the decode kernel where it compiles
for a TPU; here, on the CPU, the tests force that choice and the kernel runs
interpreted. A per-layer cache keeps the reference whatever the platform.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_smoke_config
from repro.kernels import ops
from repro.kernels.decode_attention import decode_block
from repro.models import attention
from repro.models.model import build_model


def _record(monkeypatch) -> list:
    """Record the cache shape of each decode attention that takes the kernel."""
    calls = []
    run = ops.decode_attention

    def recorded(q, k_cache, v_cache, lengths, **kw):
        if kw.get("use_pallas", True):
            calls.append(k_cache.shape)
        return run(q, k_cache, v_cache, lengths, **kw)

    monkeypatch.setattr(ops, "decode_attention", recorded)
    return calls


@pytest.fixture
def kernel_calls(monkeypatch):
    """Force the TPU's choice; record each decode kernel call's cache shape."""
    monkeypatch.setattr(attention, "_serves_kernel", lambda q: True)
    return _record(monkeypatch)


def _params(d=64, heads=4, kv_heads=2, hd=32):
    return attention.init_attention(jax.random.PRNGKey(0), d, heads, kv_heads, hd,
                                    qk_norm=True, dtype=jnp.float32)


def test_cpu_decode_takes_the_reference(monkeypatch):
    """Off a TPU the kernel would run interpreted: the reference serves."""
    calls = _record(monkeypatch)
    cache = jnp.zeros((2, 1, 2, 16, 32))
    attention.decode_attention_step(
        _params(), jnp.ones((1, 1, 64)), cache, cache, jnp.array([3], jnp.int32),
        rope_theta=10_000.0, eps=1e-6, layer=jnp.int32(1))
    assert calls == []


def test_per_layer_cache_keeps_the_reference(kernel_calls):
    """Without ``layer`` the cache is one layer's, not a stack: the reference
    serves even where the kernel is chosen."""
    cache = jnp.zeros((1, 2, 16, 32))
    attention.decode_attention_step(
        _params(), jnp.ones((1, 1, 64)), cache, cache, jnp.array([3], jnp.int32),
        rope_theta=10_000.0, eps=1e-6)
    assert kernel_calls == []


@pytest.mark.parametrize("slots,window", [(300, None), (1000, None), (300, 300), (1000, 1000)],
                         ids=["full-300", "full-1000", "ring-300", "ring-1000"])
def test_ragged_cache_length_with_the_kernel(kernel_calls, monkeypatch, slots, window):
    """A cache length that no block divides (``model.init_cache`` takes any):
    the kernel's last block reaches past the cache, and the step matches
    the reference's, for a prefix inside the first block, one that ends in
    the partial block and (as a full ring) the whole cache."""
    kv_heads, hd = 2, 32
    block = decode_block(slots, kv_heads, hd, 4)
    assert slots % block and -(-slots // block) == 2
    shape = (3, 2, kv_heads, slots, hd)
    k_cache = jax.random.normal(jax.random.PRNGKey(1), shape)
    v_cache = jax.random.normal(jax.random.PRNGKey(2), shape)
    lengths = jnp.array([block - 7, slots - 1 if window is None else 5 * slots], jnp.int32)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 1, 64))
    params = _params()

    def step(serves):
        monkeypatch.setattr(attention, "_serves_kernel", lambda q: serves)
        return attention.decode_attention_step(
            params, x, k_cache, v_cache, lengths, rope_theta=10_000.0, eps=1e-6,
            window=window, layer=jnp.int32(2))

    want, got = step(False), step(True)
    assert kernel_calls == [shape]
    for a, b in zip(got, want):
        assert np.isfinite(np.asarray(a)).all()
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-5)


def _mellum2_window_full():
    """mellum2's smoke model: 3 window layers (a ring of 16) and 1 full layer."""
    cfg = get_smoke_config("mellum2-12b-a2.5b")
    return dataclasses.replace(cfg, sliding_window=16)


# (config, prompt, cache slots, decode steps): qwen3's smoke cache of 3,000
# slots is a block of 2,048 and a partial one, and its valid prefix crosses
# from the first into the second; mellum2's ring wraps
MODELS = {
    "qwen3 dense": (lambda: get_smoke_config("qwen3-0.6b"), 2040, 3000, 16),
    "mellum2 window+full": (_mellum2_window_full, 40, 64, 24),
}


@pytest.mark.parametrize("name", list(MODELS))
def test_model_decode_with_the_kernel(kernel_calls, monkeypatch, name):
    """``decode_tokens`` from one prefill, once with the kernel (traced once
    a layer of the period, in the layer scan's body, over its kind's stack)
    and once with the reference: the same tokens, and caches equal up to
    float32 rounding."""
    make, prompt, slots, steps = MODELS[name]
    cfg = make()
    if name == "qwen3 dense":
        block = decode_block(slots, cfg.n_kv_heads, cfg.head_dim, 4)
        assert (block, -(-slots // block)) == (2048, 2)
        assert prompt < block < prompt + steps
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(2), (1, prompt), 0, cfg.vocab)
    monkeypatch.setattr(attention, "_serves_kernel", lambda q: False)
    model = build_model(cfg)
    logits, cache = model.prefill_jit(params, {"tokens": tokens}, model.init_cache(1, slots))
    first = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)

    want, want_cache = model.decode_tokens(params, cache, first, n_steps=steps)
    assert kernel_calls == []
    monkeypatch.setattr(attention, "_serves_kernel", lambda q: True)
    got, got_cache = build_model(cfg).decode_tokens(params, cache, first, n_steps=steps)
    assert kernel_calls == [cache["k"][kind].shape for kind in cfg.period]
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    for a, b in zip(jax.tree.leaves(got_cache), jax.tree.leaves(want_cache)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4)
