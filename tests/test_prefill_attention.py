"""Which path each prefill attention takes (``models/attention.py``).

A serving caller's full causal prefill runs the flash kernel where it
compiles for a TPU; here, on the CPU, the tests force that choice and the
kernel runs interpreted. Window layers, cross attention, non-causal calls
and the training forward keep their own paths whatever the platform.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_smoke_config
from repro.models import attention
from repro.models.model import build_model


def _params(d=64, heads=4, kv_heads=2, hd=32, dtype=jnp.float32):
    return attention.init_attention(jax.random.PRNGKey(0), d, heads, kv_heads, hd,
                                    qk_norm=True, dtype=dtype)


def _x(S, d=64, dtype=jnp.float32):
    x = jax.random.normal(jax.random.PRNGKey(1), (1, S, d), jnp.float32)
    return x.astype(dtype), jnp.arange(S, dtype=jnp.int32)[None]


@pytest.fixture
def flash_calls(monkeypatch):
    """Force the TPU's choice; record each call of the flash path."""
    calls = []
    run = attention._flash_prefill

    def recorded(q, k, v):
        calls.append(q.shape)
        return run(q, k, v)

    monkeypatch.setattr(attention, "_serves_kernel", lambda q: True)
    monkeypatch.setattr(attention, "_flash_prefill", recorded)
    return calls


def test_cpu_prefill_takes_the_reference():
    """Off a TPU the kernel would run interpreted: the reference serves."""
    q = jnp.ones((1, 4, 8, 32))
    assert not attention._serves_kernel(q)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5), (jnp.bfloat16, 2e-2)])
@pytest.mark.parametrize("S", [200, 130])
def test_flash_prefill_pads_a_ragged_prompt(flash_calls, monkeypatch, S, dtype, tol):
    """S not a multiple of the blocks: q/k/v are padded at the tail and the
    output sliced back; output and KV cache match the reference path."""
    p, (x, pos) = _params(dtype=dtype), _x(S, dtype=dtype)
    kw = dict(rope_theta=10_000.0, eps=1e-6, causal=True, flash=True)
    got, (k, v) = attention.prefill_attention(p, x, pos, **kw)
    assert flash_calls == [(1, 4, S, 32)]
    monkeypatch.setattr(attention, "_serves_kernel", lambda q: False)
    want, (k_ref, v_ref) = attention.prefill_attention(p, x, pos, **kw)
    assert len(flash_calls) == 1
    assert got.shape == want.shape == (1, S, 64)
    np.testing.assert_array_equal(np.asarray(k, np.float32), np.asarray(k_ref, np.float32))
    np.testing.assert_array_equal(np.asarray(v, np.float32), np.asarray(v_ref, np.float32))
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("call", ["banded window", "window over the prompt",
                                  "cross attention", "not causal", "no serving caller"])
def test_other_prefills_keep_their_paths(flash_calls, call):
    """Window layers, cross attention and non-causal calls never take the
    flash path, even where it is chosen."""
    p, (x, pos) = _params(), _x(64)
    kw = dict(rope_theta=10_000.0, eps=1e-6, flash=True)
    if call == "banded window":
        assert attention._band_block(64, 16)
        attention.prefill_attention(p, x, pos, causal=True, window=16, **kw)
    elif call == "window over the prompt":
        assert not attention._band_block(64, 128)
        attention.prefill_attention(p, x, pos, causal=True, window=128, **kw)
    elif call == "no serving caller":
        kw["flash"] = False
        attention.prefill_attention(p, x, pos, causal=True, **kw)
    elif call == "cross attention":
        kv = jnp.ones((1, 48, 2, 32))
        attention.prefill_attention(p, x, pos, causal=True, cross_kv=(kv, kv), **kw)
    else:
        attention.prefill_attention(p, x, pos, causal=False, **kw)
    assert flash_calls == []


def test_model_prefill_with_the_flash_kernel(flash_calls, monkeypatch):
    """qwen3's smoke model, a 100-token prompt: one flash call per layer
    (traced once in the layer scan's body); the last logits and the cache
    match the reference path."""
    model = build_model(get_smoke_config("qwen3-0.6b"))
    params = model.init(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(2), (1, 100), 0, 1000)

    def run():
        cache = model.init_cache(1, 128)
        return jax.jit(model.prefill)(params, {"tokens": tokens}, cache)

    logits, cache = run()
    assert len(flash_calls) == 1
    monkeypatch.setattr(attention, "_serves_kernel", lambda q: False)
    want, want_cache = run()
    assert len(flash_calls) == 1
    np.testing.assert_allclose(np.asarray(logits, np.float32), np.asarray(want, np.float32),
                               rtol=5e-2, atol=5e-2)
    for a, b in zip(jax.tree.leaves(cache), jax.tree.leaves(want_cache)):
        np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b, np.float32),
                                   rtol=5e-2, atol=5e-2)


def test_training_loss_keeps_the_reference(flash_calls):
    """The training forward is differentiated, and the kernel has no
    reverse-mode rule: ``jax.grad`` of qwen3's smoke loss takes the
    reference even where the kernel is chosen, and its gradient is finite."""
    cfg = get_smoke_config("qwen3-0.6b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 64), 0, cfg.vocab)
    batch = {"tokens": tokens, "labels": jnp.roll(tokens, -1, axis=1)}
    grads = jax.grad(lambda p: model.loss(p, batch)[0])(params)
    assert flash_calls == []
    assert all(bool(jnp.all(jnp.isfinite(g))) for g in jax.tree.leaves(grads))
