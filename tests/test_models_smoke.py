"""Per-architecture smoke tests: REDUCED variant of each family (2 layers,
d_model<=512, <=4 experts), one forward + one train step on CPU, asserting
output shapes and no NaNs — as required for deliverable (f)."""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import ARCH_IDS, get_config, get_smoke_config
from repro.models import transformer
from repro.models.attention import decode_attention_step
from repro.models.layers import rms_norm, unembed
from repro.models.model import build_model, greedy_token
from repro.optim.adamw import AdamW

KEY = jax.random.PRNGKey(0)


def _batch(cfg, B=2, S=32):
    b = {
        "tokens": jax.random.randint(KEY, (B, S), 0, cfg.vocab),
        "labels": jax.random.randint(KEY, (B, S), 0, cfg.vocab),
    }
    if cfg.family == "encdec":
        b["frames"] = jax.random.normal(KEY, (B, cfg.encoder_frames, cfg.d_model))
    return b


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_config_is_reduced(arch):
    cfg = get_smoke_config(arch)
    full = get_config(arch)
    assert cfg.n_layers == max(2, len(full.layer_types))  # one whole period
    assert cfg.layer_types == full.layer_types
    assert cfg.d_model <= 512
    assert cfg.family == full.family
    if cfg.moe is not None:
        assert cfg.moe.n_experts <= 4


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_forward_shapes_and_finite(arch):
    cfg = get_smoke_config(arch)
    model = build_model(cfg)
    params = model.init(KEY)
    batch = _batch(cfg)
    logits, aux = model.forward(params, batch)
    assert logits.shape == (2, 32, cfg.vocab)
    assert np.isfinite(np.asarray(logits, np.float32)).all()


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_one_train_step_no_nans(arch):
    cfg = get_smoke_config(arch)
    model = build_model(cfg)
    params = model.init(KEY)
    opt = AdamW(learning_rate=1e-3)
    opt_state = opt.init(params)
    batch = _batch(cfg)

    @jax.jit
    def step(params, opt_state, batch):
        (loss, m), grads = jax.value_and_grad(
            lambda p: model.loss(p, batch), has_aux=True
        )(params)
        params, opt_state, _ = opt.update(grads, opt_state, params)
        return params, opt_state, loss

    params, opt_state, loss = step(params, opt_state, batch)
    assert np.isfinite(float(loss))
    flat = jax.tree.leaves(params)
    assert all(np.isfinite(np.asarray(x, np.float32)).all() for x in flat)


@pytest.mark.parametrize("arch", ["llama3.2-1b", "qwen3-0.6b", "xlstm-1.3b",
                                  "zamba2-1.2b", "whisper-small"])
def test_decode_three_steps(arch):
    cfg = get_smoke_config(arch)
    model = build_model(cfg)
    params = model.init(KEY)
    B = 2
    cache = model.init_cache(B, 64)
    if cfg.family == "encdec":
        batch = {"frames": jax.random.normal(KEY, (B, cfg.encoder_frames, cfg.d_model))}
    else:
        batch = {"tokens": jax.random.randint(KEY, (B, 16), 0, cfg.vocab)}
    _, cache = model.prefill(params, batch, cache)
    tok = jnp.ones((B, 1), jnp.int32)
    for _ in range(3):
        logits, cache = model.decode_step(params, cache, tok)
        assert logits.shape == (B, 1, cfg.vocab)
        assert np.isfinite(np.asarray(logits, np.float32)).all()
        tok = greedy_token(logits)


@pytest.mark.parametrize("arch", ["llama3.2-1b", "qwen3-0.6b", "xlstm-1.3b", "zamba2-1.2b"])
def test_decode_matches_parallel_forward(arch):
    """prefill+decode_step == forward at the last position (no token drop)."""
    cfg = get_smoke_config(arch)
    if cfg.moe is not None:
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=float(cfg.moe.n_experts))
        )
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(1))
    B, S = 2, 33
    tokens = jax.random.randint(jax.random.PRNGKey(2), (B, S), 0, cfg.vocab)
    full, _ = model.forward(params, {"tokens": tokens})
    want = np.asarray(full[:, -1])
    cache = model.init_cache(B, 64)
    _, cache = model.prefill(params, {"tokens": tokens[:, :-1]}, cache)
    got, _ = model.decode_step(params, cache, tokens[:, -1:])
    err = np.max(np.abs(np.asarray(got[:, 0]) - want)) / (np.max(np.abs(want)) + 1e-9)
    assert err < 2e-3, err


def test_sliding_window_decode_matches_windowed_forward():
    """Ring-buffer sliding-window decode == full forward with window mask."""
    cfg = get_smoke_config("llama3.2-1b")
    cfg = dataclasses.replace(cfg, sliding_window=16)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(3))
    B, S = 1, 40  # longer than the window
    tokens = jax.random.randint(jax.random.PRNGKey(4), (B, S), 0, cfg.vocab)
    full, _ = model.forward(params, {"tokens": tokens})
    want = np.asarray(full[:, -1])
    cache = model.init_cache(B, S)
    assert cache["k"]["window"].shape[3] == 16  # ring of window size
    _, cache = model.prefill(params, {"tokens": tokens[:, :-1]}, cache)
    got, _ = model.decode_step(params, cache, tokens[:, -1:])
    err = np.max(np.abs(np.asarray(got[:, 0]) - want)) / (np.max(np.abs(want)) + 1e-9)
    assert err < 2e-3, err


def _per_layer_decode_step(cfg, params, cache, tok):
    """One greedy step as the layer scan ran before it carried the cache:
    each layer's cache sliced out as the scan's xs, updated alone, and
    re-stacked as its ys."""
    eps = cfg.norm_eps
    x = jnp.take(params["embed"], tok, axis=0)
    lengths = cache["lengths"]

    def body(x, layer):
        p, kc, vc = layer
        h, kc, vc = decode_attention_step(
            p["attn"], rms_norm(x, p["ln1"], eps), kc, vc, lengths,
            rope_theta=cfg.rope_theta, eps=eps, window=cfg.sliding_window)
        x = x + h
        m, _ = transformer._mlp_serve(cfg, p["mlp"], rms_norm(x, p["ln2"], eps))
        return x + m, (kc, vc)

    kind, = cfg.period
    x, (ks, vs) = jax.lax.scan(body, x, (params["layers"], cache["k"][kind], cache["v"][kind]))
    x = rms_norm(x, params["final_norm"], eps)
    logits = unembed(x, params["unembed"] if "unembed" in params else params["embed"].T)
    return greedy_token(logits), {"k": {kind: ks}, "v": {kind: vs}, "lengths": lengths + 1}


@pytest.mark.parametrize("arch,window", [("llama3.2-1b", 16), ("granite-moe-1b-a400m", None)])
def test_decode_tokens_matches_per_layer_cache_decode(arch, window):
    """decode_tokens (stacked cache carried through the layer scan, rows
    written in place) == a step-by-step loop over per-layer caches: the same
    tokens and the same final cache. The two sequences sit at different
    lengths, and with the window both cross the ring's wrap."""
    cfg = dataclasses.replace(get_smoke_config(arch), sliding_window=window)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(5))
    B, S, T = 2, 12, 12
    tokens = jax.random.randint(jax.random.PRNGKey(6), (B, S), 0, cfg.vocab)
    _, cache = model.prefill(params, {"tokens": tokens}, model.init_cache(B, 64))
    cache["lengths"] = jnp.array([S, S - 5], jnp.int32)
    if window is not None:
        assert cache["k"]["window"].shape[3] == window < S + T - 5
    tok = tokens[:, -1:]
    got, got_cache = model.decode_tokens(params, cache, tok, T)

    step = jax.jit(functools.partial(_per_layer_decode_step, cfg))
    want, want_cache = [], cache
    for _ in range(T):
        tok, want_cache = step(params, want_cache, tok)
        want.append(tok)
    np.testing.assert_array_equal(np.asarray(got), np.concatenate(want, axis=1))
    jax.tree.map(lambda g, w: np.testing.assert_array_equal(np.asarray(g), np.asarray(w)),
                 got_cache, want_cache)


def test_moe_load_balance_loss_positive():
    cfg = get_smoke_config("deepseek-moe-16b")
    model = build_model(cfg)
    params = model.init(KEY)
    _, aux = model.forward(params, _batch(cfg))
    assert float(aux) > 0.0
