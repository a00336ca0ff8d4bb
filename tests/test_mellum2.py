"""mellum2-12b-a2.5b at smoke size on the CPU, in float32, against a plain
``jax.numpy`` reference of its forward pass written here from the published
equations (Hugging Face ``modeling_qwen3_moe`` with the sliding-window mask
and ``_compute_yarn_parameters``), which calls nothing of the program.

The program runs as it serves: ``prefill_jit`` then ``decode_step`` /
``decode_tokens`` through its two cache stacks, a ring of ``window`` slots
for the window layers and the full length for the full layers, with a chip's
share of the experts. Prompts outrun the window, and decoding crosses the
ring's wrap.

Tolerance: both sides compute in float32 on the CPU and differ only in the
order of their sums (the program's grouped expert products, its cache, its
masked ring), which moves logits by about 1e-6 of their largest value; the
limit of 1e-4 leaves room for that, while each mutation below moves them by
1e-2 or more.
"""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_smoke_config
from repro.models import moe as moe_lib
from repro.models.model import build_model, greedy_token

TOL = 1e-4
SMOKE = get_smoke_config("mellum2-12b-a2.5b")
# two periods of 3 window + 1 full layers, a window of 16, 16 routed experts
# top-4 of which this chip holds 4 (ids 4-7)
CFG = dataclasses.replace(
    SMOKE, n_layers=8, sliding_window=16,
    moe=dataclasses.replace(SMOKE.moe, n_experts=16, top_k=4, d_expert=32, n_held=4,
                            first_held=4))
S, T = 40, 24  # prompt and decode steps: the window's ring wraps in both


# ---------------------------------------------------------------------------
# the reference
# ---------------------------------------------------------------------------


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _inv_freq(hd, theta, rope):
    """RoPE's inverse frequencies and the scale of cos and sin: YaRN's where
    ``rope`` (a layer kind's RopeConfig) gives them, else the default's."""
    if rope is None:
        return 1.0 / theta ** (np.arange(0, hd, 2) / hd), 1.0
    plain = 1.0 / rope.theta ** (np.arange(0, hd, 2) / hd)

    def dim(rot):
        return hd * math.log(rope.original_max_positions / (rot * 2 * math.pi)) / (
            2 * math.log(rope.theta))

    low = max(math.floor(dim(rope.beta_fast)), 0)
    high = min(math.ceil(dim(rope.beta_slow)), hd - 1)
    ramp = np.clip((np.arange(hd // 2) - low) / max(high - low, 1e-3), 0, 1)
    inv = plain / rope.yarn_factor * ramp + plain * (1 - ramp)
    return inv, rope.attention_factor


def _rope(x, inv, scale):  # x: (S, heads, hd)
    hd = x.shape[-1]
    ang = np.arange(x.shape[0])[:, None] * inv[None]
    cos = np.concatenate([np.cos(ang)] * 2, -1)[:, None] * scale
    sin = np.concatenate([np.sin(ang)] * 2, -1)[:, None] * scale
    rot = jnp.concatenate([-x[..., hd // 2:], x[..., :hd // 2]], -1)
    return x * cos + rot * sin


def ref_moe(cfg, mp, h, l=None):
    """The MoE of one layer over the experts its weights hold; h: (S, d)."""
    m = cfg.moe
    pick = (lambda a: a) if l is None else (lambda a: a[l])
    probs = jax.nn.softmax(h @ pick(mp["router"]), axis=-1)
    top, ids = jax.lax.top_k(probs, m.top_k)
    top = top / top.sum(-1, keepdims=True)
    y = jnp.zeros_like(h)
    for e in range(m.held):
        g = jnp.sum(jnp.where(ids == m.first_held + e, top, 0.0), axis=-1)  # (S,)
        a = h @ pick(mp["w_gate"])[e]
        b = h @ pick(mp["w_up"])[e]
        y = y + g[:, None] * ((jax.nn.silu(a) * b) @ pick(mp["w_down"])[e])
    return y


def ref_logits(cfg, params, tokens):
    """Logits (S, V) at every position of ``tokens`` (S,)."""
    with jax.default_matmul_precision("highest"):
        eps, Lp = cfg.norm_eps, params["layers"]
        x = params["embed"][jnp.asarray(tokens)]
        n = len(tokens)
        i, j = np.arange(n)[:, None], np.arange(n)[None, :]
        for l in range(cfg.n_layers):
            kind = cfg.layer_types[l % len(cfg.layer_types)]
            a = Lp["attn"]
            h = _rms(x, Lp["ln1"][l], eps)
            q = jnp.einsum("sd,dhk->shk", h, a.wq[l])
            k = jnp.einsum("sd,dhk->shk", h, a.wk[l])
            v = jnp.einsum("sd,dhk->shk", h, a.wv[l])
            q, k = _rms(q, a.q_norm[l], eps), _rms(k, a.k_norm[l], eps)
            inv, scale = _inv_freq(q.shape[-1], cfg.rope_theta,
                                   dict(cfg.rope_by_kind).get(kind))
            q, k = _rope(q, inv, scale), _rope(k, inv, scale)
            k = jnp.repeat(k, cfg.n_heads // cfg.n_kv_heads, axis=1)
            v = jnp.repeat(v, cfg.n_heads // cfg.n_kv_heads, axis=1)
            sees = j <= i
            if kind == "window":  # query i sees key j when i - window < j <= i
                sees &= j > i - cfg.sliding_window
            s = jnp.einsum("qhk,shk->hqs", q, k) / math.sqrt(q.shape[-1])
            p = jax.nn.softmax(jnp.where(sees[None], s, -jnp.inf), axis=-1)
            x = x + jnp.einsum("qhk,hkd->qd", jnp.einsum("hqs,shk->qhk", p, v), a.wo[l])
            x = x + ref_moe(cfg, Lp["mlp"], _rms(x, Lp["ln2"][l], eps), l)
        x = _rms(x, params["final_norm"], eps)
        return np.asarray(x @ params["unembed"])


# ---------------------------------------------------------------------------
# the program, and what it is compared on
# ---------------------------------------------------------------------------


def make_params(cfg, seed=0):
    """The program's weights from a seed, norm weights 1 + N(0, 0.1) so that
    none of them is the identity."""
    params = build_model(cfg).init(jax.random.PRNGKey(seed))
    leaves, tree = jax.tree_util.tree_flatten_with_path(params)
    out = []
    for i, (path, a) in enumerate(leaves):
        name = jax.tree_util.keystr(path)
        if "norm" in name or "ln" in name:
            a = 1.0 + 0.1 * jax.random.normal(jax.random.PRNGKey(1000 + i), a.shape)
        out.append(a)
    return jax.tree_util.tree_unflatten(tree, out)


@pytest.fixture(scope="module")
def params():
    return make_params(CFG)


@pytest.fixture(scope="module")
def tokens():
    return np.asarray(jax.random.randint(jax.random.PRNGKey(7), (S + T,), 0, CFG.vocab))


def err(got, want):
    got, want = np.asarray(got), np.asarray(want)
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def served_logits(cfg, params, tokens):
    """Prefill over tokens[:S], then one decode_step per later token:
    logits (T + 1, V) for positions S - 1 .. S + T - 1."""
    model = build_model(cfg)
    cache = model.init_cache(1, S + T)
    logits, cache = model.prefill_jit(params, {"tokens": jnp.asarray(tokens[None, :S])}, cache)
    out = [logits[0, -1]]
    step = jax.jit(model.decode_step)
    for t in range(S, S + T):
        logits, cache = step(params, cache, jnp.asarray(tokens[None, t:t + 1]))
        out.append(logits[0, -1])
    return np.stack(out), cache


def test_cache_has_a_ring_and_a_full_stack():
    cache = build_model(CFG).init_cache(1, S + T)
    assert cache["k"]["window"].shape == (6, 1, CFG.n_kv_heads, 16, CFG.head_dim)
    assert cache["k"]["full"].shape == (2, 1, CFG.n_kv_heads, S + T, CFG.head_dim)


def test_prefill_and_decode_match_reference(params, tokens):
    want = ref_logits(CFG, params, tokens)[S - 1:]
    got, cache = served_logits(CFG, params, tokens)
    assert err(got[0], want[0]) < TOL  # prefill
    assert err(got, want) < TOL        # and every decode step after it
    assert int(cache["lengths"][0]) == S + T


def test_decode_tokens_serves_reference_argmax(params, tokens):
    """The jitted greedy loop the benchmark drives: every served token is the
    reference's first choice at its position."""
    model = build_model(CFG)
    cache = model.init_cache(1, S + T)
    logits, cache = model.prefill_jit(params, {"tokens": jnp.asarray(tokens[None, :S])}, cache)
    first = greedy_token(logits)
    toks, _ = model.decode_tokens(params, cache, first, T - 1)
    served = np.concatenate([np.asarray(first)[0], np.asarray(toks)[0]])
    ref = ref_logits(CFG, params, np.concatenate([tokens[:S], served[:-1]]))[S - 1:]
    np.testing.assert_array_equal(served, ref.argmax(-1))


def _drop_last_held_pick(route):
    """_route with the gate of the last token's first held pick set to 0."""
    def wrapped(m, x, router):
        logits, probs, gate, idx = route(m, x, router)
        held = ((idx >= m.first_held) & (idx < m.first_held + m.held))[:, -1]  # (B, K)
        k = jnp.argmax(held, axis=-1)
        drop = jax.nn.one_hot(k, m.top_k, dtype=bool) & jnp.any(held, -1, keepdims=True)
        return logits, probs, gate.at[:, -1].set(jnp.where(drop, 0.0, gate[:, -1])), idx
    return wrapped


@pytest.mark.parametrize("mutation", ["ignore_window", "plain_rope_on_full", "drop_held_pick"])
def test_mutations_fail_the_comparison(params, tokens, mutation, monkeypatch):
    cfg = CFG
    if mutation == "ignore_window":
        cfg = dataclasses.replace(CFG, sliding_window=None)
    elif mutation == "plain_rope_on_full":
        cfg = dataclasses.replace(CFG, rope_by_kind=())
    else:
        monkeypatch.setattr(moe_lib, "_route", _drop_last_held_pick(moe_lib._route))
    want = ref_logits(CFG, params, tokens)[S - 1:]
    got, _ = served_logits(cfg, params, tokens)
    assert err(got[0], want[0]) > 100 * TOL, mutation  # already the prefill
    assert err(got, want) > 100 * TOL, mutation


def test_skewed_router_drops_no_token(params):
    """A prompt of one repeated token routes every position of the first
    layer to the same top-k experts, far past the capacity C = S·k/E·1.25
    that the training dispatch keeps: the served prefill still matches the
    reference, and the capacity dispatch, which drops the overflow, does
    not."""
    prompt = np.concatenate([np.full(S - 8, 3), np.arange(8) + 100]).astype(np.int32)
    want = ref_logits(CFG, params, prompt)
    model = build_model(CFG)
    # the repeated positions are alike in every layer, so they route alike
    assert S - 8 > moe_lib._capacity(S, CFG.moe.top_k, CFG.moe.n_experts,
                                      CFG.moe.capacity_factor)
    logits, _ = model.prefill_jit(params, {"tokens": jnp.asarray(prompt[None])},
                                  model.init_cache(1, S))
    assert err(logits[0, -1], want[-1]) < TOL
    dropping, _ = model.forward(params, {"tokens": jnp.asarray(prompt[None])})
    assert err(dropping[0], want) > 100 * TOL


def test_four_shares_sum_to_the_uncut_layer():
    """Four chips holding 4 of the 16 experts each compute, between them,
    what the reference gives for the whole layer."""
    whole = dataclasses.replace(CFG, moe=dataclasses.replace(CFG.moe, n_held=0, first_held=0))
    mp = jax.tree.map(lambda a: a[0], make_params(whole, seed=3)["layers"]["mlp"])
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 12, CFG.d_model))
    parts = []
    for chip in range(4):
        cfg = dataclasses.replace(CFG, moe=dataclasses.replace(CFG.moe, n_held=4,
                                                               first_held=4 * chip))
        share = {k: v if k == "router" else v[4 * chip: 4 * chip + 4] for k, v in mp.items()}
        parts.append(moe_lib.apply_moe_dropless(cfg, share, x))
    with jax.default_matmul_precision("highest"):
        want = ref_moe(whole, mp, x[0])
    assert err(sum(parts)[0], want) < TOL
    assert err(moe_lib.apply_moe_dropless(whole, mp, x)[0], want) < TOL
    assert min(err(p[0], want) for p in parts) > 100 * TOL  # no share is the whole


@pytest.mark.parametrize("spread", ["even", "all_held"])
def test_grouped_rows_hold_every_held_pick(params, spread):
    """The prefill's grouped product runs on twice the held pairs that even
    routing gives, and on every pair when more are held: a router that
    sends every pick of every token to this chip's experts (4 of 4 held,
    past that first count) still matches the reference."""
    mp = jax.tree.map(lambda a: a[0], params["layers"]["mlp"])
    m = CFG.moe
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(5), (1, S, CFG.d_model)))
    if spread == "all_held":  # x > 0, so every held expert's logit rises by sum(x)
        held = jnp.arange(m.n_experts) // m.held == m.first_held // m.held
        mp = {**mp, "router": mp["router"] + jnp.where(held, 1.0, -1.0)}
    pairs = S * m.top_k
    rows = moe_lib._held_rows(m, pairs)
    assert pairs * m.held // m.n_experts < rows < pairs
    with jax.default_matmul_precision("highest"):
        want = ref_moe(CFG, mp, x[0])
    ids = jax.lax.top_k(x[0] @ mp["router"], m.top_k)[1]
    n_held = int(jnp.sum((ids >= m.first_held) & (ids < m.first_held + m.held)))
    assert (n_held > rows) == (spread == "all_held")
    assert err(moe_lib.apply_moe_dropless(CFG, mp, x)[0], want) < TOL


@pytest.mark.parametrize("S_,window,heads,kv", [(40, 16, 4, 2), (64, 8, 2, 1), (96, 32, 8, 2)])
def test_banded_window_attention_matches_masked_scores(S_, window, heads, kv):
    """A window layer's prefill computes its scores by blocks of a quarter of
    the window, over the window and the block: the same as the masked S²
    scores of ``kref.attention_ref``."""
    from repro.kernels import ref as kref
    from repro.models import attention

    bq = attention._band_block(S_, window)
    assert bq == window // 4
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(S_), 3)
    q = jax.random.normal(k1, (2, heads, S_, 16))
    k = jax.random.normal(k2, (2, kv, S_, 16))
    v = jax.random.normal(k3, (2, kv, S_, 16))
    got = attention._banded_attention(q, k, v, window, bq)
    want = kref.attention_ref(q, k, v, causal=True, window=window)
    assert err(got, want) < TOL
    assert err(got, kref.attention_ref(q, k, v, causal=True, window=window + 1)) > 100 * TOL
    # where the band is not narrower than the prompt, the masked scores run
    assert attention._band_block(window + bq, window) is None
