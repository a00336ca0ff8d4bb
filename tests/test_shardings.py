"""Sharding-rule logic (pure functions on a one-device mesh), and decode
run under the rules on a mesh of four host devices."""
import json
import os
import pathlib
import subprocess
import sys

import jax
import pytest
from jax.sharding import AxisType, PartitionSpec as P

from repro.configs.registry import get_config
from repro.distributed.sharding import use_mesh, shard, logical_to_spec
from repro.launch.shardings import (
    batch_spec,
    cache_spec,
    make_rules,
    param_spec,
)

MESH = jax.make_mesh((1, 1), ("data", "model"))  # shape-logic only


def test_make_rules_divisibility_guards():
    cfg = get_config("whisper-small")  # heads 12, vocab 51865: both indivisible by 16
    # emulate a 16-wide model axis by checking the rule predicate directly
    assert cfg.n_heads % 16 != 0 and cfg.vocab % 16 != 0
    cfg2 = get_config("llama3.2-1b")   # heads 32, kv 8
    assert cfg2.n_heads % 16 == 0 and cfg2.n_kv_heads % 16 != 0


def test_param_spec_patterns():
    cfg = get_config("llama3.2-1b")
    assert param_spec("embed", (128256, 2048), cfg, MESH) == P("model", None)
    assert param_spec("layers/attn/wq", (16, 2048, 32, 64), cfg, MESH) == \
        P(None, None, "model", None)
    assert param_spec("layers/mlp/w_down", (16, 8192, 2048), cfg, MESH) == \
        P(None, "model", None)
    assert param_spec("final_norm", (2048,), cfg, MESH) == P(None)


def test_param_spec_moe_experts():
    cfg = get_config("deepseek-moe-16b")
    spec = param_spec("layers/mlp/w_gate", (28, 64, 2048, 1408), cfg, MESH)
    assert spec == P(None, "model", None, None)  # expert-sharded
    spec = param_spec("layers/mlp/shared/w_gate", (28, 2048, 2816), cfg, MESH)
    assert spec == P(None, None, "model")        # dense shared expert


def test_guard_drops_indivisible():
    cfg = get_config("whisper-small")
    mesh16 = jax.make_mesh((1, 1), ("data", "model"))
    # vocab 51865 is odd -> any model sharding on it must be dropped when
    # the axis size doesn't divide; with axis size 1 everything divides.
    spec = param_spec("embed", (51865, 768), cfg, mesh16)
    assert spec == P("model", None)  # size-1 axis always divides


def test_cache_spec_kv_head_fallbacks():
    # size-1 model axis: kv always divides -> kv-head branch
    llama = get_config("llama3.2-1b")
    spec = cache_spec("k", (16, 128, 8, 32768, 64), llama, MESH, ("data",))
    assert spec[2] == "model" and spec[3] is None
    ds = get_config("deepseek-moe-16b")  # kv=16: shard kv heads
    spec = cache_spec("k", (28, 128, 16, 32768, 128), ds, MESH, ("data",))
    assert spec[2] == "model"
    # a 16-wide model axis with kv=8 must fall through to head_dim — check
    # the branch predicate directly (can't build a 256-device mesh here)
    assert llama.n_kv_heads % 16 != 0 and llama.head_dim % 16 == 0


@pytest.mark.parametrize("kind", ["window", "full"])
def test_cache_spec_reads_a_stack_under_its_kind(kind):
    """A transformer's cache keeps "k" and "v" by kind of layer: the stack
    under "k/<kind>" is sharded as "k" is."""
    cfg = get_config("mellum2-12b-a2.5b")
    shape = (21, 128, 4, 1024, 128)
    assert cache_spec(f"k/{kind}", shape, cfg, MESH, ("data",)) == \
        cache_spec("k", shape, cfg, MESH, ("data",))
    assert cache_spec(f"v/{kind}", shape, cfg, MESH, ("data",))[2] == "model"


def test_batch_spec():
    assert batch_spec("tokens", (256, 4096), MESH, ("data",)) == \
        P(("data",), None)


def test_shard_divisibility_guard_noop():
    """shard() drops axes the dim doesn't divide — a seq constraint on a
    1-token decode tensor must be harmless."""
    import jax.numpy as jnp
    mesh = jax.make_mesh((1,), ("model",), axis_types=(AxisType.Auto,))
    with use_mesh(mesh, {"seq": "model", "batch": None}):
        x = jnp.ones((2, 1, 8))
        y = shard(x, "batch", "seq", None)  # seq dim of size 1
        assert y.shape == x.shape


def test_logical_to_spec_respects_rules():
    mesh = jax.make_mesh((1,), ("model",), axis_types=(AxisType.Auto,))
    with use_mesh(mesh, {"heads": "model", "batch": None}):
        assert logical_to_spec("batch", None, "heads", None) == \
            P(None, None, "model", None)


# Decode of a smoke qwen3 on one device and on a data 1 x model 4 mesh of
# host devices, in a fresh interpreter: the device count is fixed when jax
# first starts. Prints the sharded cache's spec, whether the tokens agree
# and the largest difference of the final caches.
_DECODE_ON_A_MESH = """
import dataclasses, json, sys
import jax, jax.numpy as jnp
from jax.sharding import AxisType
from repro.configs.registry import get_smoke_config
from repro.distributed.sharding import use_mesh
from repro.launch.shardings import make_rules, shard_inputs, shard_params_like
from repro.models.model import build_model

n_kv_heads, head_dim = map(int, sys.argv[1:])
cfg = dataclasses.replace(get_smoke_config("qwen3-0.6b"),
                          n_kv_heads=n_kv_heads, head_dim=head_dim)
model = build_model(cfg)
params = model.init(jax.random.PRNGKey(0))
prompt = jax.random.randint(jax.random.PRNGKey(1), (1, 8), 0, cfg.vocab)
_, cache = model.prefill_jit(params, {"tokens": prompt}, model.init_cache(1, 32))
tok = prompt[:, -1:]
toks, out = model.decode_tokens(params, cache, tok, n_steps=12)

mesh = jax.make_mesh((1, 4), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
put = lambda tree, like: jax.tree.map(lambda a, s: jax.device_put(a, s.sharding), tree, like)
with use_mesh(mesh, make_rules(cfg, mesh)):
    params_m = put(params, shard_params_like(params, cfg, mesh))
    _, like = shard_inputs(cfg, mesh, {"batch": {}, "cache": cache})
    cache_m = put(cache, like)
    toks_m, out_m = build_model(cfg).decode_tokens(params_m, cache_m, tok, n_steps=12)
print(json.dumps({
    "spec": [a if a is None or isinstance(a, str) else list(a)
             for a in cache_m["k"]["full"].sharding.spec],
    "tokens_agree": bool((toks == toks_m).all()),
    "max_diff": max(float(jnp.abs(a - b).max())
                    for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(out_m))),
}))
"""


@pytest.mark.parametrize("n_kv_heads,head_dim,spec", [
    pytest.param(4, 64, [None, "data", "model", None, None], id="kv_heads"),
    pytest.param(2, 64, [None, "data", None, None, "model"], id="head_dim"),
    pytest.param(2, 30, [None, "data", None, "model", None], id="length"),
])
def test_sharded_decode_matches_one_device(n_kv_heads, head_dim, spec):
    """The one decode path under a mesh: with the cache sharded by each of
    ``cache_spec``'s fallbacks (kv heads, head_dim, length) over four model
    shards, decode gives the tokens and the final cache it gives on one
    device."""
    repo = pathlib.Path(__file__).resolve().parents[1]
    env = dict(os.environ, PYTHONPATH=str(repo / "src"), JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run([sys.executable, "-c", _DECODE_ON_A_MESH, str(n_kv_heads),
                        str(head_dim)], capture_output=True, text=True, timeout=300,
                       env=env, cwd=repo)
    assert r.returncode == 0, r.stderr[-4000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["spec"] == spec
    assert out["tokens_agree"]
    assert out["max_diff"] < 1e-4
