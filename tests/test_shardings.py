"""Sharding-rule logic (pure functions — no 512-device mesh needed)."""
import jax
import pytest
from jax.sharding import AxisType, PartitionSpec as P

from repro.configs.registry import get_config
from repro.distributed.sharding import use_mesh, shard, logical_to_spec
from repro.launch.shardings import (
    add_fsdp_axes,
    batch_spec,
    cache_spec,
    dp_only_rules,
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


def test_fsdp_never_shards_layer_dim():
    spec = add_fsdp_axes(P(None, None, "model", None), (88, 12288, 96, 128),
                         MESH, ("data",))
    assert spec[0] is None  # leading (layer) dim untouched
    assert ("data",) in tuple(spec) or "data" in tuple(spec)


def test_dp_only_rules_cap_to_batch():
    rules = dp_only_rules(MESH, global_batch=256)
    assert rules["model"] is None and rules["ff"] is None
    assert rules["batch"] is not None


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
