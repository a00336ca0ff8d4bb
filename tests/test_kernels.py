"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.decode_attention import decode_attention, decode_block
from repro.kernels.flash_attention import flash_attention, flash_blocks
from repro.kernels.matmul_probe import matmul


def _rand(shape, dtype, seed):
    x = np.random.RandomState(seed).randn(*shape)
    return jnp.asarray(x, dtype)


TOL = {jnp.float32: 2e-3, jnp.bfloat16: 5e-2}


@pytest.mark.parametrize("m,k,n", [(128, 512, 128), (256, 1024, 256), (128, 128, 384)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_matmul_shapes(m, k, n, dtype):
    a, b = _rand((m, k), dtype, 0), _rand((k, n), dtype, 1)
    out = matmul(a, b, interpret=True)
    want = ref.matmul_ref(a, b)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32),
        rtol=TOL[dtype], atol=TOL[dtype] * 10,
    )


def test_matmul_padding_path():
    """ops.matmul pads ragged shapes up to block multiples."""
    a, b = _rand((100, 300), jnp.float32, 2), _rand((300, 77), jnp.float32, 3)
    out = ops.matmul(a, b)
    np.testing.assert_allclose(np.asarray(out), np.asarray(a) @ np.asarray(b),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("batch,qh,kvh,seq,d", [
    (1, 4, 4, 128, 64),     # MHA
    (2, 8, 2, 256, 64),     # GQA 4:1
    (2, 4, 1, 128, 128),    # MQA
    (1, 16, 8, 256, 128),   # qwen3-0.6b's heads
    (1, 32, 32, 256, 96),   # phi3-mini-3.8b's heads
    (1, 32, 4, 256, 128),   # mellum2-12b-a2.5b's full layers
])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_sweep(batch, qh, kvh, seq, d, causal):
    q = _rand((batch, qh, seq, d), jnp.float32, 0)
    k = _rand((batch, kvh, seq, d), jnp.float32, 1)
    v = _rand((batch, kvh, seq, d), jnp.float32, 2)
    out = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64, interpret=True)
    want = ref.attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=2e-3, atol=2e-3)


def test_flash_attention_bf16():
    q = _rand((1, 4, 128, 64), jnp.bfloat16, 0)
    k = _rand((1, 2, 128, 64), jnp.bfloat16, 1)
    v = _rand((1, 2, 128, 64), jnp.bfloat16, 2)
    out = flash_attention(q, k, v, causal=True, interpret=True)
    want = ref.attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(want, np.float32),
                               rtol=5e-2, atol=5e-2)


@pytest.mark.parametrize("block_q,block_k", [(64, 128), (128, 64), (32, 128)])
def test_flash_attention_rectangular_blocks(block_q, block_k):
    """block_q != block_k: the causal skip and the clamped K/V index map
    name the right blocks on both sides of the diagonal."""
    q = _rand((1, 8, 256, 64), jnp.float32, 0)
    k = _rand((1, 2, 256, 64), jnp.float32, 1)
    v = _rand((1, 2, 256, 64), jnp.float32, 2)
    out = flash_attention(q, k, v, causal=True, block_q=block_q, block_k=block_k,
                          interpret=True)
    want = ref.attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("qh,kvh,d", [(16, 8, 128), (32, 32, 96), (32, 4, 128)])
def test_flash_attention_bf16_served_heads(qh, kvh, d):
    """bf16 q/k/v at the served models' heads, with the blocks the kernel
    picks from the shapes (several of them along 1,024 tokens)."""
    q = _rand((1, qh, 1024, d), jnp.bfloat16, 0)
    k = _rand((1, kvh, 1024, d), jnp.bfloat16, 1)
    v = _rand((1, kvh, 1024, d), jnp.bfloat16, 2)
    out = flash_attention(q, k, v, causal=True, interpret=True)
    want = ref.attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(want, np.float32),
                               rtol=5e-2, atol=5e-2)


@pytest.mark.parametrize("seq,group,d,want", [
    (4096, 2, 128, (512, 512)),    # qwen3-0.6b
    (1024, 1, 96, (512, 512)),     # phi3-mini-3.8b
    (256, 1, 96, (256, 256)),
    (4096, 8, 128, (128, 512)),    # mellum2-12b-a2.5b's full layers
    (300, 2, 128, (512, 512)),     # padded to one block
    (8, 4, 64, (128, 128)),
])
def test_flash_blocks_from_shapes(seq, group, d, want):
    bq, bk = flash_blocks(seq, group, d)
    assert (bq, bk) == want
    assert group * bq * (bk + d) * 4 <= 2.5 * 2**20


# (q_heads, kv_heads, head_dim): groups of 1 / 2 / 8, heads of 96 and 128
DECODE_HEADS = [(4, 4, 96), (4, 2, 128), (16, 2, 128), (8, 1, 96)]
# valid prefixes of a batch of two over 512 slots in blocks of 128: one slot
# and all of them (a full ring), a block's edge and one past it, a partial
# block and a block in the middle
DECODE_LENGTHS = [(1, 512), (128, 129), (300, 200)]


@pytest.mark.parametrize("cache", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("layer", ["first", "last"])
@pytest.mark.parametrize("lengths", DECODE_LENGTHS, ids=str)
@pytest.mark.parametrize("qh,kvh,d", DECODE_HEADS)
def test_decode_attention_over_a_stack(qh, kvh, d, lengths, layer, cache):
    """Layer 0 or L−1 of a stacked f32 or bf16 cache, read where it lies,
    with an f32 query (so an f32 output): as the reference over that
    layer's valid prefix. The tolerance of 1e-5 holds p at f32 for P·V:
    rounded to bf16 it moves the output by ~1e-3."""
    L, S = 3, 512
    q = _rand((2, qh, 1, d), jnp.float32, 0)
    kc = _rand((L, 2, kvh, S, d), cache, 1)
    vc = _rand((L, 2, kvh, S, d), cache, 2)
    at = 0 if layer == "first" else L - 1
    lengths = jnp.asarray(lengths, jnp.int32)
    out = decode_attention(q, kc, vc, lengths, jnp.int32(at), block_k=128, interpret=True)
    want = ref.decode_attention_ref(q, kc[at], vc[at], lengths)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("qh,kvh,d", [(16, 8, 128), (32, 32, 96), (32, 4, 128)])
def test_decode_attention_bf16_served_heads(qh, kvh, d):
    """bf16 at the served models' heads, with the block the kernel picks
    from the shapes: as the reference, up to the output's bf16 rounding."""
    S = 1024
    q = _rand((1, qh, 1, d), jnp.bfloat16, 0)
    kc = _rand((2, 1, kvh, S, d), jnp.bfloat16, 1)
    vc = _rand((2, 1, kvh, S, d), jnp.bfloat16, 2)
    lengths = jnp.array([700], jnp.int32)
    out = decode_attention(q, kc, vc, lengths, jnp.int32(1), interpret=True)
    want = ref.decode_attention_ref(q, kc[1], vc[1], lengths)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(want, np.float32),
                               rtol=1e-2, atol=1e-2)


@pytest.mark.parametrize("seq,kvh,d,want", [
    (8192, 8, 128, 1024),   # qwen3-0.6b: 8 blocks
    (2048, 32, 96, 256),    # phi3-mini-3.8b: 8 blocks
    (8192, 4, 128, 2048),   # mellum2-12b-a2.5b's full layers: 4 blocks
    (1024, 4, 128, 1024),   # mellum2-12b-a2.5b's window ring: one block
    (300, 2, 64, 256),      # does not divide: a block and a partial one
    (64, 2, 64, 64),        # shorter than the lanes: one block
])
def test_decode_block_from_shapes(seq, kvh, d, want):
    block = decode_block(seq, kvh, d, 2)
    assert block == want
    assert kvh * block * max(d, 128) * 2 <= 2 * 2**20


@pytest.mark.parametrize("cache", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("S", [300, 1000, 1500])
def test_decode_attention_ragged_cache(S, cache):
    """A cache length that its block does not divide: the last block reaches
    past the cache (padded with NaN when interpreted), and the output is
    the reference's for a prefix ending in the first block, one ending in
    the partial block and the whole cache."""
    qh, kvh, d = 16, 8, 128
    block = decode_block(S, kvh, d, jnp.dtype(cache).itemsize)
    assert S % block
    q = _rand((3, qh, 1, d), jnp.float32, 0)
    kc = _rand((2, 3, kvh, S, d), cache, 1)
    vc = _rand((2, 3, kvh, S, d), cache, 2)
    lengths = jnp.array([block - 1, S - 3, S], jnp.int32)
    out = decode_attention(q, kc, vc, lengths, jnp.int32(1), interpret=True)
    want = ref.decode_attention_ref(q, kc[1], vc[1], lengths)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=1e-5, atol=1e-5)


def test_ops_decode_reads_one_layer_or_a_stack():
    """``ops.decode_attention`` over one layer's cache, and over a stack with
    ``layer``, kernel and reference alike."""
    q = _rand((1, 4, 1, 64), jnp.float32, 0)
    kc = _rand((2, 1, 2, 256, 64), jnp.float32, 1)
    vc = _rand((2, 1, 2, 256, 64), jnp.float32, 2)
    lengths = jnp.array([200], jnp.int32)
    want = ref.decode_attention_ref(q, kc[1], vc[1], lengths)
    for got in (ops.decode_attention(q, kc[1], vc[1], lengths),
                ops.decode_attention(q, kc, vc, lengths, layer=jnp.int32(1)),
                ops.decode_attention(q, kc, vc, lengths, layer=jnp.int32(1), use_pallas=False)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


def test_ops_fallback_matches_kernel():
    """use_pallas=False (the pjit-safe path) agrees with the kernel path."""
    q = _rand((1, 4, 128, 64), jnp.float32, 0)
    k = _rand((1, 2, 128, 64), jnp.float32, 1)
    v = _rand((1, 2, 128, 64), jnp.float32, 2)
    a = ops.flash_attention(q, k, v, use_pallas=True)
    b = ops.flash_attention(q, k, v, use_pallas=False)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("call", ["flash", "decode"])
def test_ops_raise_on_untileable_shapes(call):
    """A shape the kernel cannot tile raises; it is never handed to the
    jnp oracle behind the caller's back."""
    if call == "flash":
        q = _rand((1, 4, 100, 64), jnp.float32, 0)
        kv = _rand((1, 2, 200, 64), jnp.float32, 1)
        with pytest.raises(ValueError, match="must divide"):
            ops.flash_attention(q, kv, kv, block_q=64, block_k=64)
    else:  # any cache length tiles; query heads that no KV head count divides do not
        q = _rand((1, 3, 1, 64), jnp.float32, 0)
        kv = _rand((1, 2, 300, 64), jnp.float32, 1)
        with pytest.raises(ValueError, match="not a multiple"):
            ops.decode_attention(q, kv, kv, jnp.array([10], jnp.int32))


def test_interpret_mode_follows_operands():
    """Off a TPU the kernels run interpreted, for concrete and traced
    operands alike."""
    x = jnp.ones((8, 8))
    assert ops.runs_interpreted(x)
    assert jax.jit(lambda y: jnp.float32(ops.runs_interpreted(y)))(x) == 1.0
