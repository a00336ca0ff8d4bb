"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.decode_attention import decode_attention
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


@pytest.mark.parametrize("batch,qh,kvh,S,d,block_k", [
    (2, 4, 2, 512, 64, 256),
    (1, 8, 8, 1024, 128, 128),
    (3, 4, 1, 256, 64, 64),
])
def test_decode_attention_sweep(batch, qh, kvh, S, d, block_k):
    q = _rand((batch, qh, 1, d), jnp.float32, 0)
    kc = _rand((batch, kvh, S, d), jnp.float32, 1)
    vc = _rand((batch, kvh, S, d), jnp.float32, 2)
    lengths = jnp.asarray(
        np.random.RandomState(3).randint(1, S + 1, size=batch), jnp.int32
    )
    out = decode_attention(q, kc, vc, lengths, block_k=block_k, interpret=True)
    want = ref.decode_attention_ref(q, kc, vc, lengths)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=2e-3, atol=2e-3)


def test_decode_attention_skips_empty_blocks():
    """length=1: only the first block contributes; result equals attending
    to position 0 only."""
    q = _rand((1, 2, 1, 64), jnp.float32, 0)
    kc = _rand((1, 2, 512, 64), jnp.float32, 1)
    vc = _rand((1, 2, 512, 64), jnp.float32, 2)
    out = decode_attention(q, kc, vc, jnp.array([1], jnp.int32), interpret=True)
    np.testing.assert_allclose(
        np.asarray(out)[0, :, 0], np.asarray(vc)[0, :, 0], rtol=1e-4, atol=1e-4
    )


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
    """A length the kernel cannot tile raises; it is never handed to the
    jnp oracle behind the caller's back."""
    if call == "flash":
        q = _rand((1, 4, 100, 64), jnp.float32, 0)
        kv = _rand((1, 2, 200, 64), jnp.float32, 1)
        with pytest.raises(ValueError, match="must divide"):
            ops.flash_attention(q, kv, kv, block_q=64, block_k=64)
    else:
        q = _rand((1, 4, 1, 64), jnp.float32, 0)
        kv = _rand((1, 2, 300, 64), jnp.float32, 1)
        with pytest.raises(ValueError, match="must divide"):
            ops.decode_attention(q, kv, kv, jnp.array([10], jnp.int32))


def test_interpret_mode_follows_operands():
    """Off a TPU the kernels run interpreted, for concrete and traced
    operands alike."""
    x = jnp.ones((8, 8))
    assert ops.runs_interpreted(x)
    assert jax.jit(lambda y: jnp.float32(ops.runs_interpreted(y)))(x) == 1.0
