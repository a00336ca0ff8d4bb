"""bench/flops.py against counts made by hand from the published shapes."""
import json
import os

import pytest

from bench import flops, spec

PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def counts(name):
    b = spec.Bench(spec.ROOT)
    cfg = json.load(open(os.path.join(b.dir, "configs", f"{name}.json")))
    return flops.for_config(cfg["family"], b.adapter(cfg["family"]).sizes(cfg))


def test_qwen3_by_hand():
    c = counts("qwen3-0.6b")
    # q, k+v (8 KV heads), o, and the SwiGLU MLP of one layer
    layer = 1024 * 16 * 128 + 2 * 1024 * 8 * 128 + 16 * 128 * 1024 + 3 * 1024 * 3072
    assert layer == 15_728_640 == c.layer_params
    assert c.head_params == 1024 * 151_936 == 155_582_464
    assert 28 * layer + 155_582_464 == 595_984_384  # the 0.6 B of the name, tied
    assert c.decode_step_flops(300) == 2 * 595_984_384 + 28 * 4 * 16 * 128 * 300
    assert c.prefill_flops(128) == (2 * 128 * 28 * layer + 28 * 4 * 16 * 128 * (128 * 129 // 2)
                                    + 2 * 155_582_464)
    # weights read by one decode step: layers with their norms (2 d + 2 hd of
    # qk-norm), the final norm, the tied head = the embedding table
    assert c.step_weight_bytes == 2 * (28 * (layer + 2048 + 256) + 1024 + 155_582_464)
    assert c.kv_row_bytes == 28 * 2 * 8 * 128 * 2 == 114_688
    assert c.decode_step_bytes(300) == c.step_weight_bytes + 114_688 * 301


def test_phi3_by_hand():
    c = counts("phi3-mini-3.8b")
    layer = 4 * 3072 * 32 * 96 + 3 * 3072 * 8192  # MHA: q, k, v, o all 32 x 96
    assert layer == 113_246_208 == c.layer_params
    assert 32 * layer + 3072 * 32_064 == 3_722_379_264  # plus a 98.5 M embedding: 3.8 B
    assert c.kv_row_bytes == 32 * 2 * 32 * 96 * 2 == 393_216
    # untied: the head (d x V) and one embedding row
    assert c.step_weight_bytes == 2 * (32 * (layer + 6144) + 3072 + 98_500_608 + 3072)
    assert c.decode_step_flops(1) == 2 * 3_722_379_264 + 32 * 4 * 32 * 96


@pytest.mark.parametrize("name", ["qwen3-0.6b", "phi3-mini-3.8b"])
def test_decode_is_bound_by_bandwidth_at_b1(name):
    c = counts(name)
    S, T = 256, 32
    by_bytes = sum(c.decode_step_bytes(x) for x in c.decode_ctx(S, T)) / PEAK["hbm_bytes_per_s"]
    assert c.decode_floor_s(S, T, PEAK) == pytest.approx(by_bytes)
    assert c.request_flops(S, T) > c.prefill_flops(S)


def test_qwen3_decode_floor_matches_the_weight_stream():
    """32 served tokens take the prefill's one and 31 decode steps, each
    reading 1.19 GB of weights (45.1 ms at 819 GB/s) and 15-18 MB of cache."""
    c = counts("qwen3-0.6b")
    assert list(c.decode_ctx(128, 32)) == list(range(129, 160))
    assert c.decode_floor_s(128, 32, PEAK) == pytest.approx(
        31 * c.step_weight_bytes / 819e9, rel=0.02)
