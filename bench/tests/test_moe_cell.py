"""The ``moe`` family's files (adapter, reference, counts, the two roofline
readers): whole runs of the harness on the CPU at smoke size, through a
tiny cell that exists only in the test's copy of the benchmark, the counts
by hand at the published size, and the readers on a trace worked out by
hand."""
import json
import os
import types

import pytest

from bench import flops, spec
from conftest import REPO
from test_harness import run, state_unchanged, token_altered
from test_trace import MS, REQUESTS, TRACE

CELL = "mellum2-12b-a2.5b.tiny"
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _dump(obj, root, *parts):
    with open(os.path.join(root, *parts), "w") as f:
        json.dump(obj, f)


@pytest.fixture
def moe_root(tiny_root):
    """One period of 3 window + 1 full layers at smoke widths, a window of 8
    that every prompt of the cell (8-24 tokens) reaches or passes, and 4
    held of 8 routed experts, top-2; the cell, its mix and its limits are
    added to the copy."""
    p = os.path.join(tiny_root, "bench", "configs", "mellum2-12b-a2.5b.json")
    cfg = json.load(open(p))
    cfg.update(hidden_size=128, num_hidden_layers=4, num_attention_heads=4,
               num_key_value_heads=2, head_dim=32, vocab_size=512, moe_intermediate_size=64,
               num_experts=4, held_expert_first=2, num_experts_per_tok=2, sliding_window=8,
               initializer_range=0.1, layer_types=cfg["layer_types"][:4])
    cfg["hf_config"]["num_experts"] = 8
    _dump(cfg, p)
    _dump({"arrival": {"kind": "backlog", "count": 2000},
           "prompt_lens": [8, 16, 24], "prompt_weights": [0.4, 0.3, 0.3],
           "output_lens": [8, 16], "output_weights": [0.5, 0.5],
           "block": 20, "schedule_seed": 7}, tiny_root, "bench", "traffic", "code-tiny.json")
    _dump({"served_gap_per_std": 0.2, "sample_tokens": 300},
          tiny_root, "bench", "limits", f"{CELL}.json")
    doc = json.load(open(os.path.join(tiny_root, "BENCHMARK.json")))
    doc["configs"].append({"name": "mellum2-12b-a2.5b",
                           "file": "bench/configs/mellum2-12b-a2.5b.json"})
    doc["workloads"].append({"name": CELL, "config": "mellum2-12b-a2.5b",
                             "traffic": "code-tiny", "chips": 1})
    _dump(doc, tiny_root, "BENCHMARK.json")
    return tiny_root


def test_sound_run_is_correct(moe_root):
    res = run(moe_root, CELL)
    assert res["correct"], res["checks"]
    assert res["checks"]["served_gap_per_std"]["value"] == 0.0  # f32 program, f32 reference
    assert set(res["metrics"]) == {"tokens_per_s", "setup_s"}
    assert res["failed"] == 0 and res["attempted"] > 0


@pytest.mark.parametrize("fault", [token_altered, state_unchanged], ids=lambda f: f.__name__)
def test_broken_timed_path_is_not_correct(moe_root, fault):
    res = run(moe_root, CELL, fault=fault)
    assert not res["correct"]
    assert res["checks"]["served_gap_per_std"]["value"] > res["checks"]["served_gap_per_std"]["limit"]


def test_counts_at_the_published_size():
    b = spec.Bench(REPO)
    cfg = json.load(open(os.path.join(b.dir, "configs", "mellum2-12b-a2.5b.json")))
    s = b.adapter("moe").sizes(cfg)
    c = flops.for_config("moe", s)
    assert (s["E"], s["E_held"], s["E_first"], s["top_k"]) == (64, 16, 0, 8)
    assert s["kinds"] == ("window", "window", "window", "full") * 7
    assert c.picks == 2.0  # 8 picks of 64 experts, 16 of them here
    # q and o 2304 x 32 x 128, k and v 2304 x 4 x 128; one expert 3 x 2304 x 896
    assert c.attn_params == 2 * 2304 * 4096 + 2 * 2304 * 512
    assert c.expert_params == 3 * 2304 * 896
    assert c.experts_touched(1) == pytest.approx(2.0)
    assert c.experts_touched(2048) == pytest.approx(16.0)
    # one token through a layer, then causal pairs: all for the 7 full layers,
    # at most 1,024 keys a query for the 21 window layers
    per_token = 2 * (c.attn_params + 2304 * 64 + 2 * c.expert_params)
    window_pairs = 1024 * 1025 // 2 + (2048 - 1024) * 1024
    assert c.prefill_flops(2048) == pytest.approx(
        2048 * 28 * per_token + 4 * 32 * 128 * (7 * 2048 * 2049 // 2 + 21 * window_pairs)
        + 2 * 2304 * 98304)
    # the prefill floors are bound by FLOPs: 23.6 / 36.8 / 50.6 ms
    for S, ms in ((2048, 23.6), (3072, 36.8), (4096, 50.6)):
        assert c.prefill_floor_s(S, PEAK) * 1e3 == pytest.approx(ms, abs=0.05)
        assert c.prefill_flops(S) / PEAK["bf16_flops_per_s"] == c.prefill_floor_s(S, PEAK)
    # a decode step is bound by bytes: 2.42 GB at 2,049 keys, 2.95 ms
    assert c.decode_step_bytes(2049) / 1e9 == pytest.approx(2.418, abs=0.001)
    assert c.decode_floor_s(2048, 2, PEAK) * 1e3 == pytest.approx(2.952, abs=0.001)


def test_the_cell_serves_the_code_mix():
    b = spec.Bench(REPO)
    cell = b.workload("mellum2-12b-a2.5b.model-code-batch")
    assert (cell["config"], cell["chips"]) == ("mellum2-12b-a2.5b", 1)
    mix = b.traffic(cell["traffic"])
    assert mix["arrival"] == {"kind": "backlog", "count": 2000} and mix["block"] == 20
    assert (mix["prompt_lens"], mix["prompt_weights"]) == ([2048, 3072, 4096], [0.4, 0.3, 0.3])
    assert (mix["output_lens"], mix["output_weights"]) == ([16, 32, 64], [0.4, 0.4, 0.2])
    assert [m["name"] for m in b.per_layer(cell["name"])] == [
        "moe_decode_roofline", "moe_prefill_roofline"]


def test_rooflines_by_hand():
    """``test_trace``'s two requests read with the MoE counts: prefill 3 ms
    of device time, decode 8 ms; a trace without device events reads
    nothing."""
    b = spec.Bench(REPO)
    cfg = json.load(open(os.path.join(b.dir, "configs", "mellum2-12b-a2.5b.json")))
    c = flops.for_config("moe", b.adapter("moe").sizes(cfg))
    ctx = types.SimpleNamespace(trace=TRACE, window=(0.0, 20 * MS), requests=REQUESTS,
                                peak=PEAK, counts=c)
    decode = c.decode_floor_s(1024, 32, PEAK) + c.decode_floor_s(2048, 64, PEAK)
    prefill = c.prefill_floor_s(1024, PEAK) + c.prefill_floor_s(2048, PEAK)
    assert b.metric_reader("moe_decode_roofline").read(ctx) == pytest.approx(100 * decode / 8e-3)
    assert b.metric_reader("moe_prefill_roofline").read(ctx) == pytest.approx(
        100 * prefill / 3e-3)
    empty = types.SimpleNamespace(**{**vars(ctx), "trace": {**TRACE, "ops": [], "modules": []}})
    for name in ("moe_decode_roofline", "moe_prefill_roofline"):
        assert b.metric_reader(name).read(empty) is None
