"""The program fault that keeps ``MinosServingEngine.serve`` out of the
window (PERF.md, Open questions): the serving backend decodes from the last
prompt token a second time, so its tokens continue ``prompt + [prompt[-1]]``.

In float32 at smoke size, program and reference agree to rounding, so the
engine's tokens match the reference exactly over the duplicated context and
not over the prompt, while the harness's model path and the program's own
``prefill_jit`` logits side with the reference. When the backend is mended
this test fails, and the engine's cells can come in."""
from bench import witness


def test_engine_tokens_continue_the_duplicated_prompt(tiny_root):
    rows = witness.witness("phi3-mini-3.8b.model-gen-batch", 5, 24, root=tiny_root,
                           allow_cpu=True)
    assert max(r["gap_dup"] for r in rows) == 0.0
    assert max(r["gap_model"] for r in rows) == 0.0
    assert max(r["gap_engine"] for r in rows) > 0.05
    assert all(r["first_prefill"] == r["first_ref"] for r in rows)
    assert any(r["first_served"] != r["first_ref"] for r in rows)
