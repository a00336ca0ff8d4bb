"""Whole runs of the harness on the CPU at smoke sizes: a sound run is
correct, a broken timed path is not, a chip is required, and a cell added
by files alone runs without an edit."""
import dataclasses
import hashlib
import json
import os
import subprocess
import sys
import time

import pytest

from conftest import copy_root

CELLS = ["phi3-mini-3.8b.model-gen-batch", "qwen3-0.6b.model-docs-batch"]


def run(root, workload, *, trace=False, fault=None, seconds=2.0, seed=2**32 + 7):
    from bench import harness

    return harness.run_cell(workload, seed, seconds, trace, t0=time.perf_counter(),
                            root=root, allow_cpu=True, fault=fault)


def token_altered(model):
    """Decode returns a token other than the one the model produced."""
    inner = model.decode_tokens

    def decode_tokens(params, cache, tok, n_steps):
        toks, cache = inner(params, cache, tok, n_steps)
        i = toks.shape[1] // 2
        return toks.at[:, i].set((toks[:, i] + 1) % model.cfg.vocab), cache

    return dataclasses.replace(model, decode_tokens=decode_tokens)


def state_unchanged(model):
    """Each decode step returns the cache it was given."""
    import jax
    from repro.models.model import greedy_token

    def decode_tokens(params, cache, tok, n_steps):
        def step(tok, _):
            logits, _ = model.decode_step(params, cache, tok)
            tok = greedy_token(logits)
            return tok, tok

        _, toks = jax.lax.scan(step, tok, None, length=n_steps)
        return toks[:, :, 0].T, cache

    fn = jax.jit(decode_tokens, static_argnames=("n_steps",))
    return dataclasses.replace(model, decode_tokens=fn)


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct(tiny_root, workload):
    res = run(tiny_root, workload)
    assert res["correct"], res["checks"]
    assert res["checks"]["served_gap_per_std"]["value"] == 0.0  # f32 program, f32 reference
    assert set(res["metrics"]) == {"tokens_per_s", "setup_s"}
    assert list(res)[-1] == "checks" and res["failed"] == 0 and res["attempted"] > 0


@pytest.mark.parametrize("fault", [token_altered, state_unchanged], ids=lambda f: f.__name__)
@pytest.mark.parametrize("workload", CELLS)
def test_broken_timed_path_is_not_correct(tiny_root, workload, fault):
    res = run(tiny_root, workload, fault=fault)
    assert not res["correct"]
    assert res["checks"]["served_gap_per_std"]["value"] > res["checks"]["served_gap_per_std"]["limit"]


def test_traced_run(tiny_root):
    res = run(tiny_root, CELLS[1], trace=True, seconds=3.0)
    assert res["correct"], res["checks"]
    assert {"busy_s", "window_s"} <= set(res["device"])
    assert "breakdown" in res
    # the CPU has no device trace: metrics that need one say nothing
    assert res["metrics"] == {}


def _run_script(root, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, os.path.join(root, "bench", "run.py"),
                           "--workload", CELLS[1], "--seed", "1", "--seconds", "1",
                           "--trace", "0", *args],
                          cwd=root, env=env, capture_output=True, text=True, timeout=300)


def test_no_chip_no_result(tiny_root):
    p = _run_script(tiny_root)
    assert p.returncode != 0 and "needs a TPU" in p.stderr
    assert not p.stdout.strip()


def test_fails_without_the_program(tmp_path):
    """A directory with only BENCHMARK.json and bench/ gives no result."""
    root = copy_root(str(tmp_path))
    os.remove(os.path.join(root, "src"))
    p = _run_script(root)
    assert p.returncode != 0 and not p.stdout.strip()


def _digest(root):
    out = {}
    for d, _, files in os.walk(os.path.join(root, "bench")):
        for f in files:
            p = os.path.join(d, f)
            out[os.path.relpath(p, root)] = hashlib.sha256(open(p, "rb").read()).hexdigest()
    return out


def test_new_config_traffic_and_metric_by_files_alone(tiny_root):
    """A later change adds a configuration, a traffic mix and a per-layer
    metric as new files and BENCHMARK.json entries; nothing else is edited."""
    before = _digest(tiny_root)
    b = os.path.join(tiny_root, "bench")
    cfg = json.load(open(os.path.join(b, "configs", "qwen3-0.6b.json")))
    cfg.update(name="toy-dense", arch_id="llama3.2-1b", qk_norm=False)
    cfg["hf_config"]["tie_word_embeddings"] = False
    json.dump(cfg, open(os.path.join(b, "configs", "toy-dense.json"), "w"))
    mix = json.load(open(os.path.join(b, "traffic", "model-gen-batch.json")))
    mix.update(prompt_lens=[12, 20], prompt_weights=[0.5, 0.5], schedule_seed=7)
    json.dump(mix, open(os.path.join(b, "traffic", "toy-mix.json"), "w"))
    with open(os.path.join(b, "metrics", "requests_traced.py"), "w") as f:
        f.write("def read(ctx):\n    return len(ctx.requests) or None\n")
    json.dump({"served_gap_per_std": 10.0, "sample_tokens": 32},
              open(os.path.join(b, "limits", "toy-dense.toy-mix.json"), "w"))
    doc = json.load(open(os.path.join(tiny_root, "BENCHMARK.json")))
    doc["configs"].append({"name": "toy-dense", "source": "test", "reduced": [], "why": "test",
                           "file": "bench/configs/toy-dense.json"})
    doc["workloads"].append({"name": "toy-dense.toy-mix", "config": "toy-dense",
                             "traffic": "toy-mix", "chips": 1, "why": "test"})
    doc["per_layer"].append({"name": "requests_traced", "unit": "requests", "better": "higher",
                             "source": "program_counter", "layer": "model decode",
                             "moves": "tokens_per_s", "workloads": ["toy-dense.toy-mix"]})
    json.dump(doc, open(os.path.join(tiny_root, "BENCHMARK.json"), "w"))
    after = _digest(tiny_root)
    assert all(after[k] == v for k, v in before.items())  # nothing edited
    res = run(tiny_root, "toy-dense.toy-mix", seconds=2.0)
    assert res["correct"] and {"tokens_per_s", "setup_s"} == set(res["metrics"])
    res = run(tiny_root, "toy-dense.toy-mix", trace=True, seconds=3.0)
    assert res["metrics"]["requests_traced"]["value"] > 0
