"""Helpers for the benchmark's own CPU tests (``python -m pytest bench/tests``).

``tiny_root`` copies ``bench/`` and ``BENCHMARK.json`` into a temporary
checkout, links the program's ``src/``, and shrinks every configuration and
traffic mix to a size the CPU runs in seconds: the same files and code
paths as a chip run, at smoke sizes.
"""
from __future__ import annotations

import json
import os
import shutil
import sys

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

# At this width an init of 0.02 leaves the next token nearly blind to the
# context (a decode that ignores its cache can read 0.0); 0.1 does not.
TINY_HF = dict(hidden_size=128, num_hidden_layers=2, num_attention_heads=4,
               num_key_value_heads=2, head_dim=32, intermediate_size=256, vocab_size=512,
               initializer_range=0.1)


def shrink(root: str, dtype: str = "float32") -> None:
    for name in os.listdir(os.path.join(root, "bench", "configs")):
        p = os.path.join(root, "bench", "configs", name)
        cfg = json.load(open(p))
        cfg["hf_config"].update(TINY_HF)
        cfg["dtype"] = dtype
        json.dump(cfg, open(p, "w"))
    for name in os.listdir(os.path.join(root, "bench", "traffic")):
        p = os.path.join(root, "bench", "traffic", name)
        mix = json.load(open(p))
        mix["prompt_lens"] = [8 * (i + 1) for i in range(len(mix["prompt_lens"]))]
        mix["output_lens"] = [8 * 2 ** (i % 2) for i in range(len(mix["output_lens"]))]
        json.dump(mix, open(p, "w"))


def copy_root(dst: str) -> str:
    shutil.copytree(os.path.join(REPO, "bench"), os.path.join(dst, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), dst)
    os.symlink(os.path.join(REPO, "src"), os.path.join(dst, "src"))
    return dst


@pytest.fixture
def tiny_root(tmp_path):
    root = copy_root(str(tmp_path))
    shrink(root)
    return root
