"""chip_smoke.py's phases at the smoke size on the CPU, so the script that
proves the system on a TPU cannot rot between chip runs; and its refusal to
report success anywhere but on a TPU."""
import dataclasses
import importlib.util
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from repro.configs.registry import get_smoke_config
from repro.launch.serve import make_requests

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_phases_at_smoke_size(chip_smoke, capsys):
    # bf16 as served on the chip, at the smoke widths
    cfg = dataclasses.replace(get_smoke_config(chip_smoke.ARCH), dtype="bfloat16")
    run = chip_smoke.phase_serve(cfg, n_requests=3, prompt_len=16,
                                 max_new_tokens=4)
    assert run.engine.jit_stats["eager_calls"] == 0
    assert all(r.wall_ms > 0 for r in run.results)
    prompt = make_requests(cfg, 1, prompt_len=16, max_new_tokens=4)[0].prompt
    errs = chip_smoke.phase_correctness(run, prompt, max_new_tokens=4)
    # bf16 really differs from float32 here, within the tolerances
    assert 0 < errs["prefill"]["rel_rms_err"] <= chip_smoke.REL_RMS_TOL
    assert 0 < errs["decode"]["rel_rms_err"] <= chip_smoke.REL_RMS_TOL
    assert chip_smoke.phase_probe(128)["compiled"] is False  # no TPU here
    assert chip_smoke.phase_scan()["lanes"] > 0
    assert '"ok"' not in capsys.readouterr().out


def test_refuses_without_tpu(chip_smoke, capsys):
    assert chip_smoke.main([]) != 0
    out = capsys.readouterr().out
    assert "platform=cpu" in out and '"ok"' not in out


def test_fails_outside_a_checkout(tmp_path):
    """Alone in a directory, the script cannot import the system and must
    not print a result."""
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                          env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
