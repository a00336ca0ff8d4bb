"""The check's control at a size a CPU test holds: the served bf16 program
passes, and the same run judged by the control (the reference computed in
float8, through the harness's own check and verdict) comes out not correct,
reading several times what the program reads. The limit here is one for the
smoke size (SMOKE_LIMIT, between the two readings there); the cells' limits
are set from readings at full size on the chip (``bench/control.py``)."""
import json
import os
import time

import pytest

from conftest import copy_root, shrink

SMOKE_LIMIT = 0.2  # at this size the program reads 0.014-0.059, the control 0.53-0.74


@pytest.mark.parametrize("workload", ["phi3-mini-3.8b.model-gen-batch",
                                      "qwen3-0.6b.model-docs-batch"])
def test_control_is_not_correct_where_the_program_is(tmp_path, workload):
    from bench import harness

    root = copy_root(str(tmp_path))
    shrink(root, dtype="bfloat16")
    path = os.path.join(root, "bench", "limits", f"{workload}.json")
    json.dump(dict(json.load(open(path)), served_gap_per_std=SMOKE_LIMIT), open(path, "w"))
    for seed in (0, 1, 2):
        res = harness.run_cell(workload, seed, 2.0, False, t0=time.perf_counter(), root=root,
                               allow_cpu=True, control=True)
        program = res["program_checks"]["served_gap_per_std"]
        control = res["checks"]["served_gap_per_std"]
        assert harness.passes(res["program_checks"]), res["program_checks"]
        assert not res["correct"] and control["value"] > control["limit"]
        assert control["value"] >= 5 * program["value"]
