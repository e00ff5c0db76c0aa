"""The float8 control in the program's place comes out not correct: at
smoke widths on the CPU, over the same served streams a sound run passes
on, the control's widest gap lies above the limit and the program's below.

    JAX_PLATFORMS=cpu python3 -m pytest -q benchmarks/chip/tests
"""
from __future__ import annotations

import sys
import time
from pathlib import Path

CHIP = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(CHIP), str(CHIP.parents[1] / "src"), str(CHIP / "tests")]

import run  # noqa: E402
import tiny  # noqa: E402

sys.path.insert(0, str(CHIP / "references"))
import qwen_dense  # noqa: E402


def test_fp8_control_fails_where_the_program_passes():
    out = run.run_cell(tiny.spec("offline"), 2**31 + 5, 1.0, False,
                       t_start=time.perf_counter(),
                       control=qwen_dense.fp8_matmul)
    limit = out["checks"]["max_logit_gap"]["limit"]
    assert out["correct"]
    assert out["checks"]["max_logit_gap"]["value"] <= limit
    assert out["control_max_logit_gap"] > limit
