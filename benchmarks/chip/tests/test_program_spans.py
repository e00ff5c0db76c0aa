"""The readers of the program's own span record (``host_ms``,
``admit_share``, ``eager_bucket_share``, each in its ``.offline``,
``.qwen3-4b`` and ``.split3-1`` file): on a traced run of the tiny cell they read finite
values, and with no window opened in the process they read nothing.

    JAX_PLATFORMS=cpu python3 -m pytest -q benchmarks/chip/tests
"""
from __future__ import annotations

import math
import sys
import time
from pathlib import Path

import pytest

CHIP = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(CHIP), str(CHIP.parents[1] / "src"), str(CHIP / "tests")]

import run  # noqa: E402
import tiny  # noqa: E402
from chipbench import roofline, system  # noqa: E402
from repro.runtime import observe  # noqa: E402

SEED = 2**31 + 4099
READERS = [f"{q}.{cell}" for q in ("host_ms", "admit_share",
                                   "eager_bucket_share")
           for cell in ("offline", "qwen3-4b", "split3-1")]
UNITS = {"host_ms": "ms", "admit_share": "%", "eager_bucket_share": "%"}


def _reader(name):
    return system.load_module(CHIP / "metrics" / f"{name}.py").read


@pytest.fixture(scope="module")
def traced():
    spec = tiny.spec("offline")
    spec["per_layer"] = [{"name": n, "unit": UNITS[n.split(".")[0]]}
                         for n in READERS]
    v5e = roofline.peaks("TPU v5 lite")
    with pytest.MonkeyPatch.context() as mp:
        # the CPU has no entry in the peaks table; these readers use none
        mp.setattr(roofline, "peaks", lambda kind: v5e)
        return run.run_cell(spec, SEED, 1.0, True,
                            t_start=time.perf_counter())


@pytest.mark.parametrize("name", READERS)
def test_reader_finite_on_traced_run(traced, name):
    assert traced["correct"], traced["checks"]
    v = traced["metrics"][name]["value"]
    assert math.isfinite(v) and v >= 0.0
    if name.split(".")[0] != "host_ms":
        assert v <= 100.0


@pytest.mark.parametrize("name", READERS)
def test_reader_none_without_window(monkeypatch, name):
    monkeypatch.setattr(observe, "_LAST", observe.WindowRecord())
    assert _reader(name)({}) is None
