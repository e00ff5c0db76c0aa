"""The trace reduction, on hand-made event lists and on a small recorded
trace (``data/trace_q2off.json.gz``: the first 0.3 s of the lists
``tracing.extract`` kept from a traced ``qwen2-1.5b.offline.p25`` run on
one TPU v5e, every event clipped to that span).

    JAX_PLATFORMS=cpu python3 -m pytest -q benchmarks/chip/tests
"""
from __future__ import annotations

import gzip
import json
import sys
from pathlib import Path

import pytest

CHIP = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(CHIP), str(CHIP.parents[1] / "src")]

import run  # noqa: E402
from chipbench import roofline, tracing  # noqa: E402
from chipbench.tracing import Reduced  # noqa: E402

RECORDED = Path(__file__).resolve().parent / "data" / "trace_q2off.json.gz"
# the same cell traced with the program's step spans in the trace
# (``data/trace_q2off_spans.json.gz``: the first 0.3 s of the lists
# ``tracing.extract`` kept from a traced run on one TPU v5e, with that
# run's window counts and counters)
RECORDED_SPANS = RECORDED.with_name("trace_q2off_spans.json.gz")
# the host spans that named idle gaps before the program's step spans
OLD_SPANS = ("finish_tick_sync", "stage2_bucket_dispatch", "pool_tick_fused",
             "pool_tick", "ring_enqueue", "bench_step", "bench_scan",
             "bench_sleep")


def test_hand_made_trace():
    tr = {"window": [0, 100],
          "chips": [{"ops": [[10, 20, "%fusion.1 = f32[8] fusion(..)"],
                             [15, 30, "%paged_gather_append_kernel.3"],
                             [50, 60, "%while.2"], [52, 58, "%copy.9"],
                             [90, 130, "%fusion.7"]],
                     "modules": [[5, 35, "jit__pool_tick_fused(12)"],
                                 [45, 100, "jit_s2_paged(3)"]]}],
          "host": [[0, 100, "bench_step"], [30, 50, "finish_tick_sync"]]}
    r = Reduced.of(tr)
    assert r.window_s == pytest.approx(100e-9)
    # union: [10, 30] + [50, 60] + [90, 100] (clipped to the window)
    assert r.busy_s == pytest.approx(40e-9)
    assert r.ops_matching("paged_gather_append_kernel") == (
        pytest.approx(15e-9), 1)
    assert "while" not in r.op_s                 # containers not ranked
    assert r.op_s["copy"] == pytest.approx(6e-9)
    assert r.modules_matching("pool_tick") == (pytest.approx(30e-9), 1)
    assert r.modules_matching("s2_paged") == (pytest.approx(55e-9), 1)
    # gaps [0,10], [30,50], [60,90]: the innermost covering span names each
    assert r.idle_by_host["finish_tick_sync"] == pytest.approx(20e-9)
    assert r.idle_by_host["bench_step"] == pytest.approx(40e-9)
    assert sum(r.idle_by_host.values()) == pytest.approx(
        r.window_s - r.busy_s)
    b = r.breakdown()
    assert b["device_ops"][0][0] == \
        "_pool_tick_fused:paged_gather_append_kernel"
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_op_and_module_names():
    assert tracing.op_name("%fusion.12 = (bf16[2]) fusion(x)") == "fusion"
    assert tracing.op_name("%broadcast_select_fusion = (bf16[1]) f") == \
        "broadcast_select_fusion"
    assert tracing.module_name("jit__pool_tick_fused(123)") == \
        "jit__pool_tick_fused"


@pytest.mark.skipif(not RECORDED.exists(), reason="no recorded trace")
def test_recorded_trace():
    with gzip.open(RECORDED, "rt") as f:
        tr = json.load(f)
    r = Reduced.of(tr)
    assert 0 < r.busy_s <= r.window_s
    assert sum(r.idle_by_host.values()) == pytest.approx(
        r.window_s - r.busy_s, rel=1e-6)
    assert r.modules_matching("pool_tick")[1] >= 1
    t, n = r.ops_matching("exit_decision")
    assert n >= 1 and t > 0


def test_step_spans_rename_idle_gaps_only():
    """The program's step spans among the host spans (``run.HOST_SPANS``)
    move the step's idle from ``bench_step`` to what the step was doing;
    the device ops, busy time, the idle total and every per-layer reading
    of the cell stay as they were."""
    with gzip.open(RECORDED_SPANS, "rt") as f:
        rec = json.load(f)
    tr = rec["raw"]
    assert set(OLD_SPANS) < set(run.HOST_SPANS)
    old_tr = dict(tr, host=[h for h in tr["host"] if h[2] in OLD_SPANS])
    new, old = Reduced.of(tr), Reduced.of(old_tr)
    for field in ("window_s", "busy_s", "busy_s_per_chip", "module_s",
                  "module_n", "op_s", "op_n", "op_in_module_s"):
        assert getattr(new, field) == getattr(old, field), field
    assert sum(new.idle_by_host.values()) == pytest.approx(
        sum(old.idle_by_host.values()), rel=1e-12)
    assert new.breakdown()["device_ops"] == old.breakdown()["device_ops"]
    new_names = {k for k, _ in new.breakdown()["idle_gaps"]}
    old_names = {k for k, _ in old.breakdown()["idle_gaps"]}
    assert new_names - old_names and new_names - old_names <= set(
        run.HOST_SPANS) - set(OLD_SPANS)
    assert new.idle_by_host.get("bench_step", 0.0) < \
        old.idle_by_host["bench_step"]

    spec = run.load_cell(rec["workload"], CHIP.parents[1])
    conf, mix = spec["conf"], spec["mix"]
    from chipbench import system
    dims = system.model_dims(conf, system.arch_config(conf))
    read = {}
    for red in (new, old):
        ctx = {"trace": red, "dims": dims, "conf": conf, "mix": mix,
               "peaks": roofline.peaks("TPU v5 lite"), "chips": 1,
               "occupancy": [], **rec["ctx"]}
        read[id(red)] = {m["name"]: system.load_module(
            CHIP / "metrics" / f"{m['name']}.py").read(ctx)
            for m in spec["per_layer"]}
    assert read[id(new)] == read[id(old)]
    assert any(v is not None for v in read[id(new)].values())
