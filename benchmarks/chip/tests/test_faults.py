"""The whole run of a cell, past the harness's look for a chip, at smoke
widths on the CPU: a sound run comes out ``correct``, and each fault the
cell can have, planted underneath the timed path, makes it come out not
correct.

Faults a served model can have here: a token altered where it is produced
(the tick's greedy token of the exit head; a stage-2 bucket's greedy
token), a step that returns its state unchanged (the stage-1 tick's KV
cache; the stage-2 bucket's page pool), and an exit decision taken at
another threshold than the configuration states. The 3+1 split cell
exchanges hidden rows between chips, stage 1's submesh to stage 2's chip;
there the exchange left out (stage 2 receives zeros in place of the rows)
is planted at the hop, on four host devices (``conftest.py``).

    JAX_PLATFORMS=cpu python3 -m pytest -q benchmarks/chip/tests
"""
from __future__ import annotations

import copy
import sys
import time
from pathlib import Path

import jax.numpy as jnp
import pytest

CHIP = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(CHIP), str(CHIP.parents[1] / "src"), str(CHIP / "tests")]


import run  # noqa: E402
import tiny  # noqa: E402
from repro.core import early_exit as ee  # noqa: E402
from repro.runtime import scheduler as SCH  # noqa: E402

SEED = 2**31 + 977


def _run(kind="offline", c_thr=None, **mix):
    return run.run_cell(tiny.spec(kind, mix, c_thr), SEED, 1.0, False,
                        t_start=time.perf_counter())


def _altered_tick_token(mp):
    orig = SCH._pool_tick_fused

    def tick(*a, **k):
        out = list(orig(*a, **k))
        out[7] = (out[7] + 1) % tiny.TINY["vocab_size"]    # emitted pred
        return tuple(out)
    mp.setattr(SCH, "_pool_tick_fused", tick)


def _altered_bucket_token(mp):
    orig = SCH._greedy_row
    mp.setattr(SCH, "_greedy_row",
               lambda logits: (orig(logits) + 1) % tiny.TINY["vocab_size"])


def _stale_stage1_cache(mp):
    orig = ee.stage1_decode

    def s1(params, cfg, spec, token, caches, step):
        h, _, logits = orig(params, cfg, spec, token, caches, step)
        return h, caches, logits
    mp.setattr(ee, "stage1_decode", s1)


def _stale_stage2_pool(mp):
    orig = ee.stage2_decode

    def s2(params, cfg, spec, h, caches, step, **kw):
        logits, _ = orig(params, cfg, spec, h, caches, step, **kw)
        return logits, caches
    mp.setattr(ee, "stage2_decode", s2)


def _exchange_left_out(mp):
    orig = SCH.RingQueue.enqueue

    def enqueue(self, slab_tree, *a, **k):    # the stage-1 -> stage-2 hop
        slab_tree = dict(slab_tree, h=jnp.zeros_like(slab_tree["h"]))
        return orig(self, slab_tree, *a, **k)
    mp.setattr(SCH.RingQueue, "enqueue", enqueue)


def _shifted_exit_threshold(mp):
    orig = SCH._pool_tick_fused

    def tick(*a, **k):                  # c_thr, the ninth argument, x 1.6
        return orig(*a[:8], a[8] * 1.6, *a[9:], **k)
    mp.setattr(SCH, "_pool_tick_fused", tick)


FAULTS = {"altered_tick_token": _altered_tick_token,
          "altered_bucket_token": _altered_bucket_token,
          "stale_stage1_cache": _stale_stage1_cache,
          "stale_stage2_pool": _stale_stage2_pool}


@pytest.mark.parametrize("kind", ["offline", "chat"])
def test_sound_run_is_correct(kind):
    out = _run(kind)
    assert out["correct"], out["checks"]
    assert list(out)[-1] == "checks"


# a stale stage-2 pool only shows where one stream holds several hard
# tokens, so that fault runs with most tokens hard and longer outputs
MIX = {"stale_stage2_pool": {
    "c_thr": 0.04, "p_hard": 0.75,
    "output": {"median": 12, "sigma": 0.3, "min": 8, "max": 16}}}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_is_not_correct(fault, monkeypatch):
    FAULTS[fault](monkeypatch)
    out = _run(**MIX.get(fault, {}))
    assert not out["correct"], out["checks"]
    assert out["checks"]["max_logit_gap"]["value"] > \
        out["checks"]["max_logit_gap"]["limit"]


def test_shifted_threshold_reads_as_flipped_decisions(monkeypatch):
    """An exit decision taken at another threshold than the configuration
    states sends tokens to the wrong head far outside the band."""
    _shifted_exit_threshold(monkeypatch)
    out = _run()
    assert not out["correct"], out["checks"]
    assert out["checks"]["decision_flip"]["value"] > \
        out["checks"]["decision_flip"]["limit"]


def _split_run():
    """The tiny cell placed as the 3+1 split cell is: a 12-slot pool over
    stage 1's three devices, the paged pool, ring and buckets on the
    fourth."""
    conf = copy.deepcopy(tiny.TINY)
    conf["serving"].update(n_slots=12, n_pages=96,
                           split={"chips1": 3, "chips2": 1})
    spec = tiny.spec("offline", conf=conf)
    spec["cell"]["chips"] = 4
    return run.run_cell(spec, SEED, 1.0, False, t_start=time.perf_counter())


def test_split_sound_run_is_correct(monkeypatch):
    composed = []
    orig = SCH.ContinuousScheduler._tick_composed

    def tick(self):
        composed.append(self.placement.disaggregated)
        return orig(self)
    monkeypatch.setattr(SCH.ContinuousScheduler, "_tick_composed", tick)
    out = _split_run()
    assert out["correct"], out["checks"]
    assert out["device"]["count"] == 4
    assert composed and all(composed)        # the split's own tick ran
    assert 0.05 < out["realized_q"] < 0.95   # and rows crossed the hop


def test_split_exchange_left_out_is_not_correct(monkeypatch):
    _exchange_left_out(monkeypatch)
    out = _split_run()
    assert not out["correct"], out["checks"]
    assert out["checks"]["max_logit_gap"]["value"] > \
        out["checks"]["max_logit_gap"]["limit"]
