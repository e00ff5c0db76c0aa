"""Serving-pipeline throughput: host-loop vs device-resident server.

Measures end-to-end samples/sec of the seed's per-sample host-loop server
(``runtime.serve_loop.HostLoopServer``: per-row host syncs + Python deque +
per-bucket restacking) against the device-resident ``TwoStageServer``
(fused exit decision + compaction through ``kernels.dispatch``, device ring
buffer, async bucket drains) across hard-sample rates q ∈ {0.1, 0.3, 0.5}.
C_thr is calibrated per q on the exit-head confidences so realized q matches
the target, and the stage-2 bucket is sized at ceil(q·B) — the paper's
matched p=q operating point.

Both servers share the same jitted stage callables, so the delta is purely
the exit machinery — the thing ATHEENA keeps on-chip. Run via
``PYTHONPATH=src python -m benchmarks.run --only serve_pipeline [--json]``.

When >= 2 devices are visible (CI runs under
``XLA_FLAGS=--xla_force_host_platform_device_count=8``), each q also
builds the STAGE-DISAGGREGATED server — stage 1 / stage 2 on disjoint
submeshes, chips apportioned q-proportionally unless ``--chips1/--chips2``
override — and enforces bitwise parity against the single-device server
BEFORE timing; per-stage device counts and occupancy ride in the ``--json``
envelope so the perf trajectory captures the apportionment.
"""
from __future__ import annotations

import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import table
from repro.core import early_exit as ee
from repro.core import exit_decision as ed
from repro.core.stage_mesh import StageMeshPlan
from repro.models.config import ArchConfig
from repro.runtime import serve_loop as SL
from repro.runtime.stage_executor import StagePlacement

Q_GRID = (0.1, 0.3, 0.5)


def make_disagg_placement(p: float, chips1: Optional[int] = None,
                          chips2: Optional[int] = None
                          ) -> Optional[StagePlacement]:
    """Disaggregated placement for the parity gate: explicit chip counts
    when given, else the p-proportional apportionment. None when the host
    exposes a single device (the check is then vacuous and recorded so)."""
    n = jax.device_count()
    if n < 2:
        return None
    return StagePlacement.from_plan(StageMeshPlan.resolve(p, n, chips1,
                                                          chips2))


def _bench_cfg() -> ArchConfig:
    """Small enough that the exit machinery (the thing under test) is a
    visible share of the batch period on CPU; the model compute itself is
    identical between the two servers."""
    return ArchConfig(
        name="serve-bench", family="dense", n_layers=4, d_model=32,
        n_heads=4, n_kv_heads=2, d_ff=64, vocab=256,
        dtype="float32", param_dtype="float32", tie_embeddings=True,
    )


def _time_serve(make_server, toks: np.ndarray, batch: int, iters: int
                ) -> tuple:
    """Best-of-iters wall time over the whole token set (fresh server per
    iteration; the jitted stage fns are shared, so no recompilation)."""
    SL.serve_dataset(make_server(), toks[:2 * batch], batch=batch)  # warmup
    best, stats = float("inf"), None
    for _ in range(iters):
        server = make_server()
        t0 = time.perf_counter()
        results = SL.serve_dataset(server, toks, batch=batch)
        best = min(best, time.perf_counter() - t0)
        stats = server.stats
        assert len(results) == toks.shape[0], "dropped requests"
    return toks.shape[0] / best, stats


def run(fast: bool = False, chips1: Optional[int] = None,
        chips2: Optional[int] = None) -> dict:
    n = 512 if fast else 1024
    batch, seq = 128, 16
    iters = 2 if fast else 3
    cfg = _bench_cfg()
    spec0 = ee.EarlyExitSpec(exit_layer=2, c_thr=0.5)
    params = ee.init_ee_params(jax.random.PRNGKey(0), cfg, spec0)
    toks = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (n, seq), 0,
                                         cfg.vocab))
    _, _, exit_logits, _ = ee.stage1_prefill(params, cfg, spec0,
                                             jnp.asarray(toks))
    conf = ed.softmax_confidence(exit_logits)

    n_dev = jax.device_count()
    rows, data = [], {}
    all_parity = True
    for q in Q_GRID:
        # C_thr at the q-quantile of confidence => a q fraction stays hard
        c_thr = float(jnp.quantile(conf, q))
        spec = ee.EarlyExitSpec(exit_layer=spec0.exit_layer, c_thr=c_thr)
        capacity = max(8, int(np.ceil(q * batch)))
        sc = SL.ServeConfig(capacity=capacity, queue_depth=4, c_thr=c_thr)
        s1, s2 = SL._stage_fns(params, cfg, spec)

        # disaggregated parity gate BEFORE timing: the submesh server must
        # reproduce the single-device server bit for bit (ATHEENA's spatial
        # apportionment must not change answers)
        placement = make_disagg_placement(q, chips1, chips2)
        c1 = placement.ex1.n_devices if placement else 1
        c2 = placement.ex2.n_devices if placement else 1
        occ = {}
        parity = True
        if placement is not None:
            sub = toks[:2 * batch]
            dis = SL.build(params, cfg, spec, sc, mode="prefill",
                           scheduler=None, placement=placement)
            r_dis = SL.serve_dataset(dis, sub, batch=batch)
            r_one = SL.serve_dataset(SL.TwoStageServer(s1, s2, sc), sub,
                                     batch=batch)
            parity = (set(r_dis) == set(r_one) and all(
                np.array_equal(r_dis[i], r_one[i]) for i in r_one))
            assert parity, f"disaggregated parity broke at q={q}"
            occ = {"stage1_occupancy": dis.stats.stage1_occupancy,
                   "stage2_occupancy": dis.stats.stage2_occupancy}
        all_parity &= parity

        host_sps, host_stats = _time_serve(
            lambda: SL.HostLoopServer(s1, s2, sc), toks, batch, iters)
        dev_sps, dev_stats = _time_serve(
            lambda: SL.TwoStageServer(s1, s2, sc), toks, batch, iters)
        speedup = dev_sps / host_sps
        rows.append([f"{q:.1f}", f"{dev_stats.realized_q:.2f}", capacity,
                     f"{host_sps:,.0f}", f"{dev_sps:,.0f}",
                     f"{speedup:.2f}x",
                     f"{dev_stats.mean_bucket_fill:.2f}",
                     f"{c1}+{c2}" if placement else "-"])
        data[f"q{q}"] = {"host_sps": host_sps, "device_sps": dev_sps,
                         "speedup": speedup,
                         "realized_q": dev_stats.realized_q,
                         "chips1": c1, "chips2": c2,
                         **occ}

    # vacuously true on a 1-device host; CI pins 8 host devices so the
    # gate (benchmarks/compare.py) always sees the real check
    data["disagg"] = {"devices": n_dev, "checked": n_dev >= 2,
                      "parity": bool(all_parity)}
    txt = table(
        "Serving pipeline: host-loop vs device-resident "
        f"(B={batch}, S={seq}, N={n}, backend={jax.default_backend()}, "
        f"devices={n_dev})",
        ["q", "realized q", "bucket C", "host sps", "device sps", "speedup",
         "bucket fill", "submesh"], rows)
    return {"text": txt, **data}


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--chips1", type=int, default=None,
                    help="stage-1 submesh size (default: plan-derived)")
    ap.add_argument("--chips2", type=int, default=None,
                    help="stage-2 submesh size (default: plan-derived)")
    a = ap.parse_args()
    print(run(fast=a.fast, chips1=a.chips1, chips2=a.chips2)["text"])
