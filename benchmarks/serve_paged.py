"""Paged KV cache: token streams against the dense host-loop oracle under
long-tailed generation lengths and a page budget tighter than full width.

The continuous scheduler keeps the stage-2 cache in a shared page pool and
gives each slot only ``ceil(span / page)`` pages, so short requests do not
pay for the reservation the rare long one sets. Here the pool holds the
pages of 4 full-width slots (``N_PAGES``) and serves 12 slots from them:
admission backpressures on the free list whenever the long tail holds the
pages.

Per q in {0.1, 0.3, 0.5} (C_thr calibrated on the first decode step's
confidences), on one request trace:

  * token-stream equivalence is enforced: every paged stream must equal the
    ``HostLoopDecoder`` oracle's per sample id (gathering a block table
    over the zero NULL page reconstructs the dense cache row exactly);
  * the peak number of concurrently live slots and the peak page
    fragmentation are reported (not gated: they are host-side counts of a
    CPU run, not device measurements).

Run via ``PYTHONPATH=src python -m benchmarks.run --only serve_paged
[--json]``.
"""
from __future__ import annotations

from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import table
from repro.core import early_exit as ee
from repro.models.config import ArchConfig
from repro.runtime import serve_loop as SL
from repro.runtime.scheduler import (ContinuousScheduler, Request,
                                     poisson_arrivals)

Q_GRID = (0.1, 0.3, 0.5)
ARRIVAL_RATE = 2000.0
PAGE = 4
SEQ = 8
N_SLOTS = 12


def _bench_cfg() -> ArchConfig:
    return ArchConfig(
        name="serve-paged-bench", family="dense", n_layers=4, d_model=32,
        n_heads=4, n_kv_heads=2, d_ff=64, vocab=256,
        dtype="float32", param_dtype="float32", tie_embeddings=True,
    )


class _PeakLive:
    """Tick controller recording peak busy slots and peak page
    fragmentation (the post-drain stats read zeros — every page is home)."""

    def __init__(self):
        self.peak = 0
        self.frag = 0.0

    def on_tick(self, sched, n_dec, n_hard, conf):
        self.peak = max(self.peak, sched.n_slots - len(sched._free))
        self.frag = max(self.frag, sched.stats.page_fragmentation)


def _make_requests(prompts, n_tokens, seed: int) -> List[Request]:
    arrivals = poisson_arrivals(len(prompts), ARRIVAL_RATE, seed)
    return [Request(sample_id=i, prompt=prompts[i],
                    n_tokens=int(n_tokens[i]),
                    arrival_time=float(arrivals[i]))
            for i in range(len(prompts))]


def run(fast: bool = False) -> dict:
    # long-tailed generation lengths: the rare long request sets max_len;
    # the short majority is what the page pool reclaims
    tok_choices, tok_p = (2, 4, 6, 40), (0.42, 0.3, 0.2, 0.08)
    max_len = SEQ + max(tok_choices)                      # 48, page-aligned
    assert max_len % PAGE == 0
    n_requests = 64 if fast else 96
    n_pages = 4 * (max_len // PAGE)          # the pages of 4 full-width slots

    cfg = _bench_cfg()
    spec0 = ee.EarlyExitSpec(exit_layer=2, c_thr=0.5)
    params = ee.init_ee_params(jax.random.PRNGKey(0), cfg, spec0)
    prompts = np.asarray(jax.random.randint(
        jax.random.PRNGKey(1), (n_requests, SEQ), 0, cfg.vocab))
    n_tokens = np.random.default_rng(7).choice(tok_choices, size=n_requests,
                                               p=tok_p)
    conf = SL.decode_step0_confidences(params, cfg, spec0, prompts[:8],
                                       max_len=max_len)
    fns = SL.decode_stage_fns(params, cfg, spec0, page_size=PAGE)

    rows, data = [], {}
    for q in Q_GRID:
        c_thr = float(jnp.quantile(conf, q))
        sc = SL.ServeConfig(capacity=max(2, int(np.ceil(q * N_SLOTS))),
                            queue_depth=4, c_thr=c_thr)
        oracle = SL.HostLoopDecoder(fns, sc).generate(prompts,
                                                      max(tok_choices))
        want = {i: [int(x) for x in oracle["tokens"][i][:int(n_tokens[i])]]
                for i in range(n_requests)}
        sched = ContinuousScheduler(fns, sc, n_slots=N_SLOTS,
                                    max_len=max_len, n_pages=n_pages)
        peak = _PeakLive()
        sched.controller = peak
        for r in _make_requests(prompts, n_tokens, seed=11):
            sched.submit(r)
        equiv = sched.run() == want
        assert equiv, f"paged token-stream equivalence broke at q={q}"
        st = sched.stats
        rows.append([f"{q:.1f}", f"{st.realized_q:.2f}",
                     f"{peak.peak}/{N_SLOTS}", f"{peak.frag:.2f}", equiv])
        data[f"q{q}"] = {
            "equivalence": bool(equiv), "peak_live_slots": peak.peak,
            "ring_bytes": st.ring_bytes_moved,
            "hbm_bytes": st.cache_hbm_bytes,
            "page_fragmentation": peak.frag,
        }

    data["equivalence"] = all(data[f"q{q}"]["equivalence"] for q in Q_GRID)
    txt = table(
        "Paged KV cache vs the host-loop oracle "
        f"(N={n_requests}, prompt={SEQ}, T∈{tok_choices}, page={PAGE}, "
        f"pool={n_pages}p, {N_SLOTS} slots, "
        f"backend={jax.default_backend()})",
        ["q", "realized q", "peak live", "frag", "streams =="], rows)
    return {"text": txt, **data}


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true")
    a = ap.parse_args()
    print(run(fast=a.fast)["text"])
