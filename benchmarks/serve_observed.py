"""Observability overhead gate: the continuous scheduler with the FULL
observability plane attached (request-span tracing + metrics sampling +
named scopes) vs the same scheduler running dark.

Observability that costs goodput gets turned off in production, at which
point the first incident is debugged blind — so the plane's contract is
that it is effectively free. The observed configuration here is the
everything-on worst case short of an active profiler capture:

  * a 65536-cap ``telemetry.EventLog`` wired into the scheduler (every
    submit/admit/park/bucket/finish/tick emits a dict);
  * an ``observe.Tracer`` subscribed to that feed, assembling per-request
    span trees synchronously inside ``emit``;
  * an ``observe.StatsSampler`` subscribed to the same feed, walking
    ``ServeStats`` into the metrics registry on its cadence;
  * the ``jax.named_scope`` / ``observe.annotate`` hooks in the tick and
    dispatch hot bodies (always compiled in; annotate is a shared
    nullcontext unless a ProfileWindow is active).

Gates (``benchmarks/compare.py`` against ``baseline_cpu.json``):

  * ``overhead_ratio`` = median of per-pair observed/dark goodput ratios,
    hard ``min`` 0.95 — the <= 5% overhead contract. The pair is the
    robust unit against runner drift (machine speed on shared boxes swings
    >10% over tens of seconds, and both sides of a back-to-back pair see
    the same state); alternating which side runs first inside each pair
    cancels the residual within-pair drift, and the median sheds
    stall-poisoned pairs;
  * ``equivalence`` — observed token streams bitwise-equal to both the
    unobserved scheduler and the ``HostLoopDecoder`` oracle (tracing must
    never perturb results);
  * ``span_complete`` — every submitted request assembles exactly one
    well-nested span tree (root covers queue-wait/decode/stage-2 children,
    no orphans, no still-open requests).

Run via ``PYTHONPATH=src python -m benchmarks.run --only serve_observed
[--json]``.
"""
from __future__ import annotations

import time
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import table
from repro.core import early_exit as ee
from repro.models.config import ArchConfig
from repro.runtime import observe
from repro.runtime import serve_loop as SL
from repro.runtime.scheduler import Request, poisson_arrivals
from repro.runtime.telemetry import EventLog

ARRIVAL_RATE = 2000.0      # saturating: the pool stays full
PAGE = 16                  # stage-2 page size, as every chip cell serves
Q = 0.3                    # the CI-gated operating point


def _bench_cfg() -> ArchConfig:
    """Wider than the other CPU serving benches' model ON PURPOSE: the
    observability contract is about a REAL serving load, where a tick
    costs model-forward time and the plane's per-event host work must
    amortize into it. d_model=32 would charge the plane against μs ticks
    and gate a regime no deployment runs in."""
    return ArchConfig(
        name="serve-obs-bench", family="dense", n_layers=4, d_model=128,
        n_heads=4, n_kv_heads=2, d_ff=256, vocab=256,
        dtype="float32", param_dtype="float32", tie_embeddings=True,
    )


def _make_requests(prompts: np.ndarray, n_tokens: np.ndarray,
                   seed: int) -> List[Request]:
    arrivals = poisson_arrivals(len(prompts), ARRIVAL_RATE, seed)
    return [Request(sample_id=i, prompt=prompts[i], n_tokens=int(n_tokens[i]),
                    arrival_time=float(arrivals[i]))
            for i in range(len(prompts))]


def _observed_pass(fns, sc, n_slots, max_len, reqs):
    """One pass with the full plane attached; returns
    (goodput, results, tracer, registry). The plane is constructed BEFORE
    the scheduler — its clock starts at construction, so setup must not be
    billed to the makespan."""
    events = EventLog(cap=65536)
    tracer = observe.Tracer()
    registry = observe.MetricsRegistry()
    sampler = observe.StatsSampler(registry)
    sched = SL.ContinuousScheduler(fns, sc, n_slots=n_slots,
                                   max_len=max_len, events=events)
    tracer.attach_scheduler(sched)
    sampler.attach_scheduler(sched)
    for r in reqs:
        sched.submit(r)
    results = sched.run()
    goodput = sum(len(v) for v in results.values()) / sched.clock.now()
    sampler.sample()
    sampler.close()
    tracer.close()
    return goodput, results, tracer, registry


def _dark_pass(fns, sc, n_slots, max_len, reqs):
    sched = SL.ContinuousScheduler(fns, sc, n_slots=n_slots, max_len=max_len)
    for r in reqs:
        sched.submit(r)
    results = sched.run()
    return sum(len(v) for v in results.values()) / sched.clock.now(), results


def run(fast: bool = False) -> dict:
    # Long passes: the gate is a hard 5%-overhead floor, and per-pass noise
    # (GC pauses, CPU steal on shared runners) amortizes with makespan — a
    # 20ms stall is 5% of a 0.4s pass but 1.5% of a 1.3s one.
    seq = 8
    if fast:
        n_requests, n_slots, tok_choices = 96, 8, (6, 8, 12, 40)
    else:
        n_requests, n_slots, tok_choices = 144, 16, (6, 8, 12, 40)
    max_tok = max(tok_choices)
    max_len = seq + max_tok                     # 48: a whole number of pages
    cfg = _bench_cfg()
    spec0 = ee.EarlyExitSpec(exit_layer=2, c_thr=0.5)
    params = ee.init_ee_params(jax.random.PRNGKey(0), cfg, spec0)
    prompts = np.asarray(jax.random.randint(
        jax.random.PRNGKey(1), (n_requests, seq), 0, cfg.vocab))
    n_tokens = np.random.default_rng(7).choice(tok_choices, size=n_requests)
    conf = SL.decode_step0_confidences(params, cfg, spec0, prompts[:n_slots],
                                       max_len=max_len)
    fns = SL.decode_stage_fns(params, cfg, spec0, page_size=PAGE)
    c_thr = float(jnp.quantile(conf, Q))
    capacity = max(2, int(np.ceil(Q * n_slots)))
    sc = SL.ServeConfig(capacity=capacity, queue_depth=4, c_thr=c_thr)
    reqs = _make_requests(prompts, n_tokens, seed=11)
    expect_sids = set(range(n_requests))

    # --- correctness gates BEFORE timing: the observed run must change
    # nothing but emit everything
    oracle = SL.HostLoopDecoder(fns, sc).generate(prompts, max_tok)
    _, res_obs, tracer, registry = _observed_pass(
        fns, sc, n_slots, max_len, reqs)
    _, res_dark = _dark_pass(fns, sc, n_slots, max_len, reqs)
    equiv = all(
        res_obs[i] == res_dark[i]
        and [int(x) for x in oracle["tokens"][i][:int(n_tokens[i])]]
        == res_obs[i]
        for i in range(n_requests))
    assert equiv, "observed token streams diverged from dark/oracle"

    comp = tracer.completeness(expect_sids)
    assert comp["complete"], f"span trees incomplete: {comp}"

    # the sampler actually fed the registry, and the exposition both
    # renders and parses — the full export path, not just the counters
    parsed = observe.parse_exposition(registry.exposition())
    n_fin = parsed.get('repro_requests_finished_total{replica="0"}', 0.0)
    assert n_fin == float(n_requests), \
        f"metrics saw {n_fin} finished, expected {n_requests}"

    # --- timed alternating pairs (warmup already happened via the
    # equivalence passes above); median of per-pair ratios, see module doc
    iters = 10 if fast else 6
    obs_g, dark_g, ratios = [], [], []
    for i in range(iters):
        if i % 2 == 0:
            o = _observed_pass(fns, sc, n_slots, max_len, reqs)[0]
            d = _dark_pass(fns, sc, n_slots, max_len, reqs)[0]
        else:
            d = _dark_pass(fns, sc, n_slots, max_len, reqs)[0]
            o = _observed_pass(fns, sc, n_slots, max_len, reqs)[0]
        obs_g.append(o)
        dark_g.append(d)
        ratios.append(o / d)
    best_obs, best_dark = max(obs_g), max(dark_g)
    ratio = float(np.median(ratios))

    txt = table(
        "Observability overhead: full plane vs dark "
        f"(N={n_requests}, slots={n_slots}, q={Q}, "
        f"backend={jax.default_backend()})",
        ["dark tok/s", "observed tok/s", "obs/dark", "spans", "streams =="],
        [[f"{best_dark:,.0f}", f"{best_obs:,.0f}", f"{ratio:.3f}x",
          comp["n_spans"], equiv]])
    return {"text": txt,
            "overhead_ratio": ratio,
            "observed_goodput": best_obs,
            "unobserved_goodput": best_dark,
            "equivalence": bool(equiv),
            "span_complete": bool(comp["complete"]),
            "n_spans": comp["n_spans"],
            "n_span_annotations": comp["n_annotations"]}


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true")
    a = ap.parse_args()
    print(run(fast=a.fast)["text"])
