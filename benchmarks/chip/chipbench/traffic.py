"""One general generator for every traffic mix: a mix is a JSON file of
parameters (``traffic/<name>.json``), never code.

Every seed gets the same requests. Each block of ``block`` requests holds
one pool: a stratified grid of quantiles of the prompt-length
distribution, each length paired with one output length from the output
grid, and prompt tokens drawn once for the mix (uniform over the
vocabulary). The seed orders the pool inside each block, times the
arrivals, and renames the tokens by the run's vocabulary permutation, which
the embedding follows (``system.vocab_perm``): the model then computes the
same thing on every seed, and only the order varies. Seeds that drew their
own tokens realized hard shares from 0.25 to 0.33, and tokens/s followed
(PERF.md).

Keys of a mix file:

  kind            "backlog" (every request due when the preroll starts) or
                  "poisson" (open loop: exponential gaps at ``rate_per_s``)
  source          where the length medians come from (not read)
  prompt          {"median", "sigma", "buckets"}: log-normal lengths moved
                  to the nearest bucket in log space (the program prefills
                  same-length chunks, and each length is one compile)
  output          {"median", "sigma", "min", "max"}: log-normal, clipped
  p_hard          the hard share the exit threshold is calibrated for
  max_equal_run   longest run of equal prompt lengths in arrival order,
                  which bounds the admission chunk (a power of two) and so
                  the set of prefill shapes warm-up has to cover: a limit
                  of the program, whose prefill compiles per (chunk,
                  length), not of the traffic
  block           requests per block: the size of the request pool
  n_requests      length of the stream
  warm_start      backlog only: the first ``n_slots`` requests emit a
                  stratified fraction of their output, so the pool starts
                  as a steady state would leave it rather than in lockstep
  rate_per_s      poisson only: arrivals per second, fixed in the file
  preroll_s       seconds the stream runs before the window opens
"""
from __future__ import annotations

from dataclasses import dataclass
from statistics import NormalDist
from typing import List

import numpy as np

_Z = NormalDist()
WARMUP_SID0 = 1 << 30        # warm-up ids never meet a stream's ids


@dataclass(frozen=True)
class Req:
    sid: int
    prompt: np.ndarray      # (S,) int32
    n_tokens: int           # tokens to emit, the prefill token included
    due: float              # seconds relative to the window's origin


def _grid(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lognormal_grid(n: int, median: float, sigma: float) -> np.ndarray:
    z = np.array([_Z.inv_cdf(u) for u in _grid(n)])
    return median * np.exp(sigma * z)


def prompt_lengths(mix: dict, n: int) -> np.ndarray:
    """Log-normal lengths moved to the nearest bucket in log space (the
    geometric midpoints split them), which keeps the median where the
    source puts it; lengths past the largest bucket are cut to it."""
    p = mix["prompt"]
    raw = lognormal_grid(n, p["median"], p["sigma"])
    buckets = np.asarray(sorted(p["buckets"]), dtype=float)
    mids = np.sqrt(buckets[1:] * buckets[:-1])
    return buckets[np.searchsorted(mids, raw)].astype(int)


def output_lengths(mix: dict, n: int) -> np.ndarray:
    o = mix["output"]
    raw = lognormal_grid(n, o["median"], o["sigma"])
    return np.clip(np.ceil(raw), o["min"], o["max"]).astype(int)


def exp_gaps(n: int, rate: float) -> np.ndarray:
    return -np.log(1.0 - _grid(n)) / rate


def _blocked(values: np.ndarray, n: int, block: int,
             rng: np.random.Generator) -> np.ndarray:
    """``n`` values: the ``block``-sized grid repeated, shuffled per block."""
    out = []
    while len(out) < n:
        out.extend(values[rng.permutation(len(values))])
    return np.asarray(out[:n])


def _cap_runs(idx: np.ndarray, key: np.ndarray, cap: int) -> np.ndarray:
    """Reorder ``idx`` so that no run of equal ``key[idx]`` is longer than
    ``cap``: an entry that would extend a full run is swapped with the next
    one of another key further on (deterministic, so the seed's order stays
    the seed's)."""
    a = idx.copy()
    run = 1
    for i in range(1, len(a)):
        run = run + 1 if key[a[i]] == key[a[i - 1]] else 1
        if run <= cap:
            continue
        j = next((j for j in range(i + 1, len(a))
                  if key[a[j]] != key[a[i - 1]]), None)
        if j is None:
            break
        a[i], a[j] = a[j], a[i]
        run = 1
    return a


def generate(mix: dict, seed: int, perm: np.ndarray,
             n_slots: int) -> List[Req]:
    """The whole request stream of one run: the mix's request pool in every
    block, ordered by the seed, with prompt tokens renamed so that the
    run's embedding (rows permuted by ``perm``) reads what the
    configuration's would read for the pool's tokens."""
    pool_rng = np.random.default_rng(np.random.SeedSequence([0x7A11, 1]))
    rng = np.random.default_rng(np.random.SeedSequence([seed, 0x7A11]))
    n, block = mix["n_requests"], mix["block"]
    s_grid = prompt_lengths(mix, block)
    o_grid = output_lengths(mix, block)[pool_rng.permutation(block)]
    pool = [pool_rng.integers(0, len(perm), size=s, dtype=np.int32)
            for s in s_grid]
    rename = np.argsort(perm).astype(np.int32)     # pool id -> run's id
    pick = _cap_runs(_blocked(np.arange(block), n, block, rng), s_grid,
                     mix["max_equal_run"])
    O = o_grid[pick]
    if mix["kind"] == "backlog":
        due = np.full(n, -float(mix["preroll_s"]))
        if mix.get("warm_start"):
            frac = _grid(n_slots)[rng.permutation(n_slots)]
            k = min(n_slots, n)
            O[:k] = np.maximum(2, np.ceil(frac[:k] * O[:k])).astype(int)
    elif mix["kind"] == "poisson":
        gaps = _blocked(exp_gaps(block, mix["rate_per_s"]), n, block, rng)
        due = np.cumsum(gaps) - mix["preroll_s"]
    else:
        raise ValueError(f"unknown traffic kind {mix['kind']!r}")
    return [Req(i, rename[pool[pick[i]]], int(O[i]), float(due[i]))
            for i in range(n)]


def warmup_requests(mix: dict, seed: int, vocab: int,
                    n_tokens: int) -> List[Req]:
    """Requests that admit every (chunk, prompt length) prefill shape the
    mix can reach: per length a run of 2*cap - 1 equal prompts, which the
    scheduler admits as chunks cap, cap/2, ..., 1 (powers of two)."""
    rng = np.random.default_rng(np.random.SeedSequence([seed, 0x3A3A]))
    cap = 1 << (int(mix["max_equal_run"]).bit_length() - 1)
    out = []
    for S in sorted(mix["prompt"]["buckets"]):
        for _ in range(2 * cap - 1):
            out.append(Req(WARMUP_SID0 + len(out),
                           rng.integers(0, vocab, size=S, dtype=np.int32),
                           n_tokens, 0.0))
    return out
