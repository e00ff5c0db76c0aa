"""Hypothesis property tests on the paper's core invariants:
Eq. (2) == Eq. (4), TAP monotonicity + the ⊕ operator (Eq. 1), and the
conditional-buffer / exit-merge round trip."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")  # optional dev dep; skip, not error, when absent
from hypothesis import example, given, settings, strategies as st

from repro.core import conditional as cond
from repro.core import exit_decision as ed
from repro.core.tap import (CombinedDesign, DesignPoint, TAPFunction, combine,
                            combine_multistage, robustness_band)

SET = settings(max_examples=60, deadline=None)


# ---------------------------------------------------------------------------
# Eq. (2)  max softmax > C_thr   ==   Eq. (4) division-free (+ max shift)
# ---------------------------------------------------------------------------

@SET
@given(st.integers(2, 40), st.integers(1, 16),
       st.floats(0.05, 0.99), st.integers(0, 2**31 - 1))
def test_eq2_equals_eq4(n_classes, batch, c_thr, seed):
    k = jax.random.PRNGKey(seed)
    x = jax.random.normal(k, (batch, n_classes), jnp.float32) * 10.0
    # Eq. (2): literal softmax comparison
    sm = jax.nn.softmax(x, axis=-1)
    eq2 = jnp.max(sm, axis=-1) > c_thr
    # Eq. (4) as implemented (division-free, max-shifted)
    eq4 = ed.exit_decision(x, c_thr)
    np.testing.assert_array_equal(np.asarray(eq2), np.asarray(eq4))


@SET
@given(st.integers(2, 30), st.integers(1, 8), st.integers(0, 2**31 - 1))
def test_confidence_is_max_softmax(n_classes, batch, seed):
    x = jax.random.normal(jax.random.PRNGKey(seed), (batch, n_classes)) * 5
    conf = ed.softmax_confidence(x)
    np.testing.assert_allclose(np.asarray(conf),
                               np.asarray(jnp.max(jax.nn.softmax(x, -1), -1)),
                               rtol=1e-5)


@SET
@given(st.floats(0.05, 0.95), st.integers(0, 2**31 - 1))
def test_calibrate_threshold_hits_rate(target_rate, seed):
    conf = jax.random.uniform(jax.random.PRNGKey(seed), (4000,))
    thr = ed.calibrate_threshold(conf, target_rate)
    realized = float((conf > thr).mean())
    assert abs(realized - target_rate) < 0.02


def test_entropy_confidence_bounds():
    x = jax.random.normal(jax.random.PRNGKey(0), (32, 10)) * 3
    e = ed.entropy_confidence(x)
    assert float(e.min()) >= 0.0 and float(e.max()) <= 1.0 + 1e-6
    one_hot = jnp.full((1, 10), -100.0).at[0, 3].set(100.0)
    assert float(ed.entropy_confidence(one_hot)[0]) < 1e-3


# ---------------------------------------------------------------------------
# TAP functions + Eq. (1)
# ---------------------------------------------------------------------------

def _points(draw_resources, draw_thr):
    return [DesignPoint(resources=(float(r),), throughput=float(t))
            for r, t in zip(draw_resources, draw_thr)]


tap_strategy = st.lists(
    st.tuples(st.floats(1, 100), st.floats(1, 1000)), min_size=1, max_size=12)


@SET
@given(tap_strategy)
def test_tap_pareto_and_monotone(pts):
    tap = TAPFunction([DesignPoint(resources=(r,), throughput=t)
                       for r, t in pts])
    assert tap.is_monotone()
    # pareto: no kept point dominated by another kept point
    for a in tap.points:
        for b in tap.points:
            if a is b:
                continue
            dominated = (b.throughput >= a.throughput and
                         b.resources[0] <= a.resources[0])
            assert not dominated or b.throughput == a.throughput
    # query never exceeds budget
    for budget in (0.5, 10.0, 200.0):
        got = tap.query((budget,))
        if got is not None:
            assert got.resources[0] <= budget + 1e-9


@SET
@given(tap_strategy, tap_strategy, st.floats(0.05, 1.0))
@example([(1.0, 1.0)], [(1.0, 1.0)], 1.0)
def test_combine_eq1_invariants(pts1, pts2, p):
    f = TAPFunction([DesignPoint(resources=(r,), throughput=t)
                     for r, t in pts1], "f")
    g = TAPFunction([DesignPoint(resources=(r,), throughput=t)
                     for r, t in pts2], "g")
    budget = (150.0,)
    d = combine(f, g, p, budget)
    if d is None:
        return
    # (1) resources within budget
    assert d.resources[0] <= budget[0] + 1e-9
    # (2) design throughput = min(f(x1), g(x2)/p)
    expect = min(d.stage1.throughput, d.stage2.throughput / p)
    assert abs(d.design_throughput - expect) < 1e-9
    # (3) the argmax is optimal: no other feasible pair beats it
    for a in f.points:
        for b in g.points:
            if a.resources[0] + b.resources[0] <= budget[0] + 1e-9:
                assert min(a.throughput, b.throughput / p) <= \
                    d.design_throughput + 1e-9
    # (4) Fig. 4 robustness ordering: q < p cannot hurt, q > p cannot help
    qs = [max(p - 0.05, 1e-3), p, min(p + 0.05, 1.0)]
    band = robustness_band(d, qs)
    vals = [band[q] for q in qs]          # p = 1.0 repeats a q (dict key)
    assert vals[0] >= vals[1] - 1e-9 >= vals[2] - 2e-9
    # (5) throughput at q never exceeds the stage-1 rate (hard ceiling)
    for q in (0.01, p, 1.0):
        assert d.throughput_at(q) <= d.stage1.throughput + 1e-9


@SET
@given(tap_strategy, st.floats(0.1, 1.0))
def test_combine_multistage_reduces_to_pairwise(pts, p):
    f = TAPFunction([DesignPoint(resources=(r,), throughput=t)
                     for r, t in pts], "f")
    g = TAPFunction([DesignPoint(resources=(r * 0.7,), throughput=t * 1.1)
                     for r, t in pts], "g")
    budget = (120.0,)
    two = combine(f, g, p, budget)
    multi = combine_multistage([f, g], [1.0, p], budget)
    if two is None:
        assert multi is None
        return
    assert multi is not None
    assert abs(multi["design_throughput"] - two.design_throughput) < 1e-9


def test_combine_prefers_small_stage2_when_p_small():
    """The paper's core claim: as p shrinks, stage 2 needs fewer resources
    for the same combined throughput."""
    mk = lambda s: TAPFunction([DesignPoint(resources=(float(r),),
                                            throughput=float(r) * s)
                                for r in (1, 2, 4, 8, 16, 32, 64)])
    f, g = mk(10.0), mk(10.0)
    d_small = combine(f, g, 0.1, (64.0,))
    d_big = combine(f, g, 0.9, (64.0,))
    assert d_small.design_throughput >= d_big.design_throughput
    assert d_small.stage2.resources[0] < d_big.stage2.resources[0]


# ---------------------------------------------------------------------------
# conditional buffer + exit merge round trip
# ---------------------------------------------------------------------------

@SET
@given(st.integers(1, 64), st.integers(0, 2**31 - 1))
def test_compact_indices_is_stable_partition(batch, seed):
    mask = np.asarray(
        jax.random.bernoulli(jax.random.PRNGKey(seed), 0.4, (batch,)))
    perm, n_hard = cond.compact_indices(jnp.asarray(mask))
    perm = np.asarray(perm)
    assert sorted(perm.tolist()) == list(range(batch))        # permutation
    nh = int(n_hard)
    assert nh == int(mask.sum())
    hard_idx = np.flatnonzero(mask)
    easy_idx = np.flatnonzero(~mask)
    np.testing.assert_array_equal(perm[:nh], hard_idx)        # stable order
    np.testing.assert_array_equal(perm[nh:], easy_idx)


@SET
@given(st.integers(1, 48), st.floats(0.0, 1.0), st.integers(0, 2**31 - 1))
def test_merge_round_trip(batch, p_hard, seed):
    """serve-style: exit decision -> buffer -> merge reconstructs each
    sample's value from the correct stream."""
    k = jax.random.PRNGKey(seed)
    mask_hard = jax.random.bernoulli(k, p_hard, (batch,))
    vals = jnp.arange(batch, dtype=jnp.float32) + 1.0         # payload = id+1
    ids = jnp.arange(batch, dtype=jnp.int32)
    cap = batch                                               # lossless run
    slab, slab_ids, n_hard, overflow = cond.conditional_buffer(
        vals, ids, mask_hard, cap)
    assert int(overflow) == 0
    easy_ids = jnp.where(~mask_hard, ids, -1)
    merged = cond.exit_merge(batch, easy_ids, vals * 10.0, slab_ids,
                             slab * 100.0)
    expect = np.where(np.asarray(mask_hard),
                      (np.arange(batch) + 1.0) * 100.0,
                      (np.arange(batch) + 1.0) * 10.0)
    np.testing.assert_allclose(np.asarray(merged), expect)


@SET
@given(st.integers(2, 32), st.integers(1, 31), st.integers(0, 2**31 - 1))
def test_buffer_overflow_counts(batch, cap, seed):
    cap = min(cap, batch)
    mask = jax.random.bernoulli(jax.random.PRNGKey(seed), 0.7, (batch,))
    vals = jnp.zeros((batch, 3))
    _, slab_ids, n_hard, overflow = cond.conditional_buffer(
        vals, jnp.arange(batch, dtype=jnp.int32), mask, cap)
    assert int(overflow) == max(int(mask.sum()) - cap, 0)
    n_valid = int((np.asarray(slab_ids) >= 0).sum())
    assert n_valid == min(int(mask.sum()), cap)


def test_queue_simulator_matches_eq1_regions():
    """Fig. 4: with stage-2 provisioned for p, running q < p keeps design
    throughput; q > p degrades toward stage2_rate/q."""
    rng = np.random.default_rng(0)
    p = 0.25
    s1_rate, s2_rate = 100.0, 100.0 * p * 1.05    # stage 2 sized for p
    for q, expect_close_to_design in ((0.15, True), (0.25, True),
                                      (0.45, False)):
        seq = (rng.random(4000) < q).astype(int)
        r = cond.simulate_two_stage_queue(
            seq, stage1_rate=s1_rate, stage2_rate=s2_rate, buffer_depth=64)
        if expect_close_to_design:
            assert r["throughput"] > 0.9 * s1_rate
        else:
            assert r["throughput"] < 0.75 * s1_rate
            assert r["throughput"] > 0.9 * s2_rate / q
