"""The fused dispatch kernel (decision + compaction + in-ring enqueue, one
program) against the composed three-program chain it replaced: bitwise
parity across backends and ring states (wraparound, overflow backpressure),
a hypothesis property over random shapes/thresholds/fills, the memoized
backend resolution, the single-launch steady-state tick contract, and the
pred-as-emitted-token equivalence (satellite of the same PR: the decision
kernel's argmax IS the greedy token, so no second logits pass exists)."""
import functools
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import dispatch
from repro.runtime import scheduler as SCH
from repro.runtime import serve_loop as SL
from repro.runtime.scheduler import (ContinuousScheduler, LogicalClock,
                                     Request)
from test_scheduler import _TOY_S, _toy_tok, toy_decode_fns

try:
    from hypothesis import given, settings, strategies as st_h
    _HAVE_HYP = True
except ImportError:                                   # pragma: no cover
    _HAVE_HYP = False


# ---------------------------------------------------------------------------
# helpers: build a ring in an arbitrary state, run the composed chain the
# fused op replaced, compare pytrees bitwise
# ---------------------------------------------------------------------------

def _copy_tree(t):
    # jax.tree.map(lambda x: x, t) would alias the same buffers — a donated
    # call downstream would delete them. jnp.copy makes real copies.
    return jax.tree.map(jnp.copy, t)


def _assert_tree_equal(a, b, what=""):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb), what
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=what)


def _mk_case(b, v, d, key):
    """Random (logits, sample_ids, payload pytree, row_spec) of width b."""
    k1, k2, k3, k4 = jax.random.split(key, 4)
    logits = jax.random.normal(k1, (b, v), jnp.float32) * 3.0
    payload = {
        "h": jax.random.normal(k2, (b, d), jnp.float32),
        "cache": {"sid": jax.random.randint(k3, (b, 1), 0, 97, jnp.int32)},
        "step": jax.random.randint(k4, (b,), 0, 31, jnp.int32),
    }
    sample_ids = jnp.arange(b, dtype=jnp.int32) * 3 + 1
    spec = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape[1:], x.dtype), payload)
    return logits, sample_ids, payload, spec


def _mk_ring(size, row_spec, head, count, key):
    """A ring pre-filled with junk rows/ids so untouched-slot parity is a
    real assertion, with arbitrary head/count cursors."""
    ring = SCH.ring_init(size, row_spec)

    def junk(d):
        if jnp.issubdtype(d.dtype, jnp.floating):
            return jax.random.normal(key, d.shape).astype(d.dtype)
        return jax.random.randint(key, d.shape, 0, 89).astype(d.dtype)

    ring["data"] = jax.tree.map(junk, ring["data"])
    ring["ids"] = jax.random.randint(key, (size,), -1, 50, jnp.int32)
    ring["head"] = jnp.asarray(head % size, jnp.int32)
    ring["count"] = jnp.asarray(count, jnp.int32)
    return ring


def _composed(logits, active, sample_ids, payload, ring, c_thr, backend):
    """The three-program chain fused_dispatch replaced: exit decision,
    per-leaf gather-compact, ranged ring enqueue clipped to free space.
    Operates on a COPY of the ring (the enqueue step donates its input)."""
    exit_mask, pred, conf = dispatch.exit_decision_op(logits, c_thr,
                                                      backend=backend)
    hard = ~exit_mask if active is None else active & ~exit_mask
    b = logits.shape[0]
    slab = jax.tree.map(
        lambda x: dispatch.gather_compact_op(x, hard, b, backend=backend)[0],
        payload)
    _, src, n_hard = dispatch.gather_compact_op(
        jnp.zeros((b, 1), jnp.float32), hard, b, backend=backend)
    slab_ids = jnp.where(src >= 0,
                         jnp.take(sample_ids, jnp.maximum(src, 0)), -1)
    size = ring["ids"].shape[0]
    n_enq = min(int(n_hard), size - int(ring["count"]))
    new = SCH._ring_enqueue_range(_copy_tree(ring), slab, slab_ids, 0, n_enq)
    return new, exit_mask, pred, conf, src, n_hard


def _check_parity(logits, active, sample_ids, payload, ring, c_thr, backend):
    got = dispatch.fused_dispatch_op(logits, active, sample_ids, payload,
                                     ring, c_thr, backend=backend,
                                     donate=False)
    want = _composed(logits, active, sample_ids, payload, ring, c_thr,
                     backend)
    g_ring, g_exit, g_pred, g_conf, g_src, g_nh = got
    w_ring, w_exit, w_pred, w_conf, w_src, w_nh = want
    np.testing.assert_array_equal(np.asarray(g_exit), np.asarray(w_exit))
    np.testing.assert_array_equal(np.asarray(g_pred), np.asarray(w_pred))
    np.testing.assert_array_equal(np.asarray(g_conf), np.asarray(w_conf))
    np.testing.assert_array_equal(np.asarray(g_src), np.asarray(w_src))
    assert int(g_nh) == int(w_nh)
    _assert_tree_equal(g_ring, w_ring, what=f"ring state ({backend})")


# ---------------------------------------------------------------------------
# bitwise parity: fused vs composed, per backend, across ring states
# ---------------------------------------------------------------------------

# (size, head, count): empty, wrapping tail, and nearly-full (the enqueue
# overflows free space and must leave rows [free, n_hard) unwritten)
_RING_STATES = [(24, 0, 0), (24, 20, 5), (24, 7, 21), (8, 3, 8)]


@pytest.mark.parametrize("backend", ["ref", "interpret"])
@pytest.mark.parametrize("size,head,count", _RING_STATES)
def test_fused_dispatch_parity(backend, size, head, count):
    key = jax.random.PRNGKey(size * 7 + head * 3 + count)
    logits, sample_ids, payload, spec = _mk_case(16, 32, 8, key)
    b = logits.shape[0]
    mask = jax.random.bernoulli(jax.random.fold_in(key, 9), 0.7, (b,))
    for c_thr in (0.0, 0.6, 1.1):
        for active in (None, mask):
            ring = _mk_ring(size, spec, head, count,
                            jax.random.fold_in(key, 5))
            _check_parity(logits, active, sample_ids, payload, ring, c_thr,
                          backend)


def test_fused_dispatch_does_not_mutate_input_without_donation():
    key = jax.random.PRNGKey(0)
    logits, sample_ids, payload, spec = _mk_case(8, 16, 4, key)
    ring = _mk_ring(12, spec, 2, 3, jax.random.fold_in(key, 1))
    before = _copy_tree(ring)
    dispatch.fused_dispatch_op(logits, None, sample_ids, payload, ring, 1.1,
                               backend="ref", donate=False)
    _assert_tree_equal(ring, before, what="donate=False input ring")


# ---------------------------------------------------------------------------
# hypothesis property: fused ≡ composed over random shapes / thresholds /
# hard fractions / ring fill levels (wraparound and overflow included by
# drawing head and count freely) — satellite 3
# ---------------------------------------------------------------------------

if _HAVE_HYP:
    @settings(max_examples=25, deadline=None)
    @given(
        b=st_h.integers(1, 12),
        v=st_h.integers(2, 40),
        d=st_h.integers(1, 6),
        size=st_h.integers(2, 10),
        head=st_h.integers(0, 30),
        fill_pct=st_h.integers(0, 100),
        c_thr=st_h.floats(0.0, 1.2),
        use_active=st_h.booleans(),
        seed=st_h.integers(0, 2 ** 16),
    )
    def test_fused_equals_composed_property(b, v, d, size, head, fill_pct,
                                            c_thr, use_active, seed):
        key = jax.random.PRNGKey(seed)
        logits, sample_ids, payload, spec = _mk_case(b, v, d, key)
        active = (jax.random.bernoulli(jax.random.fold_in(key, 11), 0.6,
                                       (b,)) if use_active else None)
        count = (size * fill_pct) // 100
        ring = _mk_ring(size, spec, head, count, jax.random.fold_in(key, 13))
        _check_parity(logits, active, sample_ids, payload, ring, c_thr,
                      "ref")


# ---------------------------------------------------------------------------
# memoized backend resolution (satellite 2): override precedence, cache
# invalidation, live env var
# ---------------------------------------------------------------------------

@pytest.fixture
def clean_backend(monkeypatch):
    monkeypatch.delenv("REPRO_KERNEL_BACKEND", raising=False)
    dispatch.set_backend(None)
    yield monkeypatch
    dispatch.set_backend(None)


def test_kernel_backend_precedence(clean_backend):
    monkeypatch = clean_backend
    auto = dispatch.kernel_backend()
    assert auto == ("pallas" if jax.default_backend() == "tpu" else "ref")
    # env var beats auto
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "interpret")
    assert dispatch.kernel_backend() == "interpret"
    # set_backend beats env
    dispatch.set_backend("ref")
    assert dispatch.kernel_backend() == "ref"
    # explicit argument beats everything
    assert dispatch.kernel_backend("interpret") == "interpret"
    # restoring the override re-exposes the env var
    dispatch.set_backend(None)
    assert dispatch.kernel_backend() == "interpret"


def test_kernel_backend_memoized_and_invalidated(clean_backend):
    calls = {"n": 0}

    def probed():
        calls["n"] += 1
        return False

    clean_backend.setattr(dispatch, "_on_tpu", probed)
    dispatch.set_backend(None)                      # clear the cache
    assert dispatch.kernel_backend() == "ref"
    assert dispatch.kernel_backend() == "ref"
    assert calls["n"] == 1                          # resolution memoized
    assert (None, None, None) in dispatch._resolve_cache
    dispatch.set_backend(None)
    assert not dispatch._resolve_cache              # invalidated
    assert dispatch.kernel_backend() == "ref"
    assert calls["n"] == 2                          # re-probed once


def test_kernel_backend_pallas_degrades_off_tpu(clean_backend):
    """An explicit 'pallas' request off-TPU raises instead of quietly
    running the interpreter; the interpreter is asked for by name."""
    clean_backend.setattr(dispatch, "_on_tpu", lambda: False)
    dispatch.set_backend(None)
    with pytest.raises(RuntimeError, match="needs a TPU"):
        dispatch.kernel_backend("pallas")
    assert dispatch.kernel_backend("interpret") == "interpret"
    assert dispatch.kernel_body_backend() == "interpret"


def test_kernel_backend_rejects_unknown(clean_backend):
    with pytest.raises(ValueError):
        dispatch.set_backend("bogus")
    clean_backend.setenv("REPRO_KERNEL_BACKEND", "bogus")
    with pytest.raises(ValueError):
        dispatch.kernel_backend()
    with pytest.raises(ValueError):
        dispatch.kernel_backend("nope")


# ---------------------------------------------------------------------------
# satellite 1: the decision kernel's pred IS the greedy token — bitwise equal
# to jnp.argmax of the exit logits, first-occurrence tie-break included
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["ref", "interpret"])
def test_pred_matches_argmax_bitwise(backend):
    key = jax.random.PRNGKey(17)
    x = jax.random.normal(key, (8, 33), jnp.float32) * 4.0
    # force ties: column 5 equals each row's max, so first-occurrence
    # tie-breaking is what distinguishes a correct pred from a plausible one
    x = x.at[:, 5].set(x.max(axis=-1))
    _, pred, _ = dispatch.exit_decision_op(x, 0.5, backend=backend)
    np.testing.assert_array_equal(
        np.asarray(pred),
        np.asarray(jnp.argmax(x, axis=-1).astype(jnp.int32)))


# ---------------------------------------------------------------------------
# toy-fns scheduler runs: fused vs composed token-stream + stats parity, and
# the single-launch steady-state tick contract
# ---------------------------------------------------------------------------

def _toy_run(q_pct, n_toks, *, n_slots, capacity, queue_depth):
    fns = toy_decode_fns(q_pct)
    sc = SL.ServeConfig(capacity=capacity, queue_depth=queue_depth,
                        c_thr=0.5)
    sched = ContinuousScheduler(fns, sc, n_slots=n_slots,
                                max_len=_TOY_S + max(n_toks),
                                clock=LogicalClock())
    for i, n in enumerate(n_toks):
        sched.submit(Request(sample_id=i,
                             prompt=np.full((_TOY_S,), i, np.int32),
                             n_tokens=n))
    return sched.run(), sched.stats


@pytest.mark.parametrize("q_pct", [40, 100])
def test_fused_vs_composed_streams_and_stats(q_pct):
    """Same trace through the fused single-launch tick and the composed
    three-program tick: identical per-sample token streams AND identical
    serving counters — incl. n_stalls, so the fused overflow spill enters
    backpressure exactly where the composed chain would (q=100 with a
    2-row ring under a 6-slot pool overflows every tick)."""
    n_toks = [5, 3, 6, 1, 4, 5]
    with mock.patch.object(ContinuousScheduler, "_use_fused",
                           lambda self: False):
        res_c, st_c = _toy_run(q_pct, n_toks, n_slots=6, capacity=2,
                               queue_depth=1)
    res_f, st_f = _toy_run(q_pct, n_toks, n_slots=6, capacity=2,
                           queue_depth=1)
    expect = {i: [_toy_tok(i, t) for t in range(n)]
              for i, n in enumerate(n_toks)}
    assert res_f == expect
    assert res_c == expect
    for fld in ("n_decisions", "n_exited", "n_stage2", "n_stalls",
                "n_stage1_batches", "n_buckets"):
        assert getattr(st_f, fld) == getattr(st_c, fld), fld
    if q_pct == 100:
        assert st_f.n_stalls > 0        # the overflow spill really stalled


def test_steady_state_tick_is_single_program(monkeypatch):
    """The acceptance bar: a no-admission no-drain decode tick is ONE
    compiled program. Counted three ways — every tick goes through the
    fused launch (the composed tick would raise), no separate enqueue
    program runs, and the stage-1 body is never retraced once warm."""
    traces = {"n": 0}
    fns = toy_decode_fns(0, trace_counter=traces)     # all-easy traffic
    fused_calls = {"n": 0}
    real_fused = SCH._pool_tick_fused

    def counting_fused(*a, **k):
        fused_calls["n"] += 1
        return real_fused(*a, **k)

    def no_composed(*a, **k):
        raise AssertionError("composed _pool_tick ran in fused mode")

    def no_enqueue_range(*a, **k):
        raise AssertionError("separate ring-enqueue program launched "
                             "during an all-easy steady-state tick")

    monkeypatch.setattr(SCH, "_pool_tick_fused", counting_fused)
    monkeypatch.setattr(SCH, "_pool_tick", no_composed)
    monkeypatch.setattr(SCH, "_ring_enqueue_range", no_enqueue_range)

    sc = SL.ServeConfig(capacity=2, queue_depth=2, c_thr=0.5)
    sched = ContinuousScheduler(fns, sc, n_slots=4, max_len=_TOY_S + 12,
                                clock=LogicalClock())
    for i in range(4):
        sched.submit(Request(sample_id=i,
                             prompt=np.full((_TOY_S,), i, np.int32),
                             n_tokens=10))
    assert sched.step() == "busy"          # admission + first (warm-up) tick
    assert fused_calls["n"] == 1
    warm_traces = traces["n"]
    assert warm_traces >= 1                # eval_shape + the tick compile
    for k in range(5):                     # steady state: pool full, ring
        assert sched.step() == "busy"      # empty, nothing admitted
        assert fused_calls["n"] == 2 + k
    assert traces["n"] == warm_traces      # zero retraces: one program
    res = sched.run()
    assert res == {i: [_toy_tok(i, t) for t in range(10)] for i in range(4)}


def test_fused_tick_off_for_disaggregated_placement():
    """A placement whose stages live on different submeshes must keep the
    composed chain (the enqueue IS the cross-submesh hop)."""
    fns = toy_decode_fns(50)
    sc = SL.ServeConfig(capacity=2, queue_depth=2, c_thr=0.5)
    sched = ContinuousScheduler(fns, sc, n_slots=2, max_len=_TOY_S + 4,
                                clock=LogicalClock())
    sched.submit(Request(sample_id=0, prompt=np.zeros(_TOY_S, np.int32),
                         n_tokens=3))
    sched.run()
    assert sched._use_fused()              # single-device default: fused on
    with mock.patch.object(type(sched.placement), "disaggregated",
                           property(lambda self: True)):
        assert not sched._use_fused()


def test_fused_tick_falls_back_when_fns_resist_eval_shape():
    """Duck-typed host-side stage fns that cannot be abstractly evaluated
    (they pull their inputs to the host) must keep the composed tick
    rather than fail at pool build."""
    fns = toy_decode_fns(0)

    def opaque_s1(tok, c1, pos):
        return np.asarray(tok)          # host-side: no abstract evaluation

    hacked = fns._replace(s1_raw=opaque_s1)
    sc = SL.ServeConfig(capacity=2, queue_depth=2, c_thr=0.5)
    sched = ContinuousScheduler(hacked, sc, n_slots=2, max_len=_TOY_S + 4,
                                clock=LogicalClock())
    c1 = {"first": [jnp.zeros((2, 1), jnp.int32)], "blocks": (), "rem": []}
    rows = {"sid": jnp.zeros((2, 1), jnp.int32)}
    sched._ensure_pool(c1, rows)
    assert sched._ring_row_spec is None
    assert not sched._use_fused()


def test_fused_tick_eval_shape_failure_on_traceable_fns_raises():
    """A stage-1 body that traces but fails (a real model bug) must raise
    at pool build, never drop silently to the composed tick."""
    fns = toy_decode_fns(0)

    def broken_s1(tok, c1, pos):
        return tok @ jnp.zeros((3, 3), tok.dtype)   # shape mismatch

    broken = fns._replace(s1_raw=broken_s1)
    sc = SL.ServeConfig(capacity=2, queue_depth=2, c_thr=0.5)
    sched = ContinuousScheduler(broken, sc, n_slots=2, max_len=_TOY_S + 4,
                                clock=LogicalClock())
    c1 = {"first": [jnp.zeros((2, 1), jnp.int32)], "blocks": (), "rem": []}
    rows = {"sid": jnp.zeros((2, 1), jnp.int32)}
    with pytest.raises(TypeError):
        sched._ensure_pool(c1, rows)
