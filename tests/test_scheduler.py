"""Continuous-batching decode scheduler: per-sample token-stream
equivalence against the host-loop oracle (the continuous correctness
contract — same greedy tokens per sample id, any interleaving), scheduler
invariants under random traces (hypothesis over toy stage callables),
latency / realized-q statistics, the per-metric tolerance machinery in
benchmarks/compare.py, and the disaggregated equivalence bar (in-process
when the host exposes 8 devices, subprocess on every tier-1 run)."""
import subprocess
import sys
import textwrap
import warnings
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import early_exit as ee
from repro.runtime import serve_loop as SL
from repro.runtime.scheduler import (ContinuousScheduler, LogicalClock,
                                     Request, ServeStats, SyncScheduler,
                                     poisson_arrivals)

_REPO_ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def prompt(tiny_cfg):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(21), (6, 8), 0,
                                         tiny_cfg.vocab))


@pytest.fixture(scope="module")
def fns(tiny_cfg, tiny_params, tiny_spec):
    return SL.decode_stage_fns(tiny_params, tiny_cfg, tiny_spec)


def _decode_conf(tiny_cfg, tiny_params, tiny_spec, prompt, max_len):
    return np.asarray(SL.decode_step0_confidences(
        tiny_params, tiny_cfg, tiny_spec, prompt, max_len=max_len))


def _expect_streams(oracle_tokens, n_tokens):
    """Per-sample expected streams from a HostLoopDecoder (B, T) output."""
    return {i: [int(x) for x in oracle_tokens[i][:n_tokens[i]]]
            for i in range(len(n_tokens))}


N_TOKS = [7, 3, 5, 1, 7, 2]          # variable lengths incl. a prefill-only


def _run_continuous(fns, sc, prompt, n_tokens, n_slots, max_len,
                    arrivals=None, **kw):
    sched = ContinuousScheduler(fns, sc, n_slots=n_slots, max_len=max_len,
                                clock=LogicalClock(), **kw)
    for i in range(len(n_tokens)):
        t = 0.0 if arrivals is None else float(arrivals[i])
        sched.submit(Request(sample_id=i, prompt=prompt[i],
                             n_tokens=n_tokens[i], arrival_time=t))
    return sched.run(), sched


# ---------------------------------------------------------------------------
# the tentpole contract: per-sample greedy token streams identical to the
# host-loop oracle — all-exit, none-exit, mixed, and the calibrated q grid
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("c_thr", [0.0, 1.1, None])
def test_continuous_token_stream_equivalence(tiny_cfg, tiny_params,
                                             tiny_spec, prompt, fns, c_thr):
    """A pool smaller than the request count (backfill), variable lengths
    (incl. a one-token request), every sample's stream equal to the
    host-loop decode — for all-exit, none-exit, and mixed traffic."""
    max_tok = max(N_TOKS)
    if c_thr is None:
        conf = _decode_conf(tiny_cfg, tiny_params, tiny_spec, prompt,
                            prompt.shape[1] + max_tok)
        c_thr = float(np.median(conf))
    sc = SL.ServeConfig(capacity=3, queue_depth=2, c_thr=c_thr)
    oracle = SL.HostLoopDecoder(fns, sc).generate(prompt, max_tok)
    res, sched = _run_continuous(fns, sc, prompt, N_TOKS, n_slots=4,
                                 max_len=prompt.shape[1] + max_tok)
    assert res == _expect_streams(oracle["tokens"], N_TOKS)
    assert sched.stats.n_samples == len(N_TOKS)
    assert sched.stats.n_finished == len(N_TOKS)


def test_continuous_equivalence_q_grid(tiny_cfg, tiny_params, tiny_spec,
                                       prompt, fns):
    """The acceptance bar: identical per-sample streams at calibrated
    q ∈ {0.1, 0.3, 0.5} (single-device; the disaggregated half runs in the
    subprocess test below and in the 8-device CI job)."""
    max_tok = max(N_TOKS)
    conf = _decode_conf(tiny_cfg, tiny_params, tiny_spec, prompt,
                        prompt.shape[1] + max_tok)
    for q in (0.1, 0.3, 0.5):
        c_thr = float(np.quantile(conf, q))
        sc = SL.ServeConfig(capacity=2, queue_depth=2, c_thr=c_thr)
        oracle = SL.HostLoopDecoder(fns, sc).generate(prompt, max_tok)
        res, _ = _run_continuous(fns, sc, prompt, N_TOKS, n_slots=3,
                                 max_len=prompt.shape[1] + max_tok)
        assert res == _expect_streams(oracle["tokens"], N_TOKS), q


def test_continuous_backpressure_ring_smaller_than_pool(tiny_cfg,
                                                        tiny_params, prompt,
                                                        fns):
    """All-hard traffic through a ring smaller than the pool: the chunked
    enqueue must stall (full buckets drain first), never deadlock, never
    drop — and streams stay equivalent."""
    sc = SL.ServeConfig(capacity=2, queue_depth=2, c_thr=1.1)
    n_toks = [5] * prompt.shape[0]
    assert sc.queue_depth * sc.capacity < prompt.shape[0]
    oracle = SL.HostLoopDecoder(fns, sc).generate(prompt, 5)
    res, sched = _run_continuous(fns, sc, prompt, n_toks,
                                 n_slots=prompt.shape[0],
                                 max_len=prompt.shape[1] + 5)
    assert sched.stats.n_stalls > 0
    assert res == _expect_streams(oracle["tokens"], n_toks)


def test_continuous_eager_drain_off(tiny_cfg, tiny_params, tiny_spec,
                                    prompt, fns):
    """eager_drain_below=0 recovers pure full-bucket dispatch (maximum
    bucket fill) and still drains correctly via the all-parked path."""
    conf = _decode_conf(tiny_cfg, tiny_params, tiny_spec, prompt, 15)
    c_thr = float(np.median(conf))
    sc = SL.ServeConfig(capacity=3, queue_depth=2, c_thr=c_thr)
    oracle = SL.HostLoopDecoder(fns, sc).generate(prompt, 7)
    n_toks = [7] * prompt.shape[0]
    res, _ = _run_continuous(fns, sc, prompt, n_toks, n_slots=4,
                             max_len=15, eager_drain_below=0)
    assert res == _expect_streams(oracle["tokens"], n_toks)


def test_sync_scheduler_matches_oracle(tiny_cfg, tiny_params, prompt, fns):
    """The degenerate sync policy (batch formation over DecodeServer,
    incl. a smaller partial tail batch) yields the same truncated streams,
    records per-request latency, and counts only real traffic."""
    sc = SL.ServeConfig(capacity=3, queue_depth=2, c_thr=0.9)
    oracle = SL.HostLoopDecoder(fns, sc).generate(prompt, max(N_TOKS))
    sched = SyncScheduler(SL.DecodeServer(fns, sc), n_slots=4,
                          clock=LogicalClock())
    for i in range(len(N_TOKS)):
        sched.submit(Request(sample_id=i, prompt=prompt[i],
                             n_tokens=N_TOKS[i]))
    res = sched.run()
    assert res == _expect_streams(oracle["tokens"], N_TOKS)
    assert sched.stats.n_finished == len(N_TOKS)
    assert sched.stats.n_samples == len(N_TOKS)     # padding isn't traffic


def test_continuous_admission_gating(tiny_cfg, tiny_params, prompt, fns):
    """A request whose arrival_time is in the future is not admitted until
    the clock reaches it (the scheduler fast-forwards when idle)."""
    sc = SL.ServeConfig(capacity=2, queue_depth=2, c_thr=0.0)
    n_toks = [3] * 4
    arrivals = [0.0, 0.0, 5.0, 9.0]
    res, sched = _run_continuous(fns, sc, prompt[:4], n_toks, n_slots=4,
                                 max_len=prompt.shape[1] + 3,
                                 arrivals=arrivals)
    assert sorted(res) == [0, 1, 2, 3]
    assert sched.clock.now() >= 9.0                  # fast-forwarded
    assert sched.stats.n_finished == 4
    # the late arrivals can't have finished before they arrived
    assert all(lat >= 0.0 for lat in sched.stats.latencies)


def test_serving_programs_take_params_as_arguments(tiny_cfg, tiny_params,
                                                   tiny_spec, prompt):
    """The params enter every serving program — prefill, the pool tick,
    the paged stage-2 bucket, the host-loop oracle, the prefill server —
    as ARGUMENTS. A jit that closes over them embeds them as constants,
    which at published widths is gigabytes of host memory per program."""
    from repro.runtime import serve_api
    n_bytes = sum(x.nbytes for x in jax.tree.leaves(tiny_params))
    n_new = 8
    max_len = prompt.shape[1] + n_new
    c_thr = float(np.median(_decode_conf(tiny_cfg, tiny_params, tiny_spec,
                                         prompt, max_len)))
    sc = SL.ServeConfig(capacity=2, queue_depth=2, c_thr=c_thr)
    build = lambda **kw: serve_api.build(tiny_params, tiny_cfg, tiny_spec,
                                         sc, **kw)
    before = jax.config.jax_captured_constants_warn_bytes
    jax.config.update("jax_captured_constants_warn_bytes", n_bytes // 4)
    try:
        with warnings.catch_warnings():
            warnings.filterwarnings(
                "error", message="A large amount of constants")
            sched = build(mode="decode", scheduler="continuous", n_slots=4,
                          max_len=max_len, page_size=4, clock=LogicalClock())
            for i, p in enumerate(prompt):
                sched.submit(Request(i, p, n_tokens=n_new))
            res = sched.run()
            assert 0 < sched.stats.n_exited < sched.stats.n_decisions
            sched.tick_hlo()
            oracle = build(mode="decode", scheduler=None,
                           host=True).generate(prompt, n_new)
            SL.serve_dataset(build(mode="prefill", scheduler=None), prompt,
                             batch=4)
    finally:
        jax.config.update("jax_captured_constants_warn_bytes", before)
    assert res == _expect_streams(oracle["tokens"], [n_new] * len(prompt))


def test_continuous_rejects_overlong_and_duplicate(tiny_cfg, tiny_params,
                                                   prompt, fns):
    """Malformed requests are rejected at submit() — before they can be
    popped into a chunk and damage in-flight state."""
    sc = SL.ServeConfig(capacity=2, queue_depth=2, c_thr=0.0)
    sched = ContinuousScheduler(fns, sc, n_slots=2, max_len=10,
                                clock=LogicalClock())
    with pytest.raises(ValueError, match="exceeds pool max_len"):
        sched.submit(Request(0, prompt[0], n_tokens=99))
    with pytest.raises(ValueError, match="n_tokens must be >= 1"):
        sched.submit(Request(1, prompt[0], n_tokens=0))
    sched = ContinuousScheduler(fns, sc, n_slots=2, max_len=12,
                                clock=LogicalClock())
    sched.submit(Request(0, prompt[0], n_tokens=2))
    with pytest.raises(ValueError, match="duplicate sample id"):
        sched.submit(Request(0, prompt[1], n_tokens=2))
    # an already-ADMITTED sid is also rejected on a later submit
    sched.submit(Request(1, prompt[1], n_tokens=2))
    sched.run()
    with pytest.raises(ValueError, match="duplicate sample id"):
        sched.submit(Request(1, prompt[2], n_tokens=2))


# ---------------------------------------------------------------------------
# ServeStats: per-request latency + per-dispatch realized-q series
# ---------------------------------------------------------------------------

def test_serve_stats_latency_percentiles():
    st = ServeStats()
    for i, dt in enumerate([0.1, 0.2, 0.3, 0.4, 1.0]):
        st.record_submit(i, 10.0)
        st.record_finish(i, 10.0 + dt)
    assert st.n_finished == 5
    np.testing.assert_allclose(st.latency_p50, 0.3)
    np.testing.assert_allclose(st.latency_p90, 0.76)
    np.testing.assert_allclose(st.latency_p99, 0.976)
    d = st.as_dict()
    for k in ("latency_p50", "latency_p90", "latency_p99", "n_finished"):
        assert k in d
    # unmatched finish is ignored, empty percentiles are 0.0
    st2 = ServeStats()
    st2.record_finish(7, 1.0)
    assert st2.n_finished == 0 and st2.latency_p99 == 0.0


def test_serve_stats_realized_q_series():
    st = ServeStats()
    st.record_decisions(10, 3)
    st.record_decisions(10, 7)
    st.record_decisions(0, 0)
    assert list(st.realized_q_series) == [0.3, 0.7, 0.0]
    assert st.as_dict()["realized_q_series"] == [0.3, 0.7, 0.0]


def test_scheduler_stats_latency_recorded(tiny_cfg, tiny_params, prompt,
                                          fns):
    """The continuous scheduler stamps submit->finish per request and the
    q series grows one entry per pool tick."""
    sc = SL.ServeConfig(capacity=2, queue_depth=2, c_thr=1.1)
    n_toks = [4] * 4
    res, sched = _run_continuous(fns, sc, prompt[:4], n_toks, n_slots=4,
                                 max_len=prompt.shape[1] + 4)
    st = sched.stats
    assert st.n_finished == 4
    assert len(st.realized_q_series) == st.n_stage1_batches
    assert all(v == 1.0 for v in st.realized_q_series)   # all-hard
    assert not st.submit_times                           # all matched
    del res


# ---------------------------------------------------------------------------
# scheduler invariants under random traces: hypothesis over TOY stage fns
# (the policy machinery — slots, ring, buckets, backfill — with an
# analytically known token stream, so no model compute in the loop)
# ---------------------------------------------------------------------------

_TOY_VOCAB = 32
_TOY_S = 4


def _toy_tok(sid, t):
    return (3 + sid * 31 + t * 7) % _TOY_VOCAB


def _toy_hard(sid, t, q_pct):
    return ((sid * 131 + t * 17) % 100) < q_pct


def toy_decode_fns(q_pct: int):
    """DecodeFns whose exit decisions and greedy tokens are pure functions
    of (sample id, decode index): hard iff hash(sid, t) < q_pct; token =
    _toy_tok(sid, t). The sample id rides the stage-1 cache / stage-2 row
    payload, so the scheduler's plumbing is exactly what's under test."""

    def _logits(sid, t):
        tok = _toy_tok(sid, t)
        hard = _toy_hard(sid, t, q_pct)
        oh = jax.nn.one_hot(tok, _TOY_VOCAB)
        return jnp.where(hard[:, None], oh * 1e-3, oh * 50.0)

    def prefill(prompts, max_len):
        sid = prompts[:, 0].astype(jnp.int32)
        caches = {"first": [sid[:, None]], "blocks": (), "rem": []}
        return _logits(sid, jnp.zeros_like(sid)), caches

    def split(caches):
        return caches, {"sid": caches["first"][0]}

    def s1_raw(tok, c1, pos):
        sid = c1["first"][0][:, 0]
        t = pos - _TOY_S + 1                 # decode index being produced
        h = jnp.stack([sid, pos], 1).astype(jnp.float32)
        return h, c1, _logits(sid, t)

    def s2(h_rows, cache_rows, step):
        sid = cache_rows["sid"][:, 0]
        return _logits(sid, step - _TOY_S + 1), cache_rows

    return SL.DecodeFns(prefill, split, jax.jit(s1_raw), s2, s1_raw)


def _toy_requests(n_tokens_list):
    return [Request(sample_id=i,
                    prompt=np.full((_TOY_S,), i, np.int32),
                    n_tokens=n)
            for i, n in enumerate(n_tokens_list)]


def _toy_expected(n_tokens_list):
    return {i: [_toy_tok(i, t) for t in range(n)]
            for i, n in enumerate(n_tokens_list)}


def test_toy_fns_mixed_trace_smoke():
    """Deterministic smoke of the toy harness itself (hypothesis-free, so
    the property tests' failures can be attributed to the scheduler)."""
    fns = toy_decode_fns(q_pct=40)
    sc = SL.ServeConfig(capacity=2, queue_depth=2, c_thr=0.5)
    n_toks = [5, 1, 3, 6, 2]
    sched = ContinuousScheduler(fns, sc, n_slots=3, max_len=_TOY_S + 6,
                                clock=LogicalClock())
    for r in _toy_requests(n_toks):
        sched.submit(r)
    assert sched.run() == _toy_expected(n_toks)


try:
    from hypothesis import given, settings, strategies as st_h
    _HAVE_HYP = True
except ImportError:                                   # pragma: no cover
    _HAVE_HYP = False


if _HAVE_HYP:
    @settings(max_examples=25, deadline=None)
    @given(
        n_tokens_list=st_h.lists(st_h.integers(1, 6), min_size=1,
                                 max_size=10),
        n_slots=st_h.integers(1, 5),
        capacity=st_h.integers(1, 4),
        queue_depth=st_h.integers(1, 3),
        q_pct=st_h.integers(0, 100),
        eager=st_h.integers(0, 3),
        arrival_gaps=st_h.lists(st_h.floats(0.0, 2.0), min_size=10,
                                max_size=10),
    )
    def test_scheduler_invariants_random_traces(n_tokens_list, n_slots,
                                                capacity, queue_depth,
                                                q_pct, eager, arrival_gaps):
        """Under random q / arrival traces and pool/ring geometries: no
        sample id dropped or duplicated, per-sample token order preserved
        (streams equal the analytic oracle exactly), slot occupancy never
        exceeds the pool, and the pool fully drains."""
        fns = toy_decode_fns(q_pct=q_pct)
        sc = SL.ServeConfig(capacity=capacity, queue_depth=queue_depth,
                            c_thr=0.5, max_pending=2)
        sched = ContinuousScheduler(fns, sc, n_slots=n_slots,
                                    max_len=_TOY_S + 6,
                                    clock=LogicalClock(),
                                    eager_drain_below=eager)
        t = 0.0
        for r, gap in zip(_toy_requests(n_tokens_list), arrival_gaps):
            t += gap
            r.arrival_time = t
            sched.submit(r)
        res = sched.run()
        expect = _toy_expected(n_tokens_list)
        assert set(res) == set(expect)               # no drop, no phantom
        assert res == expect                         # order + no dup
        assert sched.peak_busy <= n_slots
        assert len(sched._free) == n_slots           # fully drained
        assert sched.stats.n_samples == len(n_tokens_list)
        assert sched.stats.n_finished == len(n_tokens_list)
        total_decode = sum(n - 1 for n in n_tokens_list)
        assert sched.stats.n_decisions == total_decode
        assert sched.stats.n_exited + sched.stats.n_stage2 == total_decode


# ---------------------------------------------------------------------------
# benchmarks/compare.py: per-metric tolerance overrides
# ---------------------------------------------------------------------------

def _gate(value, spec, got):
    from benchmarks.compare import compare
    current = {"schema_version": 1, "benches": {"b": {"m": got}}}
    baseline = {"schema_version": 1,
                "metrics": {"b.m": {"value": value, **spec}}}
    return compare(current, baseline)


def test_compare_relative_tolerance_default():
    assert _gate(2.0, {}, 1.6)["ok"]                 # -20% within 25%
    assert not _gate(2.0, {}, 1.4)["ok"]             # -30% beyond 25%


def test_compare_abs_tolerance_composition():
    """Band = max(rel * |baseline|, abs): absolute slack keeps near-zero
    baselines from flapping; relative slack rules large ones."""
    spec = {"tolerance": 0.1, "abs_tolerance": 0.5}
    assert _gate(0.2, spec, -0.25)["ok"]             # |drop| 0.45 < abs 0.5
    assert not _gate(0.2, spec, -0.35)["ok"]
    assert _gate(100.0, spec, 91.0)["ok"]            # rel 10% = 10 > abs
    assert not _gate(100.0, spec, 89.0)["ok"]


def test_compare_hard_min_bound():
    """`min` is a contract floor enforced regardless of tolerance — the
    serve_continuous >=1.3x goodput gate."""
    spec = {"tolerance": 0.25, "min": 1.3}
    assert _gate(1.45, spec, 1.31)["ok"]
    r = _gate(1.45, spec, 1.25)                      # tolerance would allow
    assert not r["ok"]
    assert r["metrics"]["b.m"]["bound_low"] == 1.3


def test_compare_hard_max_bound_lower_is_better():
    spec = {"direction": "lower", "tolerance": 1.0, "max": 2.0}
    assert _gate(1.0, spec, 1.9)["ok"]
    assert not _gate(1.0, spec, 2.1)["ok"]           # cap wins over rel 2.0


def test_compare_bounds_clamp_both_directions():
    """A `max` sanity cap on a higher-is-better metric (and a `min` on a
    lower-is-better one) is honored too — 'regardless of tolerances' means
    both directions, e.g. catching an absurd ratio from a clock bug."""
    spec = {"tolerance": 0.25, "max": 5.0}
    assert _gate(1.45, spec, 2.0)["ok"]
    assert not _gate(1.45, spec, 50.0)["ok"]
    spec = {"direction": "lower", "tolerance": 1.0, "min": 0.1}
    assert _gate(1.0, spec, 0.5)["ok"]
    assert not _gate(1.0, spec, 0.01)["ok"]


def test_compare_nan_fails():
    assert not _gate(1.45, {"min": 1.3}, float("nan"))["ok"]


# ---------------------------------------------------------------------------
# disaggregated equivalence: in-process on an 8-device host (CI job), and a
# subprocess bar on every tier-1 run — single-device AND disaggregated
# continuous streams vs the host-loop oracle at q ∈ {0.1, 0.3, 0.5}
# ---------------------------------------------------------------------------

@pytest.mark.skipif(
    jax.device_count() < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8")
def test_continuous_disaggregated_equivalence_8dev(tiny_cfg, tiny_params,
                                                   tiny_spec, prompt):
    from repro.core.stage_mesh import StageMeshPlan
    from repro.runtime.stage_executor import StagePlacement
    conf = _decode_conf(tiny_cfg, tiny_params, tiny_spec, prompt, 13)
    c_thr = float(np.median(conf))
    sc = SL.ServeConfig(capacity=2, queue_depth=2, c_thr=c_thr)
    spec = ee.EarlyExitSpec(exit_layer=2, c_thr=c_thr)
    oracle = SL.build_host_decoder(tiny_params, tiny_cfg, spec,
                                   sc).generate(prompt, 5)
    pl = StagePlacement.from_plan(
        StageMeshPlan.proportional(0.5, jax.device_count()))
    sched = SL.build_continuous_scheduler(tiny_params, tiny_cfg, spec, sc,
                                          n_slots=4, max_len=13,
                                          placement=pl,
                                          clock=LogicalClock())
    n_toks = [5] * prompt.shape[0]
    for r in [Request(i, prompt[i], 5) for i in range(prompt.shape[0])]:
        sched.submit(r)
    assert sched.run() == _expect_streams(oracle["tokens"], n_toks)
    assert sched.stats.stage1_chips + sched.stats.stage2_chips == 8


def test_continuous_equivalence_subprocess():
    """The acceptance bar on every tier-1 run: continuous streams equal the
    host-loop oracle at q ∈ {0.1, 0.3, 0.5}, single-device AND
    stage-disaggregated, under --xla_force_host_platform_device_count=8."""
    code = ("import os\n"
            "os.environ['XLA_FLAGS']="
            "'--xla_force_host_platform_device_count=8'\n"
            "import sys; sys.path.insert(0, 'src')\n" + textwrap.dedent("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.core import early_exit as ee
    from repro.core.stage_mesh import StageMeshPlan
    from repro.models.config import ArchConfig
    from repro.runtime import serve_loop as SL
    from repro.runtime.scheduler import LogicalClock, Request
    from repro.runtime.stage_executor import StagePlacement

    cfg = ArchConfig(name="t", family="dense", n_layers=4, d_model=32,
                     n_heads=4, n_kv_heads=2, d_ff=64, vocab=64,
                     dtype="float32", param_dtype="float32",
                     tie_embeddings=True)
    spec0 = ee.EarlyExitSpec(exit_layer=2, c_thr=0.5)
    params = ee.init_ee_params(jax.random.PRNGKey(0), cfg, spec0)
    prompt = np.asarray(jax.random.randint(jax.random.PRNGKey(21), (6, 8),
                                           0, cfg.vocab))
    n_toks = [5, 3, 5, 1, 4, 2]
    conf = SL.decode_step0_confidences(params, cfg, spec0, prompt,
                                       max_len=13)
    def run_sched(spec, sc, placement):
        s = SL.build_continuous_scheduler(params, cfg, spec, sc, n_slots=3,
                                          max_len=13, placement=placement,
                                          clock=LogicalClock())
        for i in range(6):
            s.submit(Request(i, prompt[i], n_toks[i]))
        return s.run()
    for q in (0.1, 0.3, 0.5):
        c_thr = float(jnp.quantile(conf, q))
        spec = ee.EarlyExitSpec(exit_layer=2, c_thr=c_thr)
        sc = SL.ServeConfig(capacity=2, queue_depth=2, c_thr=c_thr)
        oracle = SL.build_host_decoder(params, cfg, spec,
                                       sc).generate(prompt, 5)
        want = {i: [int(x) for x in oracle["tokens"][i][:n_toks[i]]]
                for i in range(6)}
        assert run_sched(spec, sc, None) == want, ("single", q)
        pl = StagePlacement.from_plan(
            StageMeshPlan.proportional(q, jax.device_count()))
        assert run_sched(spec, sc, pl) == want, ("disagg", q)
        print("q", q, "OK")
    print("EQUIV_ALL_OK")
    """))
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, cwd=_REPO_ROOT, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "EQUIV_ALL_OK" in r.stdout
