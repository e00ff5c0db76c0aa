"""Online drift control plane: telemetry filters, the controller state
machine (warmup / hysteresis / cooldown / bounded actuation), the
scheduler control surface (threshold, drain policy, occupancy cap,
discrete-point capacity re-size — with token streams invariant under every
actuation), and closed-loop convergence on a nonstationary trace.

The end-to-end tests drive the REAL ``ContinuousScheduler`` with the drift
benchmark's semi-synthetic ``DecodeFns`` (analytic confidences/tokens, so
expected streams are known exactly and hard rates are controllable — see
``benchmarks/serve_drift.py``)."""
import jax
import numpy as np
import pytest

from benchmarks.serve_drift import (PROVISIONED_P, conf_of, difficulty_trace,
                                    drift_fns, make_controller, token_of)
from repro.core import exit_decision as ed
from repro.core.stage_mesh import StageMeshPlan, stage2_capacity
from repro.runtime import serve_loop as SL
from repro.runtime import telemetry as TM
from repro.runtime.controller import ControllerConfig, DriftController
from repro.runtime.scheduler import (ContinuousScheduler, LogicalClock,
                                     Request, ServeStats)
from repro.runtime.stage_executor import StagePlacement

_S = 4                       # drift_fns prompt length


# ---------------------------------------------------------------------------
# telemetry: the shared drift filter + rolling reservoir + control windows
# ---------------------------------------------------------------------------

def test_ewma_empty_and_constant():
    assert TM.ewma([]) == 0.0
    assert TM.ewma([0.3] * 50) == pytest.approx(0.3)


def test_ewma_window_bound():
    """Entries older than the window cannot haunt the estimate."""
    series = [1.0] * 10_000 + [0.0] * TM.DRIFT_WINDOW
    assert TM.ewma(series) < 1e-6
    # and the same series truncated to the window is identical
    assert TM.ewma(series) == TM.ewma(series[-TM.DRIFT_WINDOW:])


def test_ewma_tracks_step_change():
    """A step in q crosses most of the gap within ~2/alpha entries."""
    series = [0.25] * 100 + [0.8] * 30
    assert TM.ewma(series, alpha=0.1) > 0.6


def test_confidence_reservoir_rolls():
    r = TM.ConfidenceReservoir(size=8)
    r.extend(np.linspace(0.0, 1.0, 20))
    assert len(r) == 8 and r.full
    np.testing.assert_allclose(r.snapshot(),
                               np.linspace(0.0, 1.0, 20)[-8:].astype(
                                   np.float32))
    r.clear()
    assert len(r) == 0
    with pytest.raises(ValueError):
        TM.ConfidenceReservoir(size=0)


def test_control_window_deltas_survive_reset():
    """observe_counters folds LIFETIME counters as per-visit deltas; the
    high-water marks persist across window resets so a new window never
    re-counts old stalls."""
    w = TM.ControlWindow()
    w.observe(8, 2)
    w.observe_counters(n_stalls=3, n_buckets=2, bucket_fill_sum=1.5)
    assert w.stalls == 3 and w.mean_bucket_fill == pytest.approx(0.75)
    assert w.q == pytest.approx(0.25) and w.mean_active == 8
    w.reset()
    w.observe(4, 4)
    w.observe_counters(n_stalls=4, n_buckets=3, bucket_fill_sum=2.5)
    assert w.stalls == 1 and w.buckets == 1          # deltas, not lifetime
    assert w.q == pytest.approx(1.0)


def test_serve_stats_windowed_drift_view():
    """ServeStats exposes the windowed drift view through the SAME ewma
    definition the controller uses, and as_dict carries it."""
    st = ServeStats()
    for _ in range(20):
        st.record_decisions(10, 8)
    assert st.q_drift == 0.0                         # no provisioned p
    st.provisioned_p = 0.25
    assert st.realized_q_ewma == pytest.approx(
        TM.ewma(st.realized_q_series))
    assert st.q_drift == pytest.approx(st.realized_q_ewma - 0.25)
    d = st.as_dict()
    for k in ("provisioned_p", "realized_q_ewma", "q_drift"):
        assert k in d
    assert d["q_drift"] == pytest.approx(0.8 - 0.25)


# ---------------------------------------------------------------------------
# calibrate_threshold edge cases: the controller re-solves it ONLINE, so
# its corners are part of the control plane's contract
# ---------------------------------------------------------------------------

def test_calibrate_threshold_empty_and_bad_rate_raise():
    """An empty reservoir or a garbage target must fail loudly, never
    return a NaN threshold into the actuation path."""
    import jax.numpy as jnp
    with pytest.raises(ValueError, match="non-empty"):
        ed.calibrate_threshold(jnp.zeros((0,)), 0.5)
    conf = jnp.asarray([0.2, 0.6, 0.9])
    for bad in (-0.1, 1.5):
        with pytest.raises(ValueError, match="target_exit_rate"):
            ed.calibrate_threshold(conf, bad)


def test_calibrate_threshold_rate_extremes():
    """target 0: nobody exits (strict conf > C_thr); target 1: everybody
    exits, including ties at the minimum."""
    import jax.numpy as jnp
    conf = jnp.asarray([0.3, 0.3, 0.5, 0.7, 0.9])
    thr0 = ed.calibrate_threshold(conf, 0.0)
    assert float((np.asarray(conf) > thr0).mean()) == 0.0
    thr1 = ed.calibrate_threshold(conf, 1.0)
    assert thr1 < 0.3
    assert float((np.asarray(conf) > thr1).mean()) == 1.0
    # a single-element set works at every rate
    one = jnp.asarray([0.6])
    assert not bool(0.6 > ed.calibrate_threshold(one, 0.0))
    assert bool(0.6 > ed.calibrate_threshold(one, 1.0))


def test_calibrate_threshold_ties_at_quantile_boundary():
    """Mass at the quantile boundary under-exits (the strict comparison
    sends boundary samples to stage 2 — the conservative side), never
    over-exits."""
    import jax.numpy as jnp
    # all-identical confidences: any interior rate realizes 0 exits
    flat = jnp.full((100,), 0.5)
    thr = ed.calibrate_threshold(flat, 0.4)
    assert thr == pytest.approx(0.5)
    assert float((np.asarray(flat) > thr).mean()) == 0.0
    # bimodal with the quantile landing on the upper atom: the atom stays
    # hard (realized <= target), the clearly-confident half still exits
    bimodal = jnp.asarray([0.3] * 50 + [0.7] * 50)
    thr = ed.calibrate_threshold(bimodal, 0.25)
    assert float((np.asarray(bimodal) > thr).mean()) <= 0.25 + 1e-9
    thr_half = ed.calibrate_threshold(bimodal, 0.5)
    assert float((np.asarray(bimodal) > thr_half).mean()) == pytest.approx(
        0.5)


# ---------------------------------------------------------------------------
# ControllerConfig validation + the state machine over a fake scheduler
# ---------------------------------------------------------------------------

def test_controller_config_validation():
    with pytest.raises(ValueError, match="provisioned_p"):
        ControllerConfig(provisioned_p=0.0)
    with pytest.raises(ValueError, match="release_band"):
        ControllerConfig(provisioned_p=0.3, target_band=0.05,
                         release_band=0.05)
    with pytest.raises(ValueError, match="replan_band"):
        ControllerConfig(provisioned_p=0.3, target_band=0.1,
                         replan_band=0.05)
    with pytest.raises(ValueError, match="max_thr_step"):
        ControllerConfig(provisioned_p=0.3, max_thr_step=0.0)
    with pytest.raises(ValueError, match="persistence_ticks"):
        ControllerConfig(provisioned_p=0.3, persistence_ticks=0)


class FakeSched:
    """Minimal control surface: records every actuation, fakes stats."""

    def __init__(self, c_thr=0.8, n_slots=8, capacity=2):
        self.stats = ServeStats()
        self.c_thr = c_thr
        self.sc = SL.ServeConfig(capacity=capacity, c_thr=c_thr)
        self.n_slots = n_slots
        self.eager_drain_below = capacity
        self.active_cap = n_slots
        self.controller = None
        self.requested_capacity = None
        self.placement = StagePlacement.single_device()

    def set_c_thr(self, v):
        self.c_thr = float(v)

    def set_eager_drain_below(self, k):
        self.eager_drain_below = int(k)

    def set_active_cap(self, cap):
        self.active_cap = max(1, min(int(cap), self.n_slots))

    def request_capacity(self, cap):
        self.requested_capacity = int(cap)


def _tick(ctl, sched, n=8, n_hard=8, conf=None):
    sched.stats.record_decisions(n, n_hard)
    ctl.on_tick(sched, n, n_hard,
                conf if conf is not None else np.full(n, 0.5, np.float32))


def _mk(p=0.25, **kw):
    kw.setdefault("min_decisions", 32)
    kw.setdefault("persistence_ticks", 2)
    kw.setdefault("cooldown_ticks", 4)
    kw.setdefault("min_reservoir", 8)
    kw.setdefault("autoscale", False)
    kw.setdefault("replan", False)
    return DriftController(ControllerConfig(provisioned_p=p, **kw))


def test_warmup_gates_actuation():
    ctl = _mk(min_decisions=64)
    fake = ctl.attach(FakeSched(c_thr=0.8))
    assert fake.stats.provisioned_p == 0.25
    for _ in range(7):                     # 56 decisions, all hard: q = 1
        _tick(ctl, fake)
    assert ctl.state.phase == "warmup"
    assert fake.c_thr == 0.8 and ctl.state.n_recalibrations == 0


def test_hysteresis_needs_persistence_and_release_rearm():
    """An excursion shorter than persistence_ticks never actuates — the
    streak builds while the filtered drift sits outside the target band
    and resets once it re-enters the release band."""
    # persistence high enough that this trace can never trip it: what's
    # under test is the streak/re-arm bookkeeping, not the trip point
    ctl = _mk(min_decisions=8, persistence_ticks=50, cooldown_ticks=0)
    fake = ctl.attach(FakeSched(c_thr=0.8))
    for _ in range(10):                    # warmup met, EWMA(q) = 0.25
        _tick(ctl, fake, n=8, n_hard=2)
    assert ctl.state.phase == "steady" and ctl.state.drift_streak == 0
    for _ in range(12):                    # sustained drift: streak builds
        _tick(ctl, fake, n=8, n_hard=8)
    assert ctl.state.drift_streak > 0
    assert ctl.state.n_recalibrations == 0           # below persistence
    for _ in range(60):                    # back in band: streak re-arms
        _tick(ctl, fake, n=8, n_hard=2)
    assert ctl.state.drift_streak == 0 and ctl.state.phase == "steady"
    assert ctl.state.n_recalibrations == 0


def test_persistent_drift_actuates():
    ctl = _mk(min_decisions=8, persistence_ticks=3, cooldown_ticks=0)
    fake = ctl.attach(FakeSched(c_thr=0.8))
    for _ in range(30):                    # sustained all-hard traffic
        _tick(ctl, fake, n=8, n_hard=8)
    assert ctl.state.n_recalibrations >= 1
    assert fake.c_thr < 0.8


def test_cooldown_holds_after_actuation():
    ctl = _mk(min_decisions=8, persistence_ticks=1, cooldown_ticks=10)
    fake = ctl.attach(FakeSched(c_thr=0.8))
    for _ in range(40):                    # all-hard: actuate once
        _tick(ctl, fake)
    # every post-actuation visit inside the cooldown must not re-actuate:
    # 40 all-hard ticks with persistence 1 would otherwise actuate ~many
    # times; cooldown 10 caps it near 40 / 11
    assert 1 <= ctl.state.n_recalibrations <= 4


def test_recalibration_is_bounded_and_converges_to_quantile():
    """The solved threshold is the (1-p)-exit-rate quantile of the
    reservoir; each actuation moves at most max_thr_step toward it."""
    ctl = _mk(min_decisions=8, persistence_ticks=1, cooldown_ticks=0,
              max_thr_step=0.05, reservoir_size=64)
    fake = ctl.attach(FakeSched(c_thr=0.9))
    conf = np.linspace(0.1, 0.3, 8).astype(np.float32)   # all below thr
    prev = fake.c_thr
    while ctl.state.n_recalibrations == 0:
        _tick(ctl, fake, conf=conf)
    assert prev - fake.c_thr == pytest.approx(0.05, abs=1e-6), \
        "first step must clip at max_thr_step"
    for _ in range(80):
        _tick(ctl, fake, conf=conf)
    # converged: the 25th percentile of the reservoir (exit rate 0.75)
    want = float(np.quantile(np.linspace(0.1, 0.3, 8), 0.25))
    assert fake.c_thr == pytest.approx(want, abs=0.02)
    kinds = {a["kind"] for a in ctl.state.actions}
    assert "recalibrate" in kinds


def test_replan_escalation_reports_and_applies_capacity():
    """Past the re-plan band the Eq. (1)/proportional re-plan fires; under
    apply_replan the bucket-capacity half is requested on the scheduler."""
    ctl = _mk(min_decisions=8, persistence_ticks=1, cooldown_ticks=0,
              replan=True, apply_replan=True, replan_band=0.2)
    fake = ctl.attach(FakeSched(c_thr=0.8, n_slots=8, capacity=2))
    for _ in range(60):                    # q -> 1: way past the band
        _tick(ctl, fake)
    st = ctl.state
    assert st.n_replans >= 1
    assert fake.requested_capacity == stage2_capacity(
        8, min(max(st.q_ewma, 0.01), 1.0), multiple=1)
    # degenerate placement: no chip re-split to recommend
    assert st.recommended_plan is None
    assert any(a["kind"] == "replan" for a in st.actions)


def test_replan_with_taps_recommends_combined_design():
    """With profiled TAP curves the re-plan actuator runs the real Eq. (1)
    re-combination at the observed q."""
    from repro.core.tap import DesignPoint, TAPFunction
    mk = lambda scale: TAPFunction([
        DesignPoint(resources=(float(c), c * 16.0), throughput=scale * c)
        for c in range(1, 9)])
    ctl = DriftController(
        ControllerConfig(provisioned_p=0.25, min_decisions=8,
                         persistence_ticks=1, cooldown_ticks=0,
                         replan_band=0.2, recalibrate=False,
                         autoscale=False),
        taps=(mk(100.0), mk(80.0)), chips=8)
    fake = ctl.attach(FakeSched(c_thr=0.8))
    for _ in range(60):
        _tick(ctl, fake)
    plan = ctl.state.recommended_plan
    assert plan is not None
    assert plan.chips1 + plan.chips2 <= 8
    # q -> 1 means stage 2 sees ~full traffic: it gets at least as many
    # chips as the p = 0.25 provisioning would give it
    assert plan.chips2 >= 2


def test_autoscaler_slo_cap_and_drain_policy():
    """p99 over the SLO shrinks the live-occupancy cap (bounded, by one);
    once the transient ages out of the WINDOWED latency view and stalls
    stop, it grows back — on the same lifetime stats object, no reset. A
    starved window with healthy fill raises eager_drain_below."""
    ctl = _mk(min_decisions=8, autoscale=True, autoscale_every=4,
              latency_slo_p99=0.5, latency_window=8, target_band=0.5,
              replan_band=0.6)
    fake = ctl.attach(FakeSched(c_thr=0.8, n_slots=8, capacity=4))
    fake.eager_drain_below = 0
    # slow requests: p99 ~ 2.0 >> SLO 0.5
    for i in range(10):
        fake.stats.record_submit(i, 0.0)
        fake.stats.record_finish(i, 2.0)
    # starved pool: 1 live row per tick, buckets full when they dispatch
    for i in range(8):
        fake.stats.record_bucket(1.0)
        _tick(ctl, fake, n=1, n_hard=0)
    assert fake.active_cap < 8                       # SLO shrink
    assert fake.eager_drain_below > 0                # starvation drain
    assert ctl.state.n_autoscale_events >= 1
    # recovery: the overload is transient — later finishes are fast, the
    # slow ones age out of the latency window, and the cap must grow back
    cap_low = fake.active_cap
    for i in range(10, 30):
        fake.stats.record_submit(i, 0.0)
        fake.stats.record_finish(i, 0.01)
    for _ in range(12):
        _tick(ctl, fake, n=8, n_hard=0)
    assert fake.active_cap > cap_low


# ---------------------------------------------------------------------------
# the real scheduler's control surface, driven by drift_fns (analytic
# streams: every actuation must leave per-sample tokens EXACTLY intact)
# ---------------------------------------------------------------------------

def _flat_fns(n, difficulty=0.7):
    return drift_fns(np.full(n, difficulty, np.float32), d_model=16,
                     burn1=1, burn2=1)


def _run_sched(fns, sc, n, n_tokens, n_slots=4, attach=None, **kw):
    sched = ContinuousScheduler(fns, sc, n_slots=n_slots,
                                max_len=_S + n_tokens,
                                clock=LogicalClock(), **kw)
    if attach is not None:
        attach(sched)
    for i in range(n):
        sched.submit(Request(i, np.full((_S,), i, np.int32), n_tokens))
    return sched.run(), sched


def _expected(n, n_tokens):
    return {i: [token_of(i, t) for t in range(n_tokens)] for i in range(n)}


def test_set_c_thr_midrun_changes_rate_not_streams():
    """Re-aiming the threshold mid-run flips the hard rate (0.7-difficulty
    confidences: thr above -> all hard, below -> all easy) while per-sample
    token streams stay exactly the analytic ones."""
    n, n_tokens = 8, 12
    fns = _flat_fns(n)

    class FlipThr:
        def __init__(self):
            self.ticks = 0

        def on_tick(self, sched, n_dec, n_hard, conf=None):
            self.ticks += 1
            if self.ticks == 6:
                sched.set_c_thr(0.2)       # everyone exits from here on

    sc = SL.ServeConfig(capacity=2, queue_depth=2, c_thr=0.95)  # all hard
    def attach(s):
        s.controller = FlipThr()
    res, sched = _run_sched(fns, sc, n, n_tokens, attach=attach)
    assert res == _expected(n, n_tokens)
    qs = list(sched.stats.realized_q_series)
    assert qs[0] == 1.0 and qs[-1] == 0.0            # the flip happened


def test_active_cap_bounds_occupancy():
    n, n_tokens = 10, 6
    fns = _flat_fns(n)
    sc = SL.ServeConfig(capacity=2, queue_depth=2, c_thr=0.5)
    def attach(s):
        s.set_active_cap(2)
    res, sched = _run_sched(fns, sc, n, n_tokens, n_slots=6, attach=attach)
    assert res == _expected(n, n_tokens)
    assert sched.peak_busy <= 2
    assert sched.stats.n_finished == n


def test_active_cap_clamps():
    fns = _flat_fns(2)
    sc = SL.ServeConfig(capacity=2, queue_depth=2, c_thr=0.5)
    sched = ContinuousScheduler(fns, sc, n_slots=4, max_len=_S + 4,
                                clock=LogicalClock())
    sched.set_active_cap(0)
    assert sched.active_cap == 1                     # progress guaranteed
    sched.set_active_cap(99)
    assert sched.active_cap == 4


def test_request_capacity_applies_at_discrete_point():
    """A capacity re-size lands at an empty-ring boundary: the config is a
    fresh object (caller's untouched), the ring re-sizes, streams hold."""
    n, n_tokens = 8, 10
    fns = _flat_fns(n)
    sc = SL.ServeConfig(capacity=2, queue_depth=2, c_thr=0.95)  # all hard

    class Resize:
        def __init__(self):
            self.ticks = 0

        def on_tick(self, sched, n_dec, n_hard, conf=None):
            self.ticks += 1
            if self.ticks == 4:
                sched.request_capacity(4)

    def attach(s):
        s.controller = Resize()
    res, sched = _run_sched(fns, sc, n, n_tokens, n_slots=4, attach=attach)
    assert res == _expected(n, n_tokens)
    assert sched.sc.capacity == 4
    assert sc.capacity == 2                          # caller's config intact
    assert sched.ring.sc.capacity == 4


def test_controller_disabled_leaves_scheduler_untouched():
    """No controller: the control fields keep constructor values and the
    run is the PR-4 path (streams equal, no control state)."""
    n, n_tokens = 6, 8
    fns = _flat_fns(n)
    sc = SL.ServeConfig(capacity=2, queue_depth=2, c_thr=0.5)
    res, sched = _run_sched(fns, sc, n, n_tokens)
    assert res == _expected(n, n_tokens)
    assert sched.controller is None
    assert sched.c_thr == 0.5 and sched.active_cap == sched.n_slots
    assert sched.sc is sc                            # no config swap


# ---------------------------------------------------------------------------
# closed loop end to end: nonstationary trace -> controller converges
# ---------------------------------------------------------------------------

def test_closed_loop_converges_on_drift_trace():
    """On a piecewise/ramped difficulty trace the controlled scheduler
    re-calibrates and steers the realized exit rate back toward the
    provisioned p, while the uncontrolled one saturates at q ~ 1."""
    p = PROVISIONED_P
    n, n_tokens, n_slots = 64, 12, 8
    diff = difficulty_trace(n)
    fns = drift_fns(diff, d_model=16, burn1=1, burn2=1)
    # phase-A calibration
    sids = np.arange(n // 4)
    conf = np.concatenate([conf_of(sids, t, diff[sids])
                           for t in range(1, n_tokens)])
    thr0 = float(np.quantile(conf, p))
    sc = SL.ServeConfig(capacity=2, queue_depth=4, c_thr=thr0)

    res_u, sched_u = _run_sched(fns, sc, n, n_tokens, n_slots=n_slots)
    ctl = make_controller(p)
    res_c, sched_c = _run_sched(fns, sc, n, n_tokens, n_slots=n_slots,
                                attach=ctl.attach)
    assert res_u == _expected(n, n_tokens)
    assert res_c == _expected(n, n_tokens)           # actuation-invariant
    assert ctl.state.n_recalibrations >= 2
    q_tail_c = ctl.realized_q_tail(min_decisions=128)
    q_tail_u = np.mean(list(sched_u.stats.realized_q_series)[-24:])
    assert abs(q_tail_c - p) < 0.1, q_tail_c         # steered back to p
    assert q_tail_u > 0.9, q_tail_u                  # uncontrolled saturates
    assert ctl.state.c_thr < thr0                    # threshold moved down


@pytest.mark.skipif(
    jax.device_count() < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8")
def test_controller_disaggregated_replan_recommends_split(tiny_cfg,
                                                          tiny_params,
                                                          tiny_spec):
    """On a real disaggregated placement the re-plan actuator recommends a
    q-proportional chip re-split over the stage submeshes (report-only),
    and streams stay equivalent to the host-loop oracle."""
    from repro.core import early_exit as ee
    prompt = np.asarray(jax.random.randint(jax.random.PRNGKey(21), (6, 8),
                                           0, tiny_cfg.vocab))
    sc = SL.ServeConfig(capacity=2, queue_depth=2, c_thr=1.1)   # all hard
    oracle = SL.build(tiny_params, tiny_cfg, tiny_spec, sc, scheduler=None,
                      host=True).generate(prompt, 5)
    pl = StagePlacement.from_plan(
        StageMeshPlan.proportional(0.5, jax.device_count()))
    sched = SL.build(tiny_params, tiny_cfg, tiny_spec, sc, n_slots=4,
                     max_len=16, page_size=4, placement=pl,
                     clock=LogicalClock())
    ctl = DriftController(ControllerConfig(
        provisioned_p=0.25, min_decisions=8, persistence_ticks=1,
        cooldown_ticks=0, recalibrate=False, autoscale=False,
        replan_band=0.1))
    ctl.attach(sched)
    for i in range(prompt.shape[0]):
        sched.submit(Request(i, prompt[i], 5))
    res = sched.run()
    want = {i: [int(x) for x in oracle["tokens"][i][:5]]
            for i in range(prompt.shape[0])}
    assert res == want
    plan = ctl.state.recommended_plan
    assert plan is not None
    assert plan.chips1 + plan.chips2 == 8
    assert plan.chips2 > plan.chips1                 # q ~ 1: stage 2 heavy
