"""Online drift control plane: closed-loop threshold re-calibration,
scheduler autoscaling, and elastic stage re-planning.

ATHEENA provisions hardware for a *measured* exit probability p (paper
§IV); in a live deployment the realized hard rate q drifts with the input
distribution, silently invalidating the provisioned design — the runtime
threshold/hardware co-adaptation HAPI (Laskaridis et al., 2020) and the
adaptive-inference survey identify as the piece offline DSE cannot cover.
``DriftController`` closes the loop over the serving telemetry the
scheduler already syncs:

    SENSE ──> FILTER ──> HYSTERESIS ──> ACTUATE
      │          │            │             │
      │          │            │             ├─ 1. threshold re-calibration:
      │          │            │             │    re-solve C_thr as the
      │          │            │             │    (1-p)-quantile of the
      │          │            │             │    rolling confidence
      │          │            │             │    reservoir (bounded step)
      │          │            │             ├─ 2. scheduler autoscaling:
      │          │            │             │    live-slot occupancy cap +
      │          │            │             │    eager-drain / bucket-drain
      │          │            │             │    policy from latency and
      │          │            │             │    occupancy feedback
      │          │            │             └─ 3. stage re-planning: Eq. (1)
      │          │            │                  re-combined at the observed
      │          │            │                  q (elastic.replan_rate /
      │          │            │                  proportional split); report,
      │          │            │                  or apply — a full live
      │          │            │                  chip-re-split migration
      │          │            │                  (runtime/migration.py) when
      │          │            │                  the scheduler can rebuild
      │          │            │                  its stage fns, else the
      │          │            │                  bucket-capacity half
      │          │            └─ |EWMA(q) - p| must exceed the band for
      │          │               ``persistence_ticks`` consecutive visits;
      │          │               re-arm only below the release band
      │          └─ windowed EWMA of the per-dispatch q series
      │             (ServeStats.realized_q_ewma — telemetry.ewma)
      └─ per-tick (n_decisions, n_hard, live-row confidences): scalars the
         hot loops fetch anyway, so sensing costs no extra syncs

Actuation discipline — what makes this safe to leave attached:

  * **warmup**: nothing actuates before ``min_decisions`` decisions have
    been sensed (a threshold solved from ten samples is noise);
  * **hysteresis**: drift must *persist* (band + streak), so a single
    hairy bucket never re-aims the threshold;
  * **cooldown**: after any actuation the controller holds for
    ``cooldown_ticks`` visits, letting the plant respond before it is
    measured again (the EWMA lags the threshold change);
  * **bounded steps**: one actuation moves C_thr at most
    ``max_thr_step``, the occupancy cap and drain policy by one slot —
    persistent drift converges over a few actuations, transient noise
    cannot slam the operating point;
  * **no steady-state recompiles**: C_thr is a traced argument, the cap
    and drain policy are host-side ints. Only the re-plan actuator's
    bucket re-size compiles a new drain program, and only at a discrete
    re-plan point (empty ring).

Everything degrades to PR-4 behavior when no controller is attached: the
schedulers' control fields keep their constructor values and the hot loops
are byte-for-byte the uncontrolled ones (enforced by the unchanged parity
tests).
"""
from __future__ import annotations

import itertools
from collections import deque
from dataclasses import dataclass, field
from typing import Deque, List, Optional, Tuple

import numpy as np

from repro.core import exit_decision as ed
from repro.core.stage_mesh import StageMeshPlan, stage2_capacity
from repro.runtime import elastic
from repro.runtime.telemetry import ConfidenceReservoir, ControlWindow

# state-machine phases
WARMUP, STEADY, CORRECTING, COOLDOWN = ("warmup", "steady", "correcting",
                                        "cooldown")


@dataclass
class ControllerConfig:
    """Tuning knobs for one control loop. The defaults are deliberately
    conservative: a controller that actuates rarely and in small steps is
    one an operator can leave attached."""
    provisioned_p: float                 # the rate the stage mesh was sized for
    target_band: float = 0.05            # hysteresis enter band on |EWMA(q)-p|
    release_band: float = 0.02           # re-arm band (must be < target_band)
    replan_band: float = 0.15            # beyond this, thresholding alone
                                         # cannot correct -> stage re-plan
    min_decisions: int = 64              # warmup: sense this much before acting
    persistence_ticks: int = 3           # drift must persist this many visits
    cooldown_ticks: int = 8              # hold after any actuation
    max_thr_step: float = 0.1            # bounded |ΔC_thr| per actuation
    reservoir_size: int = 2048           # rolling confidence window
    min_reservoir: int = 64              # don't re-solve a quantile on less
    # actuator enables
    recalibrate: bool = True
    autoscale: bool = True
    replan: bool = True                  # report re-plans
    apply_replan: bool = False           # ...and apply the capacity half
    # autoscaler feedback targets
    latency_slo_p99: Optional[float] = None   # seconds; None = no cap control
    min_active_cap: int = 1
    autoscale_every: int = 16            # visits between autoscaler passes
    starvation_fill: float = 0.5         # bucket-fill floor before the drain
                                         # policy trades fill for latency
    latency_window: int = 64             # SLO feedback looks at the last N
                                         # finished requests, not lifetime

    def __post_init__(self):
        if not 0.0 < self.provisioned_p <= 1.0:
            raise ValueError(f"provisioned_p must be in (0, 1], got "
                             f"{self.provisioned_p}")
        if self.release_band >= self.target_band:
            raise ValueError(
                f"release_band ({self.release_band}) must be strictly inside "
                f"target_band ({self.target_band}) — equal bands would chatter")
        if self.replan_band < self.target_band:
            raise ValueError(
                f"replan_band ({self.replan_band}) must be >= target_band "
                f"({self.target_band}) — re-planning is the escalation")
        if self.max_thr_step <= 0.0:
            raise ValueError(f"max_thr_step must be > 0, got "
                             f"{self.max_thr_step}")
        if self.persistence_ticks < 1 or self.cooldown_ticks < 0:
            raise ValueError("persistence_ticks >= 1 and cooldown_ticks >= 0 "
                             "required")


@dataclass
class ControllerState:
    """Everything the loop knows, reportable: phase, the filtered drift,
    actuation counters, and a bounded action log (what changed, when, why
    — the audit trail a drifting deployment gets asked for)."""
    phase: str = WARMUP
    ticks: int = 0
    decisions_seen: int = 0
    drift_streak: int = 0
    cooldown_left: int = 0
    q_ewma: float = 0.0
    drift: float = 0.0
    c_thr: Optional[float] = None
    n_recalibrations: int = 0
    n_autoscale_events: int = 0
    n_replans: int = 0
    recommended_plan: Optional[StageMeshPlan] = None
    actions: List[dict] = field(default_factory=list)

    _ACTION_CAP = 256                    # bounded audit log

    def log(self, kind: str, **detail) -> None:
        self.actions.append({"tick": self.ticks, "kind": kind, **detail})
        if len(self.actions) > self._ACTION_CAP:
            del self.actions[: len(self.actions) - self._ACTION_CAP]

    def as_dict(self) -> dict:
        plan = self.recommended_plan
        return {"phase": self.phase, "ticks": self.ticks,
                "decisions_seen": self.decisions_seen,
                "q_ewma": self.q_ewma, "drift": self.drift,
                "c_thr": self.c_thr,
                "n_recalibrations": self.n_recalibrations,
                "n_autoscale_events": self.n_autoscale_events,
                "n_replans": self.n_replans,
                "recommended_plan": (None if plan is None else
                                     {"chips1": plan.chips1,
                                      "chips2": plan.chips2}),
                "actions_tail": self.actions[-8:]}


class DriftController:
    """The closed loop. Attach to a scheduler (``attach``), and the
    scheduler's hot loop calls ``on_tick`` once per pool tick with the
    scalars it synced anyway.

    Actuators are duck-typed against the scheduler's control surface:
    ``set_c_thr`` is always driven; ``set_active_cap``/
    ``request_capacity``/``request_migration`` only where the scheduler
    exposes them, so a partial control surface (a test fake) is driven as
    far as it goes.

    ``taps`` (optional) are the profiled (stage-1, stage-2) TAP curves and
    ``chips`` the deployment budget: with them the re-plan actuator runs
    the real Eq. (1) re-combination (``elastic.replan_rate``); without,
    it falls back to the p-proportional chip split when the placement
    spans enough devices, else reports the drift with no plan.
    """

    # bounded (n_decisions, n_hard) per-visit history: lets callers compute
    # a decision-WEIGHTED realized q over any trailing span (per-tick q is
    # occupancy-biased — a drain-down tick with one live slot votes 0 or 1)
    HISTORY_CAP = 1024

    def __init__(self, cfg: ControllerConfig,
                 taps: Optional[Tuple] = None, chips: Optional[int] = None):
        self.cfg = cfg
        self.state = ControllerState()
        self.reservoir = ConfidenceReservoir(cfg.reservoir_size)
        self.window = ControlWindow()
        self.history: Deque[Tuple[int, int]] = deque(maxlen=self.HISTORY_CAP)
        self.taps = taps
        self.chips = chips

    def realized_q_tail(self, min_decisions: int = 256) -> float:
        """Decision-weighted realized q over the most recent visits
        spanning at least ``min_decisions`` decisions — the settled
        operating point (what the ±band acceptance bar measures)."""
        dec = hard = 0
        for d, h in reversed(self.history):
            dec += d
            hard += h
            if dec >= min_decisions:
                break
        return hard / dec if dec else 0.0

    # -- wiring --------------------------------------------------------------

    def attach(self, sched):
        """Wire this controller into a scheduler: the scheduler's hot loop
        starts calling ``on_tick`` (with the live rows' confidences, which
        feed the reservoir) and its stats gain the provisioned p (the
        windowed ``q_drift`` view). Returns the scheduler for chaining."""
        sched.controller = self
        sched.stats.provisioned_p = self.cfg.provisioned_p
        self.state.c_thr = float(sched.c_thr)
        return sched

    # -- the loop ------------------------------------------------------------

    def on_tick(self, sched, n_decisions: int, n_hard: int,
                confidences=None) -> None:
        """One controller visit: sense the tick, refresh the filter, walk
        the hysteresis state machine, maybe actuate."""
        st, cfg = self.state, self.cfg
        st.ticks += 1
        st.decisions_seen += int(n_decisions)
        self.history.append((int(n_decisions), int(n_hard)))
        self.window.observe(n_decisions, n_hard)
        stats = sched.stats
        self.window.observe_counters(stats.n_stalls, stats.n_buckets,
                                     stats.bucket_fill_sum)
        if confidences is not None and len(confidences):
            self.reservoir.extend(confidences)

        # FILTER: the shared windowed-EWMA drift view on ServeStats
        st.q_ewma = stats.realized_q_ewma
        st.drift = st.q_ewma - cfg.provisioned_p

        if st.decisions_seen < cfg.min_decisions:
            st.phase = WARMUP
            return
        if st.cooldown_left > 0:
            st.cooldown_left -= 1
            st.phase = COOLDOWN
        else:
            # HYSTERESIS: enter on persistent excursion past target_band,
            # re-arm only once the drift falls back inside release_band
            if abs(st.drift) > cfg.target_band:
                st.drift_streak += 1
            elif abs(st.drift) < cfg.release_band:
                st.drift_streak = 0
                st.phase = STEADY
            if st.drift_streak >= cfg.persistence_ticks:
                st.phase = CORRECTING
                self._actuate_drift(sched)
                st.drift_streak = 0
                st.cooldown_left = cfg.cooldown_ticks

        # the autoscaler runs on its own cadence and feedback (latency +
        # occupancy, not q-drift), but shares the actuation discipline
        if (cfg.autoscale and st.ticks % cfg.autoscale_every == 0
                and st.decisions_seen >= cfg.min_decisions):
            self._autoscale(sched)
            self.window.reset()

    # -- actuator 1 + 3: drift correction ------------------------------------

    def _actuate_drift(self, sched) -> None:
        """Past the target band: re-calibrate the threshold. Past the
        re-plan band: thresholding alone cannot correct — escalate to the
        Eq. (1) stage re-plan as well."""
        cfg, st = self.cfg, self.state
        if abs(st.drift) >= cfg.replan_band and cfg.replan:
            self._replan(sched)
        if cfg.recalibrate:
            self._recalibrate(sched)

    def _recalibrate(self, sched) -> None:
        """Re-solve C_thr from the rolling reservoir so the realized exit
        rate is steered back to (1 - p) under the CURRENT distribution —
        bounded to ``max_thr_step`` per actuation."""
        cfg, st = self.cfg, self.state
        if len(self.reservoir) < cfg.min_reservoir:
            st.log("recalibrate_skipped", reason="reservoir",
                   n=len(self.reservoir))
            return
        target = ed.calibrate_threshold(self.reservoir.snapshot(),
                                        target_exit_rate=1.0
                                        - cfg.provisioned_p)
        prev = st.c_thr if st.c_thr is not None else sched.c_thr
        step = max(-cfg.max_thr_step, min(cfg.max_thr_step, target - prev))
        new = prev + step
        if new == prev:
            return
        sched.set_c_thr(new)
        st.c_thr = new
        st.n_recalibrations += 1
        st.log("recalibrate", c_thr=new, solved=float(target),
               drift=st.drift, clipped=bool(new != target))

    def _replan(self, sched) -> None:
        """Stage re-plan at the observed q: the real Eq. (1) re-combination
        when TAP curves are in hand, else the p-proportional split over the
        current chip count. Under ``apply_replan`` the re-plan is APPLIED:
        a full live migration (chip re-split + stage-fns rebuild + bucket
        re-size through ``runtime.migration.LiveMigrator``) when the
        scheduler can rebuild its stage callables against a new placement
        (``fns_factory``) and the placement is disaggregated; otherwise
        the bucket-capacity half via ``request_capacity``. Either applies
        only at a discrete re-plan point."""
        cfg, st = self.cfg, self.state
        q = min(max(st.q_ewma, 0.01), 1.0)
        plan = None
        if self.taps is not None and self.chips is not None:
            ep = elastic.replan_rate(self.taps[0], self.taps[1],
                                     cfg.provisioned_p, q, self.chips)
            plan = StageMeshPlan.from_chips(
                int(ep.design.stage1.resources[0]),
                int(ep.design.stage2.resources[0]))
            recovered = ep.degradation
        else:
            recovered = None
            placement = getattr(sched, "placement", None)
            if placement is not None and placement.disaggregated:
                n_dev = (sched.stats.stage1_chips
                         + sched.stats.stage2_chips)
                plan = StageMeshPlan.proportional(q, n_dev)
        st.recommended_plan = plan
        st.n_replans += 1
        applied = None
        if cfg.apply_replan:
            cap = (stage2_capacity(sched.n_slots, q, multiple=1)
                   if hasattr(sched, "n_slots") else None)
            factory = getattr(sched, "fns_factory", None)
            placement = getattr(sched, "placement", None)
            if (plan is not None and factory is not None
                    and hasattr(sched, "request_migration")
                    and placement is not None and placement.disaggregated):
                # full chip re-split: carve the re-planned submeshes out of
                # the SAME device set the current placement occupies and
                # hand the migrator placement + rebuilt fns + capacity
                from repro.runtime.migration import MigrationPlan
                devs = (list(placement.ex1.devices)
                        + list(placement.ex2.devices))
                new_pl = type(placement).from_plan(plan, devs)
                sched.request_migration(MigrationPlan(
                    placement=new_pl, fns=factory(new_pl), capacity=cap,
                    reason=f"controller-replan:q={q:.3f}"))
                applied = "migration"
            elif cap is not None and hasattr(sched, "request_capacity"):
                sched.request_capacity(cap)
                applied = "capacity"
        st.log("replan", q=q,
               plan=(None if plan is None else (plan.chips1, plan.chips2)),
               recovered_throughput_ratio=recovered, applied=applied)

    # -- actuator 2: autoscaling ---------------------------------------------

    def _autoscale(self, sched) -> None:
        """Occupancy/latency feedback over the last control window, one
        bounded step per pass:

          * starved pool (live slots below the bucket size) with healthy
            fill -> raise ``eager_drain_below``: partial buckets beat a
            starved stage 1;
          * rich pool with thin buckets (fill under ``starvation_fill``)
            -> lower it: bucket bubbles waste the provisioned stage 2;
          * p99 latency over the SLO -> shrink the live-occupancy cap
            (admission-side, by attrition) — queueing delay is traded for
            utilization; back under the SLO with no backpressure stalls ->
            grow it back toward the pool size.
        """
        st = self.state
        win = self.window
        if win.ticks == 0:
            return
        changed = {}
        cap_bucket = getattr(getattr(sched, "sc", None), "capacity", None)
        eager = getattr(sched, "eager_drain_below", None)
        if eager is not None and cap_bucket:
            if (win.mean_active < cap_bucket
                    and win.mean_bucket_fill >= self.cfg.starvation_fill
                    and eager < cap_bucket):
                sched.set_eager_drain_below(eager + 1)
                changed["eager_drain_below"] = eager + 1
            elif (win.mean_active >= cap_bucket
                  and 0 < win.mean_bucket_fill < self.cfg.starvation_fill
                  and eager > 0):
                sched.set_eager_drain_below(eager - 1)
                changed["eager_drain_below"] = eager - 1
        slo = self.cfg.latency_slo_p99
        if slo is not None and hasattr(sched, "set_active_cap"):
            # WINDOWED p99 — over the last latency_window finishes, not the
            # lifetime reservoir: a transient overload must age out of the
            # feedback signal or the cap ratchets down and never recovers
            p99 = self._recent_p99(sched.stats)
            cap = sched.active_cap
            if p99 is None:
                pass                     # no new evidence: hold the cap
            elif p99 > slo and cap > self.cfg.min_active_cap:
                sched.set_active_cap(cap - 1)
                changed["active_cap"] = cap - 1
            elif (p99 <= slo and win.stall_rate == 0.0
                  and cap < sched.n_slots):
                sched.set_active_cap(cap + 1)
                changed["active_cap"] = cap + 1
        if changed:
            st.n_autoscale_events += 1
            st.log("autoscale", window=win.as_dict(), **changed)

    def _recent_p99(self, stats) -> Optional[float]:
        """p99 over the most recent ``latency_window`` finished requests
        (None when nothing has finished yet)."""
        lat = stats.latencies
        n = len(lat)
        if n == 0:
            return None
        k = self.cfg.latency_window
        tail = list(itertools.islice(lat, max(0, n - k), n))
        return float(np.percentile(np.asarray(tail), 99.0))
