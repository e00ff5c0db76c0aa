"""Zero-downtime live migration: apply a new stage plan to a RUNNING
``ContinuousScheduler`` with no dropped requests.

ATHEENA sizes the two-stage split offline for a measured exit probability
p; PR 5's drift controller re-solves that split online but could only
*report* it (plus the bucket-capacity half). This module makes the re-plan
real: a compensating state machine that walks a live slot pool from one
``StagePlacement`` (chip split, stage callables, bucket capacity) to
another between two scheduler loop iterations, so re-planning — and its
failure twin, device-loss degradation — is a pause measured in
milliseconds instead of a restart measured in minutes.

The state machine (each stage pushes a compensation; any failure unwinds
the stack LIFO and serving resumes on the OLD placement):

    QUIESCE   close admission; drain every in-flight ring bucket (retried,
              bounded by ``quiesce_timeout_s``); harvest every pending
              device result. Post-state: no parked slot, empty ring, empty
              pending window — the pool is at a shape-change-safe point.
    SNAPSHOT  capture the scheduler's full mutable state: *references* to
              the device arrays (jax.Arrays are immutable and nothing
              donates them between here and RESUME, so refs ARE a
              consistent, zero-copy snapshot) plus copies of the host-side
              slot metadata and queues.
    RE-PLACE  swap in the new stage callables (``fns_factory``/-provided
              ``DecodeFns`` re-slice params per ``ee.split_params`` onto
              the new submeshes), rebuild the ring at the new capacity on
              the new stage-2 executor, and ``jax.device_put`` the slot
              lanes / pooled stage-1 cache / stage-2 row store under the
              new placement's NamedShardings (``elastic.relayout``'s move,
              applied to live serving state).
    RESUME    re-open admission and record the measured pause
              (admission-closed -> admission-reopened wall time) in
              ``ServeStats.migration_pauses_ms``.

Correctness contract (tests/test_migration.py): per-sample token streams
are bitwise-equal to an unmigrated run across every migration — per-row
computations are batch- and placement-independent, and the quiesce point
guarantees no row's home changes shape under an in-flight bucket. A rolled
back migration restores byte-identical scheduler state.

Device loss rides the same machine: ``migrate_on_device_loss`` re-plans
the surviving chips (p-proportional, or the caller's Eq. (1) re-solve),
degrades the placement via ``elastic.degrade_placement``, rebuilds the
stage callables, and arms the migrator — losing a stage-2 chip degrades
throughput instead of crashing the server.
"""
from __future__ import annotations

import copy
import math
import time
from dataclasses import dataclass
from typing import Callable, List, Optional, Tuple

from repro.core.stage_mesh import StageMeshPlan
from repro.runtime import elastic, faults
from repro.runtime.scheduler import (ContinuousScheduler, RingQueue,
                                     ServeConfig, _PARKED)
from repro.runtime.stage_executor import StagePlacement


class MigrationError(RuntimeError):
    """A migration stage failed. The migrator has already rolled back to
    the pre-migration placement (``__cause__`` holds the stage failure);
    serving continues on the old plan."""


class QuiesceTimeout(MigrationError):
    """QUIESCE could not drain the in-flight ring within the bounded
    wait — the pool never reached a shape-change-safe point."""


@dataclass(frozen=True)
class MigrationPlan:
    """What to migrate TO. Every field is optional — ``None`` keeps the
    scheduler's current value — so a capacity-only re-size, a pure chip
    re-split, and a full re-plan are all the same plan type.

    ``fns`` must be built against ``placement`` (the stage callables close
    over param slices placed on its submeshes); ``capacity`` is clamped to
    [1, n_slots] like ``request_capacity``. ``pause_budget_ms`` is the
    zero-downtime budget: exceeding it is *recorded* (an over-budget pause
    is an SLO violation, not a correctness failure — the bench gates it).
    """
    placement: Optional[StagePlacement] = None
    fns: Optional[object] = None
    capacity: Optional[int] = None
    pause_budget_ms: float = math.inf
    quiesce_timeout_s: float = 30.0
    reason: str = "replan"

    def __post_init__(self):
        if self.fns is not None and self.placement is None:
            raise ValueError("a MigrationPlan with new stage fns must name "
                             "the placement they were built against")
        if self.quiesce_timeout_s <= 0:
            raise ValueError(f"quiesce_timeout_s must be > 0, got "
                             f"{self.quiesce_timeout_s}")


# device-state attributes re-placed onto the new submeshes: (attr, stage,
# io) — io=True lanes shard batch-leading dims over 'data'; the pooled
# stage-1 cache re-places replicated (its block leaves carry superblock
# leading axes that must NOT shard over the batch axis rule)
_DEVICE_STATE: Tuple[Tuple[str, int, bool], ...] = (
    ("_tok", 1, True), ("_pos", 1, True), ("_active_lane", 1, True),
    ("_start_lane", 1, True), ("_budget_lane", 1, True),
    ("_c1", 1, False), ("_rows", 2, True),
    # the paged stage-2 page pool (None on dense schedulers / cold pools —
    # the relayout loop skips None attrs); replicated like _c1: its leaves
    # lead with page/superblock axes, never the batch axis
    ("_pool", 2, False),
)

# host-side mutable containers snapshotted by shallow copy (``queue`` is a
# serve_api.RequestQueue, which defines ``__copy__`` to clone its deque +
# sid set together)
_HOST_STATE = ("_sid", "_emitted", "_budget", "_state", "_free",
               "_parked_fifo", "_pending", "queue", "results",
               "_slot_pages", "_slot_len")


class LiveMigrator:
    """One migration attempt over a running scheduler. Single-shot: build,
    ``run()``, discard. On success the scheduler is serving on the new
    plan; on failure it is serving on the old one (byte-identical state)
    and ``MigrationError`` is raised with the stage failure as cause."""

    def __init__(self, sched: ContinuousScheduler, plan: MigrationPlan):
        self.sched = sched
        self.plan = plan
        self._compensations: List[Tuple[str, Callable[[], None]]] = []
        self.pause_ms: Optional[float] = None

    # -- the stages ----------------------------------------------------------

    def _quiesce(self) -> None:
        s = self.sched
        s._admission_open = False
        self._compensations.append(
            ("reopen-admission",
             lambda: setattr(s, "_admission_open", True)))
        faults.fault_point("migrate:quiesce")
        deadline = time.perf_counter() + self.plan.quiesce_timeout_s
        # drain every in-flight bucket: real dispatches (their tokens are
        # emitted normally and are NOT rolled back), retried on transient
        # faults like any other drain
        while s.ring.count > 0:
            if time.perf_counter() >= deadline:
                raise QuiesceTimeout(
                    f"ring still holds {s.ring.count} rows after "
                    f"{self.plan.quiesce_timeout_s:.1f}s — cannot reach a "
                    f"shape-change-safe point")
            faults.retry(s._dispatch_bucket, "forced", what="quiesce-drain")
        while s._pending:
            s._harvest_one()
        assert not any(st == _PARKED for st in s._state), \
            "quiesced with parked slots despite an empty ring"

    def _snapshot(self) -> None:
        faults.fault_point("migrate:snapshot")
        s = self.sched
        snap: dict = {}
        # device arrays: refs are the snapshot (immutable; no donation can
        # touch them before RESUME because no tick runs mid-migration and
        # RE-PLACE only issues non-donating device_put)
        for attr, _stage, _io in _DEVICE_STATE:
            snap[attr] = getattr(s, attr)
        for attr in _HOST_STATE:
            val = getattr(s, attr)
            snap[attr] = copy.copy(val)      # shallow copy, same container
        for attr in ("fns", "placement", "ex1", "ex2", "sc", "ring",
                     "c_thr", "eager_drain_below", "active_cap"):
            snap[attr] = getattr(s, attr)
        chips = (s.stats.stage1_chips, s.stats.stage2_chips)
        # the page allocator's free list: an EXACT state capture (its own
        # defensive-copy snapshot — the lane is donated by frees, so a bare
        # ref would not survive post-rollback serving)
        alloc = getattr(s, "_alloc", None)
        alloc_snap = alloc.snapshot() if alloc is not None else None

        def restore():
            for attr, val in snap.items():
                setattr(s, attr, val)
            s.stats.stage1_chips, s.stats.stage2_chips = chips
            if alloc is not None:
                alloc.restore(alloc_snap)
        self._compensations.append(("restore-snapshot", restore))

    def _replace(self) -> None:
        faults.fault_point("migrate:replace")
        s, plan = self.sched, self.plan
        new_pl = plan.placement if plan.placement is not None else s.placement
        new_fns = plan.fns if plan.fns is not None else s.fns
        if getattr(s, "_paged", False) and (
                getattr(new_fns, "s2_paged", None) is None
                or getattr(new_fns, "page_size", None) != s.page_size):
            raise MigrationError(
                "a paged scheduler can only migrate onto stage fns built "
                f"with the same page_size={s.page_size} "
                "(decode_stage_fns(page_size=...)) — the live page pool's "
                "layout is not convertible mid-serve")
        cap = (s.sc.capacity if plan.capacity is None
               else max(1, min(int(plan.capacity), s.n_slots)))
        new_sc = ServeConfig(capacity=cap, queue_depth=s.sc.queue_depth,
                             c_thr=s.sc.c_thr, max_pending=s.sc.max_pending,
                             harvest_timeout_s=s.sc.harvest_timeout_s)
        s.fns = new_fns
        s.placement = new_pl
        s.ex1, s.ex2 = new_pl.ex1, new_pl.ex2
        s.sc = new_sc
        # fresh ring on the new stage-2 executor at the new capacity (the
        # quiesced ring is empty; the buffer re-allocates on next enqueue)
        s.ring = RingQueue(new_sc, s.ex2, s.stats)
        # re-lay-out live device state under the new placement's shardings
        # — the elastic.relayout move applied to serving state. Skipped
        # when the pool is cold (nothing admitted yet).
        if s._c1 is not None:
            for attr, stage, io in _DEVICE_STATE:
                val = getattr(s, attr)
                if val is None:              # e.g. _pool on a dense pool
                    continue
                ex = s.ex1 if stage == 1 else s.ex2
                put = ex.place_io if io else ex.place
                setattr(s, attr,
                        faults.retry(put, val, what=f"relayout:{attr}"))
            alloc = getattr(s, "_alloc", None)
            if alloc is not None:
                alloc.relayout(lambda x: faults.retry(
                    s.ex2.place, x, what="relayout:_alloc"))
        s.stats.record_placement(new_pl)

    def _resume(self, t0: float) -> None:
        faults.fault_point("migrate:resume")
        s = self.sched
        s._admission_open = True
        self.pause_ms = (time.perf_counter() - t0) * 1e3
        s.stats.record_migration(self.pause_ms)
        faults.LOG.emit("migration", reason=self.plan.reason,
                        pause_ms=self.pause_ms,
                        capacity=s.sc.capacity,
                        stage1_chips=s.stats.stage1_chips,
                        stage2_chips=s.stats.stage2_chips,
                        over_budget=bool(
                            self.pause_ms > self.plan.pause_budget_ms))

    # -- driver --------------------------------------------------------------

    def run(self) -> float:
        """Execute QUIESCE -> SNAPSHOT -> RE-PLACE -> RESUME. Returns the
        measured pause in ms; raises ``MigrationError`` after a clean
        rollback on any stage failure."""
        t0 = time.perf_counter()
        stage = "quiesce"
        try:
            self._stage_event("quiesce")
            self._quiesce()
            stage = "snapshot"
            self._stage_event("snapshot")
            self._snapshot()
            stage = "replace"
            self._stage_event("replace")
            self._replace()
            stage = "resume"
            self._stage_event("resume")
            self._resume(t0)
            self._stage_event("done", pause_ms=self.pause_ms)
            return self.pause_ms
        except BaseException as exc:
            self._rollback(stage, exc)
            if isinstance(exc, MigrationError):
                raise
            raise MigrationError(
                f"migration ({self.plan.reason}) failed in {stage.upper()}: "
                f"{exc}") from exc

    def _rollback(self, stage: str, exc: BaseException) -> None:
        """Unwind the compensation stack LIFO: the snapshot restore (when
        taken) rewinds every RE-PLACE mutation to the captured refs, then
        admission re-opens. Compensations are pure ref/flag restores — no
        device work, nothing that can itself fail."""
        for _name, comp in reversed(self._compensations):
            comp()
        self._compensations.clear()
        self.sched.stats.record_migration_rollback()
        self._stage_event("rollback", failed_stage=stage)
        faults.LOG.emit("migration_rollback", reason=self.plan.reason,
                        failed_stage=stage, error=str(exc))

    def _stage_event(self, stage: str, **fields) -> None:
        """Annotate the scheduler's request-lifecycle feed (when wired)
        with the migration state machine's transitions — the tracer
        renders them as control-track instants alongside the request
        spans they pause."""
        ev = getattr(self.sched, "events", None)
        if ev is not None:
            ev.emit(f"migrate_{stage}", reason=self.plan.reason, **fields)


def migrate_on_device_loss(sched: ContinuousScheduler, failed,
                           q: Optional[float] = None,
                           pause_budget_ms: float = math.inf) -> None:
    """Degrade a running disaggregated scheduler after losing devices:
    re-split the SURVIVING chips (p-proportional at the observed hard rate
    ``q``, default the provisioned/realized rate), rebuild the stage
    callables against the degraded placement via the scheduler's
    ``fns_factory``, and arm a live migration — throughput degrades, the
    server survives.

    ``failed`` is a set of failed device *ids* (or device objects). The
    migration applies at the scheduler's next discrete re-plan point.
    """
    if sched.fns_factory is None:
        raise MigrationError(
            "device-loss degradation needs a fns_factory to rebuild stage "
            "callables on the surviving placement")
    devs = list(sched.ex1.devices) + list(sched.ex2.devices)
    if not devs:
        raise MigrationError("single-device placement has no chips to lose")
    failed_ids = {getattr(d, "id", d) for d in failed}
    failed_idx = [i for i, d in enumerate(devs) if d.id in failed_ids]
    survivors = len(devs) - len(failed_idx)
    if survivors < 2:
        raise MigrationError(
            f"{survivors} surviving device(s) cannot host a disaggregated "
            f"two-stage split — fall back to single-device serving")
    if q is None:
        st = sched.stats
        q = st.provisioned_p if st.provisioned_p is not None \
            else max(st.realized_q, 0.01)
    plan = StageMeshPlan.proportional(min(max(float(q), 0.01), 1.0),
                                      survivors)
    new_pl = elastic.degrade_placement(devs, failed_idx, plan)
    sched.request_migration(MigrationPlan(
        placement=new_pl, fns=sched.fns_factory(new_pl),
        pause_budget_ms=pause_budget_ms,
        reason=f"device-loss:{sorted(failed_ids)}"))
