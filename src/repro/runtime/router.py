"""Fleet-scale replica router: admission across N serving replicas with
difficulty-aware routing, per-tenant SLO classes, and a live ops surface.

ATHEENA provisions a network section's hardware to the exit probability
``p`` of the traffic it actually sees; at fleet scale the same principle
says *routing* should shape per-replica traffic so each replica's
provisioning stays matched to its realized hard rate ``q`` — steer easy
traffic to exit-heavy (small-stage-2) replicas and hard traffic to fat
ones, the progressive-inference scheduling framing of HAPI. The
``FleetRouter`` owns admission across a fleet of replicas and depends ONLY
on the transport-agnostic ``ReplicaHandle`` surface (``serve_api.py``), so
the replicas can be in-process ``ContinuousScheduler`` objects today and
multi-process / multi-host proxies tomorrow.

Routing policies (``policy=``):

  * ``round_robin``   — cycle over eligible replicas (the baseline the
                        fleet benchmark gates against);
  * ``least_loaded``  — min live occupancy + queue depth;
  * ``drift_aware``   — match the submitting tenant's rolling difficulty
                        estimate (EWMA of realized per-request hard rate,
                        learned from each replica's finish feed) to each
                        replica's provisioned ``p``, penalized by the
                        replica's current drift (``realized_q_ewma`` above
                        its ``p`` means its stage-2 is already saturating)
                        and a load tiebreak.

SLO classes and preemption: every ``Request`` carries ``tenant`` /
``slo_class``; classes order admission by priority, optionally cap a
tenant's in-flight requests, and let a blocked higher-priority request
preempt a lower-priority (or over-budget same-priority) tenant's QUEUED
request off a replica. Preemption uses ``revoke_queued`` — only unadmitted
requests move, so a preempted request has never emitted a token and goes
back into the router's pending set (re-queued, NEVER dropped: the
no-drop/no-dup contract extends fleet-wide, and per-sample token streams
stay equal to a single-scheduler oracle because per-row compute is batch-
and replica-composition-independent).

Ops surface: ``FleetStats.as_dict`` aggregates per-replica ``ServeStats``
(each itself a versioned schema) plus per-tenant difficulty/usage and the
router's own counters; a streaming per-request event feed
(submit/route/preempt/finish/degrade) rides the PR-6 ``EventLog`` —
``router.events.subscribe(fn)`` sees every event as it is emitted.
``degrade_replica`` wires replica health to ``migrate_on_device_loss``:
the degraded replica's queued requests are revoked and redistributed, its
in-flight work drains normally, and (when device loss is the cause) the
survivor chips are re-split via a live migration.

Clock discipline: all replicas MUST share one clock object (the router's),
so "the fleet at time t" is one coherent statement — ``FleetRouter``
asserts this at construction.
"""
from __future__ import annotations

import itertools
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence

from repro.runtime.scheduler import Request
from repro.runtime.serve_api import validate_request
from repro.runtime.telemetry import EventLog

__all__ = ["DEFAULT_SLO_CLASSES", "FleetRouter", "FleetStats", "SLOClass",
           "TenantState"]

HEALTHY = "healthy"
DEGRADED = "degraded"

ROUTING_POLICIES = ("round_robin", "least_loaded", "drift_aware")

# difficulty-estimate smoothing: per-request hard rates are noisy (a
# 16-token request has 15 Bernoulli decisions), so the tenant estimate
# folds them at alpha=0.3 — converged within ~10 finishes of a regime
# change, stable against any one request
_DIFFICULTY_ALPHA = 0.3


@dataclass(frozen=True)
class SLOClass:
    """One service class: ``priority`` orders admission (lower = more
    urgent; preemption only ever flows down the priority order), and
    ``max_inflight`` optionally caps a tenant's concurrently-routed
    requests (the budget whose violation makes a tenant preemptible by
    its own priority peers)."""
    name: str
    priority: int
    max_inflight: Optional[int] = None


DEFAULT_SLO_CLASSES: Dict[str, SLOClass] = {
    "gold": SLOClass("gold", 0),
    "standard": SLOClass("standard", 1),
    "batch": SLOClass("batch", 2),
}


@dataclass
class TenantState:
    """Rolling per-tenant view: the difficulty estimate ``drift_aware``
    routes by (EWMA of realized per-request hard rate, None until the
    tenant's first finish), plus usage counters."""
    difficulty_ewma: Optional[float] = None
    inflight: int = 0
    n_submitted: int = 0
    n_finished: int = 0
    n_preempted: int = 0

    def observe_finish(self, n_hard: float, n_dec: float) -> None:
        if n_dec <= 0:
            return
        q = float(n_hard) / float(n_dec)
        self.difficulty_ewma = (
            q if self.difficulty_ewma is None
            else _DIFFICULTY_ALPHA * q
            + (1.0 - _DIFFICULTY_ALPHA) * self.difficulty_ewma)

    def as_dict(self) -> dict:
        return {"difficulty_ewma": self.difficulty_ewma,
                "inflight": self.inflight,
                "n_submitted": self.n_submitted,
                "n_finished": self.n_finished,
                "n_preempted": self.n_preempted}


class FleetStats:
    """The fleet ops aggregate: router counters + per-tenant state +
    per-replica ``ServeStats`` (each replica dict is itself the versioned
    ``ServeStats`` schema). ``as_dict`` is versioned like the per-replica
    schema: adding/removing/renaming a top-level key bumps
    ``SCHEMA_VERSION``.

    v2 adds the fleet-wide paged-cache aggregates (sums of the per-replica
    v3 gauges): ``fleet_cache_pages_total`` / ``fleet_cache_pages_in_use``
    / ``fleet_cache_hbm_bytes`` / ``fleet_ring_bytes_moved``."""
    SCHEMA_VERSION = 2

    def __init__(self, router: "FleetRouter"):
        self._router = router
        self.n_submitted = 0
        self.n_routed = 0
        self.n_preemptions = 0
        self.n_requeued = 0
        self.n_degraded = 0

    @property
    def n_finished(self) -> int:
        return sum(t.n_finished for t in self._router.tenants.values())

    @property
    def fleet_realized_q(self) -> float:
        """Decision-weighted realized hard rate across the fleet."""
        dec = sum(r.stats.n_decisions for r in self._router.replicas)
        hard = sum(r.stats.n_stage2 for r in self._router.replicas)
        return hard / dec if dec else 0.0

    def as_dict(self) -> dict:
        rt = self._router
        return {
            "schema_version": self.SCHEMA_VERSION,
            "policy": rt.policy,
            "n_replicas": len(rt.replicas),
            "n_pending": len(rt._pending),
            "n_submitted": self.n_submitted,
            "n_routed": self.n_routed,
            "n_finished": self.n_finished,
            "n_preemptions": self.n_preemptions,
            "n_requeued": self.n_requeued,
            "n_degraded": self.n_degraded,
            # the fleet-wide contract: requests are re-queued, never
            # dropped — anything submitted is pending, in flight, or done
            "n_dropped": (self.n_submitted - self.n_finished
                          - len(rt._pending) - sum(
                              t.inflight for t in rt.tenants.values())),
            "fleet_realized_q": self.fleet_realized_q,
            "fleet_cache_pages_total": sum(
                r.stats.cache_pages_total for r in rt.replicas),
            "fleet_cache_pages_in_use": sum(
                r.stats.cache_pages_in_use for r in rt.replicas),
            "fleet_cache_hbm_bytes": sum(
                r.stats.cache_hbm_bytes for r in rt.replicas),
            "fleet_ring_bytes_moved": sum(
                r.stats.ring_bytes_moved for r in rt.replicas),
            "health": list(rt.health),
            "tenants": {name: t.as_dict()
                        for name, t in sorted(rt.tenants.items())},
            "replicas": [r.stats.as_dict() for r in rt.replicas],
        }


@dataclass(order=True)
class _Pending:
    """Router-queue entry, ordered by (priority, arrival, submit seq) —
    the admission order a route pass walks."""
    priority: int
    arrival_time: float
    seq: int
    req: Request = field(compare=False)


class FleetRouter:
    """Admission owner across N ``ReplicaHandle`` replicas.

    ``max_queue_per_replica`` bounds each replica's unadmitted queue (the
    backpressure that makes load-aware policies meaningful; default: the
    replica's own ``n_slots``). ``provisioned_p`` optionally declares each
    replica's design-time hard rate (written to its ``stats`` so drift is
    measurable); ``drift_aware`` falls back to 0.5 for undeclared
    replicas."""

    def __init__(self, replicas: Sequence, *, policy: str = "drift_aware",
                 slo_classes: Optional[Dict[str, SLOClass]] = None,
                 max_queue_per_replica: Optional[int] = None,
                 provisioned_p: Optional[Sequence[float]] = None,
                 events: Optional[EventLog] = None):
        if not replicas:
            raise ValueError("a fleet needs at least one replica")
        if policy not in ROUTING_POLICIES:
            raise ValueError(f"policy must be one of {ROUTING_POLICIES}, "
                             f"got {policy!r}")
        self.replicas = list(replicas)
        clock = self.replicas[0].clock
        if any(r.clock is not clock for r in self.replicas):
            raise ValueError("all replicas must share ONE clock object — "
                             "pass the same clock= to every build()")
        self.clock = clock
        self.policy = policy
        self.slo_classes = dict(slo_classes if slo_classes is not None
                                else DEFAULT_SLO_CLASSES)
        self.max_queue = max_queue_per_replica
        if provisioned_p is not None:
            if len(provisioned_p) != len(self.replicas):
                raise ValueError(
                    f"provisioned_p has {len(provisioned_p)} entries for "
                    f"{len(self.replicas)} replicas")
            for r, p in zip(self.replicas, provisioned_p):
                r.stats.provisioned_p = float(p)
        self.health: List[str] = [HEALTHY] * len(self.replicas)
        self.tenants: Dict[str, TenantState] = {}
        self.events = events if events is not None else EventLog(cap=4096)
        self.stats = FleetStats(self)
        self._pending: List[_Pending] = []
        self._seen: set = set()          # every sid ever submitted (no-dup)
        self._routed_to: Dict[int, int] = {}   # sid -> replica idx (queued
        self._tenant_of: Dict[int, str] = {}   # or in flight)
        self._seq = itertools.count()
        self._rr = 0                           # round_robin cursor

    # -- admission -----------------------------------------------------------

    def _slo(self, req: Request) -> SLOClass:
        try:
            return self.slo_classes[req.slo_class]
        except KeyError:
            raise ValueError(
                f"request {req.sample_id}: unknown slo_class "
                f"{req.slo_class!r} (have {sorted(self.slo_classes)})"
            ) from None

    def submit(self, req: Request) -> None:
        """Fleet-wide validated admission: same errors as a single
        replica's ``submit`` (shared ``serve_api.validate_request``), with
        the duplicate check over everything the FLEET has ever seen."""
        validate_request(req, max_len=None,
                         is_dup=lambda sid: sid in self._seen)
        slo = self._slo(req)                 # reject unknown class early
        self._seen.add(req.sample_id)
        tenant = self.tenants.setdefault(req.tenant, TenantState())
        tenant.n_submitted += 1
        self.stats.n_submitted += 1
        self._pending.append(_Pending(slo.priority, req.arrival_time,
                                      next(self._seq), req))
        self.events.emit("submit", sid=req.sample_id, tenant=req.tenant,
                         slo=req.slo_class)

    # -- placement -----------------------------------------------------------

    def _room(self, i: int) -> bool:
        r = self.replicas[i]
        cap = self.max_queue if self.max_queue is not None else r.n_slots
        return r.queue_len < cap

    def _eligible(self, req: Request) -> List[int]:
        return [i for i in range(len(self.replicas))
                if self.health[i] == HEALTHY and self._room(i)]

    def _score_drift_aware(self, i: int, d_hat: float) -> float:
        """Lower is better: provisioning mismatch + saturation penalty +
        load tiebreak. A replica whose realized q already runs above its
        provisioned p has a saturating stage-2 bucket — routing more hard
        traffic there buys latency, not throughput."""
        r = self.replicas[i]
        p = r.stats.provisioned_p
        p = 0.5 if p is None else float(p)
        q = r.stats.realized_q_ewma
        load = (r.n_busy + r.queue_len) / max(r.n_slots, 1)
        return abs(d_hat - p) + max(0.0, q - p) + 0.25 * load

    def _tenant_difficulty(self, tenant: str) -> float:
        t = self.tenants.get(tenant)
        if t is not None and t.difficulty_ewma is not None:
            return t.difficulty_ewma
        # prior before the tenant's first finish: the fleet's mean
        # provisioned p (an uninformed request is best matched to an
        # average replica), else 0.5
        ps = [r.stats.provisioned_p for r in self.replicas
              if r.stats.provisioned_p is not None]
        return float(sum(ps) / len(ps)) if ps else 0.5

    def _place(self, req: Request, candidates: List[int]) -> int:
        if self.policy == "round_robin":
            for k in range(len(self.replicas)):
                i = (self._rr + k) % len(self.replicas)
                if i in candidates:
                    self._rr = (i + 1) % len(self.replicas)
                    return i
            raise AssertionError("no candidate")   # callers pass non-empty
        if self.policy == "least_loaded":
            return min(candidates, key=lambda i: (
                self.replicas[i].n_busy + self.replicas[i].queue_len, i))
        d_hat = self._tenant_difficulty(req.tenant)
        return min(candidates,
                   key=lambda i: (self._score_drift_aware(i, d_hat), i))

    # -- preemption ----------------------------------------------------------

    def _preemptible(self, prio: int) -> List:
        """(victim_priority, -arrival, replica_idx, req) for every QUEUED
        request a priority-``prio`` arrival may displace: strictly lower
        priority classes, or same-priority tenants over their in-flight
        budget. Sorted worst-victim-first."""
        victims = []
        for i, r in enumerate(self.replicas):
            if self.health[i] != HEALTHY:
                continue                     # degrade already revoked these
            for q in r.queue:
                v_slo = self._slo(q)
                over = (v_slo.max_inflight is not None
                        and self.tenants[q.tenant].inflight
                        > v_slo.max_inflight)
                if v_slo.priority > prio or (v_slo.priority == prio
                                             and over):
                    victims.append((v_slo.priority, q.arrival_time, i, q))
        # displace the lowest class first; within a class, the latest
        # arrival (it has waited least)
        victims.sort(key=lambda v: (-v[0], -v[1]))
        return victims

    def _try_preempt(self, req: Request, slo: SLOClass) -> Optional[int]:
        """Blocked-by-room path: displace one queued lower-priority (or
        over-budget) request back into the router's pending set, freeing
        its replica slot for ``req``. Returns the freed replica index, or
        None when nothing is preemptible."""
        for _prio, _at, i, victim in self._preemptible(slo.priority):
            taken = self.replicas[i].revoke_queued([victim.sample_id])
            if not taken:                    # admitted since the scan —
                continue                     # no longer preemptible
            v = taken[0]
            t = self.tenants[v.tenant]
            t.inflight -= 1
            t.n_preempted += 1
            del self._routed_to[v.sample_id]
            self.stats.n_preemptions += 1
            self.stats.n_requeued += 1
            v_slo = self._slo(v)
            self._pending.append(_Pending(v_slo.priority, v.arrival_time,
                                          next(self._seq), v))
            self.events.emit("preempt", sid=v.sample_id, tenant=v.tenant,
                             slo=v.slo_class, replica=i,
                             by_sid=req.sample_id, by_slo=slo.name)
            return i
        return None

    # -- the routing pass ----------------------------------------------------

    def _route_one(self, req: Request, slo: SLOClass) -> bool:
        tenant = self.tenants[req.tenant]
        if (slo.max_inflight is not None
                and tenant.inflight >= slo.max_inflight):
            return False                     # budget-blocked: preemption
        candidates = self._eligible(req)     # cannot help, wait for
        if not candidates:                   # finishes
            freed = self._try_preempt(req, slo)
            if freed is None:
                return False
            candidates = [freed]
        i = self._place(req, candidates)
        self.replicas[i].submit(req)
        self._routed_to[req.sample_id] = i
        self._tenant_of[req.sample_id] = req.tenant
        tenant.inflight += 1
        self.stats.n_routed += 1
        self.events.emit("route", sid=req.sample_id, tenant=req.tenant,
                         slo=req.slo_class, replica=i, policy=self.policy,
                         queue_len=self.replicas[i].queue_len,
                         n_busy=self.replicas[i].n_busy)
        return True

    def _route(self) -> int:
        """One admission pass: walk arrived pending requests in (priority,
        arrival, seq) order, placing what fits. Blocked requests stay
        pending — nothing is ever dropped."""
        now = self.clock.now()
        arrived = sorted(p for p in self._pending
                         if p.arrival_time <= now)
        if arrived and not any(h == HEALTHY for h in self.health):
            raise RuntimeError(
                "no healthy replica left to route pending requests")
        n = 0
        for p in arrived:
            if self._route_one(p.req, self._slo(p.req)):
                self._pending.remove(p)
                n += 1
        return n

    # -- finish feed ---------------------------------------------------------

    def _harvest(self, i: int) -> None:
        for sid, n_hard, n_dec in self.replicas[i].drain_finished():
            ridx = self._routed_to.pop(sid, None)
            # the replica feed carries only sids; the tenant comes from the
            # routing record stamped in _route_one
            tenant_name = self._tenant_of.pop(sid, "default")
            t = self.tenants.setdefault(tenant_name, TenantState())
            t.inflight = max(0, t.inflight - 1)
            t.n_finished += 1
            t.observe_finish(n_hard, n_dec)
            self.events.emit("finish", sid=sid, tenant=tenant_name,
                             replica=i if ridx is None else ridx,
                             n_decisions=n_dec, n_hard=n_hard)

    # -- the fleet loop ------------------------------------------------------

    def step(self) -> str:
        """One fleet iteration: route what is admissible, step every
        replica with live work, fold finish feeds. Same state machine as a
        single replica: ``"busy"`` (progress), ``"waiting"`` (future
        arrivals own the clock — call ``advance_clock``), ``"idle"``."""
        routed = self._route()
        busy = routed > 0
        waiting = False
        for i, r in enumerate(self.replicas):
            if r.n_busy == 0 and r.queue_len == 0:
                continue
            st = r.step()
            self._harvest(i)
            if st == "busy":
                busy = True
            elif st == "waiting":
                waiting = True
        if busy:
            return "busy"
        if waiting or self._pending:
            return "waiting"
        return "idle"

    def advance_clock(self) -> bool:
        """Jump the shared clock to the next fleet event (earliest pending
        arrival or replica-queued arrival in the future). Returns False
        when there is nothing to advance to."""
        now = self.clock.now()
        times = [p.arrival_time for p in self._pending
                 if p.arrival_time > now]
        for r in self.replicas:
            t = r.next_arrival()
            if t is not None and t > now:
                times.append(t)
        if not times:
            return False
        self.clock.advance_to(min(times))
        return True

    def run(self) -> Dict[int, List[int]]:
        """Drive the fleet until every request finishes; returns the
        merged per-sample results (exactly the streams a single-scheduler
        oracle run of the same requests produces)."""
        while True:
            st = self.step()
            if st == "idle":
                break
            if st == "waiting" and not self.advance_clock():
                # arrived-but-blocked work with no future event means every
                # replica must drain something first; step again (replicas
                # with in-flight work report busy, so this cannot spin)
                if not any(r.n_busy > 0 for r in self.replicas):
                    raise RuntimeError(
                        "fleet wedged: pending requests, no healthy "
                        "capacity, nothing in flight")
        for i, r in enumerate(self.replicas):
            r.drain()                        # final deferred-token harvest
            self._harvest(i)
        return self.results

    @property
    def results(self) -> Dict[int, List[int]]:
        out: Dict[int, List[int]] = {}
        for r in self.replicas:
            out.update(r.results)
        return out

    # -- health / degrade ----------------------------------------------------

    def degrade_replica(self, i: int, failed_devices=None,
                        q: Optional[float] = None,
                        pause_budget_ms: float = float("inf")) -> int:
        """Mark replica ``i`` DEGRADED: it gets no new traffic, its queued
        (unadmitted) requests are revoked and redistributed to the rest of
        the fleet, and its in-flight work drains normally (streams
        unperturbed). With ``failed_devices``, the replica is additionally
        re-planned onto its survivor chips via ``migrate_on_device_loss``
        (live migration at its next discrete re-plan point). Returns the
        number of redistributed requests."""
        if self.health[i] == DEGRADED:
            return 0
        self.health[i] = DEGRADED
        self.stats.n_degraded += 1
        revoked = self.replicas[i].revoke_queued(None)
        for req in revoked:
            t = self.tenants[req.tenant]
            t.inflight -= 1
            del self._routed_to[req.sample_id]
            self._tenant_of.pop(req.sample_id, None)
            slo = self._slo(req)
            self._pending.append(_Pending(slo.priority, req.arrival_time,
                                          next(self._seq), req))
            self.stats.n_requeued += 1
        if failed_devices is not None:
            from repro.runtime.migration import migrate_on_device_loss
            migrate_on_device_loss(self.replicas[i], failed_devices, q=q,
                                   pause_budget_ms=pause_budget_ms)
        self.events.emit("degrade", replica=i,
                         redistributed=len(revoked),
                         device_loss=failed_devices is not None)
        return len(revoked)

    def restore_replica(self, i: int) -> None:
        """Return a drained DEGRADED replica to the routable set."""
        self.health[i] = HEALTHY
        self.events.emit("restore", replica=i)
