"""Fleet router (`runtime/router.py`): per-sample token-stream equivalence
across N replicas under every routing policy, SLO priority admission with
requeue-never-drop preemption, replica degrade redistribution, the tenant
difficulty feed, the FleetStats/event ops surface — and a hypothesis
property test driving random fleets (policy, tenant mix, arrivals,
preemption pressure, one mid-trace degrade) against the analytic oracle."""
import numpy as np
import pytest

from repro.runtime import serve_loop as SL
from repro.runtime.router import (DEFAULT_SLO_CLASSES, DEGRADED, HEALTHY,
                                  ROUTING_POLICIES, FleetRouter, SLOClass,
                                  TenantState)
from repro.runtime.scheduler import (ContinuousScheduler, LogicalClock,
                                     Request)
from repro.runtime.telemetry import EventLog
from test_scheduler import _TOY_S, _toy_tok, toy_decode_fns

_MAX_LEN = _TOY_S + 6


def _req(sid, n_tokens=3, tenant="default", slo="standard", arrival=0.0):
    return Request(sample_id=sid, prompt=np.full((_TOY_S,), sid, np.int32),
                   n_tokens=n_tokens, tenant=tenant, slo_class=slo,
                   arrival_time=arrival)


def _expected(n_tokens_list):
    return {i: [_toy_tok(i, t) for t in range(n)]
            for i, n in enumerate(n_tokens_list)}


def _fleet(n_replicas=2, policy="drift_aware", q_pcts=None, n_slots=3,
           capacity=2, **kw):
    """N continuous replicas over toy DecodeFns sharing ONE LogicalClock.
    Different per-replica q_pct changes only the exit path, never the
    greedy tokens — streams stay placement-independent by construction."""
    q_pcts = q_pcts if q_pcts is not None else [50] * n_replicas
    clock = LogicalClock()
    sc = SL.ServeConfig(capacity=capacity, queue_depth=2, c_thr=0.5)
    reps = [ContinuousScheduler(toy_decode_fns(q), sc, n_slots=n_slots,
                                max_len=_MAX_LEN, clock=clock)
            for q in q_pcts]
    return FleetRouter(reps, policy=policy, **kw)


# ---------------------------------------------------------------------------
# the fleet contract: streams equal the single-scheduler oracle, no policy
# exceptions
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("policy", ROUTING_POLICIES)
def test_fleet_stream_equivalence(policy):
    n_toks = [4, 1, 3, 6, 2, 5, 3, 4]
    router = _fleet(n_replicas=3, policy=policy, q_pcts=[0, 50, 100])
    for i, n in enumerate(n_toks):
        router.submit(_req(i, n, tenant=f"t{i % 2}"))
    assert router.run() == _expected(n_toks)
    d = router.stats.as_dict()
    assert d["n_dropped"] == 0
    assert d["n_finished"] == len(n_toks)
    assert d["n_submitted"] == d["n_routed"] == len(n_toks)
    # traffic actually spread: more than one replica served something
    assert sum(1 for r in d["replicas"] if r["n_samples"] > 0) >= 2


def test_fleet_requires_shared_clock():
    sc = SL.ServeConfig(capacity=2, queue_depth=2, c_thr=0.5)
    reps = [ContinuousScheduler(toy_decode_fns(50), sc, n_slots=2,
                                max_len=_MAX_LEN, clock=LogicalClock())
            for _ in range(2)]                        # two DIFFERENT clocks
    with pytest.raises(ValueError, match="share ONE clock"):
        FleetRouter(reps)
    with pytest.raises(ValueError, match="at least one replica"):
        FleetRouter([])
    with pytest.raises(ValueError, match="policy must be one of"):
        _fleet(policy="random")


def test_fleet_rejects_duplicates_and_unknown_slo():
    router = _fleet()
    router.submit(_req(0))
    with pytest.raises(ValueError, match="duplicate sample id 0"):
        router.submit(_req(0))
    with pytest.raises(ValueError, match="unknown slo_class"):
        router.submit(_req(1, slo="platinum"))
    router.run()
    with pytest.raises(ValueError, match="duplicate sample id 0"):
        router.submit(_req(0))                        # finished sids too


def test_least_loaded_balances():
    router = _fleet(policy="least_loaded", max_queue_per_replica=4)
    for i in range(4):
        router.submit(_req(i))
    router._route()                                   # one admission pass
    loads = [r.n_busy + r.queue_len for r in router.replicas]
    assert loads == [2, 2]


def test_drift_aware_matches_difficulty_to_provisioning():
    router = _fleet(provisioned_p=[0.1, 0.9], max_queue_per_replica=4)
    # prior before any finish: the fleet's mean provisioned p
    assert router._tenant_difficulty("nobody") == pytest.approx(0.5)
    router.tenants["easy"] = TenantState(difficulty_ewma=0.05)
    router.tenants["hard"] = TenantState(difficulty_ewma=0.95)
    assert router._place(_req(0, tenant="easy"), [0, 1]) == 0
    assert router._place(_req(1, tenant="hard"), [0, 1]) == 1


def test_tenant_difficulty_learned_from_finish_feed():
    """All-hard traffic teaches difficulty 1.0, all-easy teaches 0.0 —
    the replica finish feed -> TenantState EWMA plumbing."""
    for q_pct, want in ((100, 1.0), (0, 0.0)):
        router = _fleet(n_replicas=1, q_pcts=[q_pct])
        for i in range(4):
            router.submit(_req(i, n_tokens=4, tenant="t"))
        router.run()
        t = router.tenants["t"]
        assert t.n_finished == 4
        assert t.difficulty_ewma == pytest.approx(want)


def test_tenant_state_ewma_alpha():
    t = TenantState()
    t.observe_finish(3, 3)                            # first finish: q
    assert t.difficulty_ewma == pytest.approx(1.0)
    t.observe_finish(0, 3)                            # alpha=0.3 fold
    assert t.difficulty_ewma == pytest.approx(0.7)
    t.observe_finish(0, 0)                            # no decisions: no-op
    assert t.difficulty_ewma == pytest.approx(0.7)


# ---------------------------------------------------------------------------
# SLO classes: priority admission, budgets, preemption (requeue, not drop)
# ---------------------------------------------------------------------------

def test_gold_preempts_queued_batch_and_nothing_drops():
    n_toks = [3] * 8
    router = _fleet(n_slots=2, max_queue_per_replica=1)
    for i in range(6):
        router.submit(_req(i, n_toks[i], tenant="bulk", slo="batch"))
    # fill slots (2+2) with batch, then one route-only pass so the replica
    # queues hold UNADMITTED batch victims when gold arrives
    for _ in range(2):
        router.step()
    router._route()
    assert sum(r.queue_len for r in router.replicas) > 0
    for i in (6, 7):
        router.submit(_req(i, n_toks[i], tenant="vip", slo="gold"))
    router.step()
    assert router.stats.n_preemptions >= 1
    assert router.stats.n_requeued >= 1
    assert router.tenants["bulk"].n_preempted >= 1
    assert router.run() == _expected(n_toks)          # preempted finished
    assert router.stats.as_dict()["n_dropped"] == 0


def test_max_inflight_budget_respected():
    slos = dict(DEFAULT_SLO_CLASSES)
    slos["batch"] = SLOClass("batch", 2, max_inflight=1)
    n_toks = [3, 3, 3]
    router = _fleet(n_replicas=1, slo_classes=slos)
    for i, n in enumerate(n_toks):
        router.submit(_req(i, n, tenant="t", slo="batch"))
    while router.step() != "idle":
        assert router.tenants["t"].inflight <= 1      # the budget
    assert router.run() == _expected(n_toks)


def test_preemption_never_touches_admitted_requests():
    """A victim admitted between the scan and the revoke yields an empty
    revoke — the router moves on instead of perturbing its stream."""
    router = _fleet(n_slots=2, max_queue_per_replica=1)
    router.submit(_req(0, 3, slo="batch"))
    router.step()                                     # sid 0 is ADMITTED
    assert router.replicas[0].n_busy + router.replicas[1].n_busy == 1
    assert router._try_preempt(_req(9, slo="gold"),
                               router.slo_classes["gold"]) is None
    assert router.stats.n_preemptions == 0


# ---------------------------------------------------------------------------
# health: degrade/restore, redistribution, the no-healthy-replica fence
# ---------------------------------------------------------------------------

def test_degrade_redistributes_and_streams_survive():
    n_toks = [3] * 8
    router = _fleet(n_slots=2, max_queue_per_replica=2)
    for i, n in enumerate(n_toks):
        router.submit(_req(i, n))
    router.step()                                     # some queued on r0
    n_redis = router.degrade_replica(0)
    assert router.health == [DEGRADED, HEALTHY]
    assert router.replicas[0].queue_len == 0          # queue revoked
    assert router.stats.n_degraded == 1
    assert router.stats.n_requeued == n_redis
    assert router.degrade_replica(0) == 0             # idempotent
    assert router.run() == _expected(n_toks)          # in-flight drained,
    assert router.stats.as_dict()["n_dropped"] == 0   # rest redistributed
    router.restore_replica(0)
    assert router.health == [HEALTHY, HEALTHY]


def test_no_healthy_replica_raises():
    router = _fleet()
    router.submit(_req(0))
    for i in range(2):
        router.degrade_replica(i)
    with pytest.raises(RuntimeError, match="no healthy replica"):
        router.step()


# ---------------------------------------------------------------------------
# ops surface: the event feed and the versioned fleet schema
# ---------------------------------------------------------------------------

def test_event_feed_streams_per_request_lifecycle():
    log = EventLog(cap=512)
    seen = []
    log.subscribe(lambda ev: seen.append(ev))
    n_toks = [3, 2, 4]
    router = _fleet(events=log)
    for i, n in enumerate(n_toks):
        router.submit(_req(i, n, tenant="t"))
    router.run()
    kinds = [ev["event"] for ev in seen]
    assert kinds.count("submit") == 3
    assert kinds.count("route") == 3
    assert kinds.count("finish") == 3
    assert [ev["seq"] for ev in seen] == sorted(ev["seq"] for ev in seen)
    fin = [ev for ev in seen if ev["event"] == "finish"]
    assert sorted(ev["sid"] for ev in fin) == [0, 1, 2]
    assert all(ev["tenant"] == "t" for ev in fin)


_FLEET_V2_KEYS = frozenset({
    "schema_version", "policy", "n_replicas", "n_pending", "n_submitted",
    "n_routed", "n_finished", "n_preemptions", "n_requeued", "n_degraded",
    "n_dropped", "fleet_realized_q", "fleet_cache_pages_total",
    "fleet_cache_pages_in_use", "fleet_cache_hbm_bytes",
    "fleet_ring_bytes_moved", "health", "tenants", "replicas",
})


def test_fleet_stats_schema():
    router = _fleet(provisioned_p=[0.2, 0.8])
    n_toks = [3, 2]
    for i, n in enumerate(n_toks):
        router.submit(_req(i, n, tenant="t"))
    router.run()
    d = router.stats.as_dict()
    assert set(d) == _FLEET_V2_KEYS
    assert d["schema_version"] == router.stats.SCHEMA_VERSION == 2
    assert d["policy"] == "drift_aware" and d["n_replicas"] == 2
    assert d["health"] == [HEALTHY, HEALTHY]
    assert d["tenants"]["t"]["n_finished"] == 2
    # each replica entry is itself the versioned ServeStats schema, with
    # the provisioned p the router stamped
    assert [r["schema_version"] for r in d["replicas"]] == [3, 3]
    assert [r["provisioned_p"] for r in d["replicas"]] == [0.2, 0.8]


# ---------------------------------------------------------------------------
# the property test: random fleets vs the analytic oracle
# ---------------------------------------------------------------------------

try:
    from hypothesis import example, given, settings, strategies as st_h
    _HAVE_HYP = True
except ImportError:                                   # pragma: no cover
    _HAVE_HYP = False


if _HAVE_HYP:
    @settings(max_examples=20, deadline=None)
    @given(
        n_tokens_list=st_h.lists(st_h.integers(1, 6), min_size=2,
                                 max_size=12),
        policy=st_h.sampled_from(ROUTING_POLICIES),
        n_replicas=st_h.integers(1, 3),
        n_slots=st_h.integers(1, 3),
        max_queue=st_h.integers(1, 3),
        q_pcts=st_h.lists(st_h.integers(0, 100), min_size=3, max_size=3),
        tenant_picks=st_h.lists(st_h.integers(0, 2), min_size=12,
                                max_size=12),
        slo_picks=st_h.lists(
            st_h.sampled_from(["gold", "standard", "batch"]),
            min_size=12, max_size=12),
        arrival_gaps=st_h.lists(st_h.floats(0.0, 2.0), min_size=12,
                                max_size=12),
        pre_steps=st_h.integers(0, 4),
        degrade=st_h.booleans(),
    )
    # one slot and a one-deep queue: a prefill-only request admitted and
    # finished within one replica step is progress, not a wedged fleet
    @example(n_tokens_list=[3, 1, 1], policy="round_robin", n_replicas=1,
             n_slots=1, max_queue=1, q_pcts=[0, 0, 0], tenant_picks=[0] * 12,
             slo_picks=["gold"] * 12, arrival_gaps=[0.0] * 12, pre_steps=0,
             degrade=False)
    def test_fleet_invariants_random(n_tokens_list, policy, n_replicas,
                                     n_slots, max_queue, q_pcts,
                                     tenant_picks, slo_picks, arrival_gaps,
                                     pre_steps, degrade):
        """Random fleet geometry x routing policy x tenant/SLO mix x
        arrival trace, with preemption pressure (bounded queues, mixed
        priorities) and one mid-trace replica degrade: no sample dropped
        or duplicated, every per-sample token stream exactly equal to the
        analytic oracle, all slots drained, nothing left pending."""
        router = _fleet(n_replicas=n_replicas, policy=policy,
                        q_pcts=q_pcts[:n_replicas], n_slots=n_slots,
                        max_queue_per_replica=max_queue)
        t = 0.0
        for i, n in enumerate(n_tokens_list):
            t += arrival_gaps[i]
            router.submit(_req(i, n, tenant=f"t{tenant_picks[i]}",
                               slo=slo_picks[i], arrival=t))
        for _ in range(pre_steps):
            if router.step() == "waiting":
                router.advance_clock()
        if degrade and n_replicas > 1:
            router.degrade_replica(0)                 # mid-trace loss
        res = router.run()
        expect = _expected(n_tokens_list)
        assert set(res) == set(expect)                # no drop, no phantom
        assert res == expect                          # order + no dup
        d = router.stats.as_dict()
        assert d["n_dropped"] == 0
        assert d["n_finished"] == len(n_tokens_list)
        assert d["n_pending"] == 0
        assert all(t["inflight"] == 0 for t in d["tenants"].values())
        for r in router.replicas:
            assert r.n_busy == 0 and r.queue_len == 0
