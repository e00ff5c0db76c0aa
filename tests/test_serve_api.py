"""Transport-agnostic serving API (`runtime/serve_api.py`): the shared
submit-side validation (byte-identical errors across every admission
surface), the RequestQueue revocation/copy semantics the fleet router and
live migration ride, the ReplicaHandle protocol the scheduler implements,
the unified `build()` construction matrix, and the versioned ServeStats
schema freeze."""
import json
from pathlib import Path

import numpy as np
import pytest

from repro.runtime import serve_loop as SL
from repro.runtime.scheduler import (ContinuousScheduler, LogicalClock,
                                     Request, ServeStats)
from repro.runtime.serve_api import (ReplicaHandle, RequestQueue, build,
                                     validate_request)
from test_scheduler import _TOY_S, toy_decode_fns

_REPO_ROOT = Path(__file__).resolve().parents[1]


def _req(sid, n_tokens=2, arrival=0.0, prompt_len=_TOY_S):
    return Request(sample_id=sid,
                   prompt=np.full((prompt_len,), sid, np.int32),
                   n_tokens=n_tokens, arrival_time=arrival)


# ---------------------------------------------------------------------------
# one validation definition, byte-identical errors on every surface
# ---------------------------------------------------------------------------

def _submit_error(surface, req) -> str:
    with pytest.raises(ValueError) as ei:
        surface(req)
    return str(ei.value)


def test_validate_request_messages():
    assert _submit_error(validate_request, _req(0, n_tokens=0)) \
        == "n_tokens must be >= 1, got 0"
    msg = _submit_error(
        lambda r: validate_request(r, max_len=5), _req(7, n_tokens=9))
    assert msg == f"request 7: S + n_tokens = {_TOY_S + 9} exceeds pool " \
                  f"max_len 5"
    assert _submit_error(
        lambda r: validate_request(r, is_dup=lambda sid: True), _req(3)) \
        == "duplicate sample id 3"


def test_submit_errors_identical_across_surfaces():
    """The same malformed request produces the same error string whether
    it hits a bare RequestQueue, the continuous scheduler, or the fleet
    router — the single-definition contract."""
    from repro.runtime.router import FleetRouter
    max_len = _TOY_S + 4
    sc = SL.ServeConfig(capacity=2, queue_depth=2, c_thr=0.5)

    def surfaces():
        clock = LogicalClock()
        cont = ContinuousScheduler(toy_decode_fns(50), sc, n_slots=2,
                                   max_len=max_len, clock=clock)
        return {
            "queue": RequestQueue(max_len=max_len),
            "continuous": cont,
            # the router is unbounded in max_len (replicas own pool
            # geometry) so it only joins the n_tokens/duplicate cases
            "router": FleetRouter([cont]),
        }

    def errs(req, *, skip=()):
        out = {}
        for name, s in surfaces().items():
            if name in skip:
                continue
            fn = s.append if isinstance(s, RequestQueue) else s.submit
            out[name] = _submit_error(fn, req)
        return out

    got = errs(_req(0, n_tokens=0))
    assert len(set(got.values())) == 1, got
    got = errs(_req(1, n_tokens=99), skip=("router",))
    assert len(set(got.values())) == 1, got
    # duplicates: submit once, then again
    for name, s in surfaces().items():
        fn = s.append if isinstance(s, RequestQueue) else s.submit
        fn(_req(5))
        assert _submit_error(fn, _req(5)) == "duplicate sample id 5", name


# ---------------------------------------------------------------------------
# RequestQueue semantics: FIFO, head-gated arrival, revocation, snapshot
# ---------------------------------------------------------------------------

def test_request_queue_fifo_and_inspection():
    q = RequestQueue()
    for sid, t in [(3, 1.0), (1, 2.0), (2, 0.5)]:
        q.append(_req(sid, arrival=t))
    assert len(q) == 3 and bool(q)
    assert [r.sample_id for r in q] == [3, 1, 2]      # arrival order kept
    assert q.next_arrival() == 1.0                    # HEAD gates admission
    assert 3 in q and 9 not in q
    assert q.popleft().sample_id == 3
    assert 3 not in q                                 # pop = admission
    assert q.next_arrival() == 2.0
    q.append(_req(3))                                 # popped sid re-usable
    assert RequestQueue().next_arrival() is None


def test_request_queue_revoke_unadmitted_only():
    q = RequestQueue()
    for sid in range(5):
        q.append(_req(sid, arrival=float(sid)))
    admitted = q.popleft()                            # sid 0 is in flight
    taken = q.revoke([1, 3, 0, 99])                   # 0/99 aren't queued
    assert [r.sample_id for r in taken] == [1, 3]
    assert [r.sample_id for r in q] == [2, 4]         # survivor order kept
    assert admitted.sample_id == 0
    # revoked sids are re-appendable (re-queue on another replica)
    q.append(taken[0])
    assert [r.sample_id for r in q.revoke(None)] == [2, 4, 1]
    assert len(q) == 0


def test_request_queue_copy_is_independent():
    q = RequestQueue(max_len=20)
    q.append(_req(0))
    q.append(_req(1))
    import copy
    snap = copy.copy(q)
    q.popleft()
    q.append(_req(2))
    assert [r.sample_id for r in snap] == [0, 1]      # snapshot unperturbed
    assert [r.sample_id for r in q] == [1, 2]
    with pytest.raises(ValueError, match="duplicate sample id"):
        snap.append(_req(1))                          # membership copied too


# ---------------------------------------------------------------------------
# ReplicaHandle: the scheduler implements the routable surface
# ---------------------------------------------------------------------------

def test_schedulers_implement_replica_handle():
    sc = SL.ServeConfig(capacity=2, queue_depth=2, c_thr=0.5)
    cont = ContinuousScheduler(toy_decode_fns(50), sc, n_slots=2,
                               max_len=_TOY_S + 4, clock=LogicalClock())
    assert isinstance(cont, ReplicaHandle)
    assert cont.n_busy == 0 and cont.queue_len == 0
    assert cont.next_arrival() is None
    assert cont.drain_finished() == []
    assert not isinstance(object(), ReplicaHandle)


def test_continuous_finish_feed_per_request():
    """drain_finished hands (sid, n_hard, n_decisions) per finished
    request — the per-request hardness the router's tenant estimates
    fold. All-hard toy traffic: n_hard == n_decisions == n_tokens - 1."""
    sc = SL.ServeConfig(capacity=2, queue_depth=2, c_thr=0.5)
    sched = ContinuousScheduler(toy_decode_fns(100), sc, n_slots=2,
                                max_len=_TOY_S + 6, clock=LogicalClock())
    for sid, n in [(0, 4), (1, 2)]:
        sched.submit(_req(sid, n_tokens=n))
    sched.run()
    feed = sorted(sched.drain_finished())
    assert [(s, h, d) for s, h, d in feed] == [(0, 3, 3), (1, 1, 1)]
    assert sched.drain_finished() == []               # pop semantics


# ---------------------------------------------------------------------------
# build(): the one construction path
# ---------------------------------------------------------------------------

def test_build_matrix_types(tiny_cfg, tiny_params, tiny_spec):
    sc = SL.ServeConfig(capacity=2, queue_depth=2, c_thr=0.5)
    b = lambda **kw: build(tiny_params, tiny_cfg, tiny_spec, sc, **kw)
    assert isinstance(b(mode="prefill", scheduler=None), SL.TwoStageServer)
    assert isinstance(b(mode="prefill", scheduler=None, host=True),
                      SL.HostLoopServer)
    assert isinstance(b(scheduler=None, host=True), SL.HostLoopDecoder)
    cont = b(n_slots=2, max_len=12, page_size=4, clock=LogicalClock())
    assert isinstance(cont, ContinuousScheduler)
    assert cont.page_size == 4 and cont.n_pages == 2 * 12 // 4
    assert cont.fns_factory is not None               # migration rebuilds


@pytest.mark.parametrize("kw,match", [
    (dict(mode="train"), "mode must be one of"),
    (dict(scheduler="fifo"), "takes scheduler='continuous'"),
    (dict(scheduler="sync", n_slots=2, max_len=12, page_size=4),
     "takes scheduler='continuous'"),
    (dict(scheduler=None), "takes scheduler='continuous'"),
    (dict(mode="prefill", scheduler="continuous"), "takes scheduler=None"),
    (dict(scheduler="continuous", host=True), "takes scheduler=None"),
    (dict(n_slots=2, max_len=12), "needs n_slots, max_len"),
    (dict(scheduler=None, host=True, page_size=4), "page pool"),
], ids=["mode", "unknown_scheduler", "sync", "bare_decode_server",
        "prefill_scheduler", "host_scheduler", "no_page_size",
        "paged_oracle"])
def test_build_rejects_bad_points(tiny_cfg, tiny_params, tiny_spec, kw,
                                  match):
    """Decode serving is the continuous scheduler over the paged store;
    the sync policy and the bare step-synchronous decode server are gone,
    and build refuses to name them."""
    sc = SL.ServeConfig(capacity=2, queue_depth=2, c_thr=0.5)
    with pytest.raises(ValueError, match=match):
        build(tiny_params, tiny_cfg, tiny_spec, sc, **kw)


# ---------------------------------------------------------------------------
# ServeStats: the versioned, frozen schema
# ---------------------------------------------------------------------------

_SERVE_STATS_V3_KEYS = frozenset({
    "schema_version", "n_samples", "n_decisions", "n_exited", "n_stage2",
    "n_stalls", "realized_q", "decisions_per_sample", "mean_bucket_fill",
    "stage1_chips", "stage2_chips", "stage1_occupancy", "stage2_occupancy",
    "n_finished", "latency_p50", "latency_p90", "latency_p99",
    "provisioned_p", "realized_q_ewma", "q_drift", "n_migrations",
    "n_migration_rollbacks", "migration_pause_p50_ms",
    "migration_pause_p99_ms", "cache_pages_total", "cache_pages_in_use",
    "cache_pages_free", "cache_hbm_bytes", "page_fragmentation",
    "ring_bytes_moved", "realized_q_series",
})


def test_serve_stats_schema_frozen():
    """Adding/removing/renaming an as_dict key REQUIRES a schema_version
    bump — this freeze makes that deliberate. (If you changed the schema
    on purpose: bump ServeStats.SCHEMA_VERSION, update this set, and the
    README's serving-stats schema table.)"""
    d = ServeStats().as_dict()
    assert set(d) == _SERVE_STATS_V3_KEYS
    assert d["schema_version"] == ServeStats.SCHEMA_VERSION == 3


# baseline_cpu.json metric leaves that are sourced straight from a
# ServeStats field (vs computed by the benchmark itself) -> the as_dict
# key that must keep existing for the gate to stay meaningful
_STATS_BACKED_LEAVES = {
    "migration_pause_p99_ms": "migration_pause_p99_ms",
    "n_migrations": "n_migrations",
    "n_rollbacks": "n_migration_rollbacks",
}


def test_baseline_gated_metrics_exist_in_stats_schema():
    baseline = json.loads(
        (_REPO_ROOT / "benchmarks" / "baseline_cpu.json").read_text())
    d = ServeStats().as_dict()
    hits = 0
    for metric in baseline["metrics"]:
        leaf = metric.rsplit(".", 1)[-1]
        if leaf in _STATS_BACKED_LEAVES:
            hits += 1
            assert _STATS_BACKED_LEAVES[leaf] in d, metric
    assert hits >= 3          # the map must not go dead silently
