"""The observability plane (``runtime/observe.py``): frozen metrics
schema, Prometheus exposition render/parse, the zero-dependency HTTP
endpoint, span assembly + Chrome trace export against a REAL continuous
scheduler run (toy stage fns), the stats sampler's counters, and the
profiler hooks' inert-by-default contract.
"""
import json
import urllib.request

import pytest

from repro.kernels import dispatch
from repro.runtime import observe
from repro.runtime import serve_loop as SL
from repro.runtime.scheduler import LogicalClock
from repro.runtime.telemetry import EventLog

from test_scheduler import (_TOY_S, _toy_expected, _toy_requests,
                            toy_decode_fns)

# ---------------------------------------------------------------------------
# the FROZEN metrics schema — adding/renaming/relabeling a metric must be a
# deliberate, reviewed act (dashboards and alerts key on these), exactly
# like the ServeStats v3 key set in test_serve_api.py
# ---------------------------------------------------------------------------

_SCHEMA_V1 = {
    ("repro_requests_submitted_total", "c", ("replica",)),
    ("repro_requests_finished_total", "c", ("replica",)),
    ("repro_decisions_total", "c", ("replica",)),
    ("repro_exited_total", "c", ("replica",)),
    ("repro_stage2_total", "c", ("replica",)),
    ("repro_stalls_total", "c", ("replica",)),
    ("repro_buckets_total", "c", ("replica",)),
    ("repro_ring_bytes_moved_total", "c", ("replica",)),
    ("repro_migrations_total", "c", ("replica",)),
    ("repro_migration_rollbacks_total", "c", ("replica",)),
    ("repro_realized_q", "g", ("replica",)),
    ("repro_realized_q_ewma", "g", ("replica",)),
    ("repro_q_drift", "g", ("replica",)),
    ("repro_stage1_occupancy", "g", ("replica",)),
    ("repro_stage2_occupancy", "g", ("replica",)),
    ("repro_mean_bucket_fill", "g", ("replica",)),
    ("repro_slots_busy", "g", ("replica",)),
    ("repro_queue_depth", "g", ("replica",)),
    ("repro_cache_pages_total", "g", ("replica",)),
    ("repro_cache_pages_in_use", "g", ("replica",)),
    ("repro_cache_pages_in_use_peak", "g", ("replica",)),
    ("repro_cache_hbm_bytes", "g", ("replica",)),
    ("repro_page_fragmentation", "g", ("replica",)),
    ("repro_events_dropped_total", "c", ("feed",)),
    ("repro_routed_total", "c", ("policy",)),
    ("repro_preemptions_total", "c", ()),
    ("repro_fleet_pending", "g", ()),
    ("repro_backend_resolutions_total", "c", ()),
    ("repro_backend_compiles_total", "c", ("program",)),
    ("repro_request_latency_seconds", "h", ("replica",)),
}


def test_metrics_schema_is_frozen():
    got = {(n, k, labels) for n, k, labels, _ in observe.METRICS_SCHEMA}
    assert got == _SCHEMA_V1, (
        "METRICS_SCHEMA changed — dashboards/alerts key on metric names "
        "and labels; update _SCHEMA_V1 here only as a deliberate schema "
        f"bump. diff: {got.symmetric_difference(_SCHEMA_V1)}")
    helps = [h for *_x, h in observe.METRICS_SCHEMA]
    assert all(helps), "every metric needs HELP text"


# ---------------------------------------------------------------------------
# registry + exposition
# ---------------------------------------------------------------------------

def test_registry_is_closed():
    reg = observe.MetricsRegistry()
    with pytest.raises(KeyError):
        reg.get("repro_made_up_total")


def test_metric_label_validation():
    reg = observe.MetricsRegistry()
    with pytest.raises(ValueError):
        reg.get("repro_requests_finished_total").inc(1, shard="x")


def test_exposition_round_trip():
    reg = observe.MetricsRegistry()
    reg.get("repro_requests_finished_total").inc(3, replica=0)
    reg.get("repro_realized_q").set(0.25, replica=0)
    reg.get("repro_fleet_pending").set(7)
    text = reg.exposition()
    assert "# HELP repro_requests_finished_total" in text
    assert "# TYPE repro_requests_finished_total counter" in text
    got = observe.parse_exposition(text)
    assert got['repro_requests_finished_total{replica="0"}'] == 3.0
    assert got['repro_realized_q{replica="0"}'] == 0.25
    assert got["repro_fleet_pending"] == 7.0


def test_counter_set_total_is_monotone_max():
    reg = observe.MetricsRegistry()
    m = reg.get("repro_decisions_total")
    m.set_total(10, replica=0)
    m.set_total(7, replica=0)          # stale sample never regresses it
    m.set_total(12, replica=0)
    assert m.value(replica=0) == 12.0


def test_histogram_exposition_cumulative():
    reg = observe.MetricsRegistry()
    h = reg.get("repro_request_latency_seconds")
    for v in (0.003, 0.003, 0.3, 20.0):
        h.observe(v, replica=0)
    got = observe.parse_exposition(reg.exposition())
    k = 'repro_request_latency_seconds_bucket{replica="0",le="%s"}'
    assert got[k % "0.005"] == 2.0
    assert got[k % "0.5"] == 3.0
    assert got[k % "+Inf"] == 4.0                    # cumulative
    assert got['repro_request_latency_seconds_count{replica="0"}'] == 4.0
    assert got['repro_request_latency_seconds_sum{replica="0"}'] == \
        pytest.approx(20.306)


def test_parse_exposition_rejects_garbage():
    with pytest.raises(ValueError):
        observe.parse_exposition("")
    with pytest.raises(ValueError):
        observe.parse_exposition("this is not prometheus text\n")


def test_metrics_server_scrape(tmp_path):
    reg = observe.MetricsRegistry()
    reg.get("repro_fleet_pending").set(3)
    with observe.MetricsServer(reg, port=0) as srv:
        assert srv.port > 0
        with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/metrics", timeout=10) as r:
            assert r.status == 200
            assert "text/plain" in r.headers["Content-Type"]
            body = r.read().decode()
    got = observe.parse_exposition(body)
    assert got["repro_fleet_pending"] == 3.0
    # dump path shares the renderer
    out = tmp_path / "m.prom"
    observe.dump_metrics(reg, str(out))
    assert observe.parse_exposition(out.read_text())["repro_fleet_pending"] \
        == 3.0


# ---------------------------------------------------------------------------
# tracer + sampler against a real continuous-scheduler run
# ---------------------------------------------------------------------------

def _observed_toy_run(n_toks=(5, 1, 3, 6, 2), q_pct=40):
    events = EventLog(cap=4096)
    tracer = observe.Tracer()
    reg = observe.MetricsRegistry()
    sampler = observe.StatsSampler(reg, cadence_s=0.0)   # sample every event
    fns = toy_decode_fns(q_pct=q_pct)
    sc = SL.ServeConfig(capacity=2, queue_depth=2, c_thr=0.5)
    sched = SL.ContinuousScheduler(fns, sc, n_slots=3, max_len=_TOY_S + 6,
                                   clock=LogicalClock(), events=events)
    tracer.attach_scheduler(sched)
    sampler.attach_scheduler(sched)
    for r in _toy_requests(list(n_toks)):
        sched.submit(r)
    res = sched.run()
    sampler.sample()
    sampler.close()
    tracer.close()
    return res, tracer, reg, sched


def test_tracer_on_real_scheduler_run():
    n_toks = (5, 1, 3, 6, 2)
    res, tracer, _reg, _sched = _observed_toy_run(n_toks)
    assert res == _toy_expected(list(n_toks))        # tracing never perturbs
    comp = tracer.completeness(expect_sids=set(range(len(n_toks))))
    assert comp["complete"], comp
    assert comp["n_finished"] == len(n_toks)


def test_sampler_feeds_registry_from_real_run():
    n_toks = (5, 1, 3, 6, 2)
    _res, _tracer, reg, sched = _observed_toy_run(n_toks)
    got = observe.parse_exposition(reg.exposition())
    assert got['repro_requests_finished_total{replica="0"}'] == len(n_toks)
    assert got['repro_requests_submitted_total{replica="0"}'] == len(n_toks)
    assert got['repro_decisions_total{replica="0"}'] == \
        sched.stats.n_decisions
    assert got['repro_stage2_total{replica="0"}'] == sched.stats.n_stage2
    assert got['repro_request_latency_seconds_count{replica="0"}'] == \
        len(n_toks)
    assert any(k.startswith("repro_backend_compiles_total{program=")
               for k in got)
    assert got["repro_backend_resolutions_total"] >= 1


def test_chrome_trace_structure():
    n_toks = (3, 2)
    _res, tracer, _reg, _sched = _observed_toy_run(n_toks)
    trace = tracer.chrome_trace()
    evs = trace["traceEvents"]
    phases = {e["ph"] for e in evs}
    assert phases <= {"X", "i", "M"}
    assert any(e["ph"] == "X" and e["name"] == "request" for e in evs)
    for e in evs:
        if e["ph"] == "X":
            assert e["dur"] >= 0 and "pid" in e and "tid" in e
    meta = {(e["name"], e["args"]["name"]) for e in evs if e["ph"] == "M"}
    assert ("process_name", "replica0") in meta
    # round-trips through json (Perfetto loads files, not objects)
    json.loads(json.dumps(trace))


def test_span_jsonl_export(tmp_path):
    _res, tracer, _reg, _sched = _observed_toy_run((3, 2))
    p = tmp_path / "spans.jsonl"
    n = tracer.export_jsonl(str(p))
    lines = [json.loads(ln) for ln in p.read_text().splitlines()]
    assert len(lines) == n > 0
    kinds = {ln["kind"] for ln in lines}
    assert kinds == {"span", "instant"}


def test_export_events_jsonl_appends_with_extra(tmp_path):
    log = EventLog(cap=16)
    log.emit("a", x=1)
    log.emit("b", y=2)
    p = tmp_path / "ev.jsonl"
    assert observe.export_events_jsonl(str(p), log, pid=123) == 2
    assert observe.export_events_jsonl(str(p), log, pid=123) == 2  # append
    lines = [json.loads(ln) for ln in p.read_text().splitlines()]
    assert len(lines) == 4
    assert all(ln["pid"] == 123 for ln in lines)
    assert lines[0]["event"] == "a" and lines[0]["x"] == 1


def test_sampler_tracks_dropped_events():
    reg = observe.MetricsRegistry()
    sampler = observe.StatsSampler(reg, cadence_s=0.0)
    log = EventLog(cap=2)
    sampler.attach_log("tiny", log)
    for i in range(5):
        log.emit("e", i=i)
    sampler.sample()
    sampler.close()
    assert reg.get("repro_events_dropped_total").value(feed="tiny") == 3.0


# ---------------------------------------------------------------------------
# profiler hooks + backend-resolution counter
# ---------------------------------------------------------------------------

def test_annotate_is_inert_by_default():
    assert not observe.profiling_active()
    with observe.annotate("anything"):
        pass                          # nullcontext: no profiler dependency
    assert observe.annotate("a") is observe.annotate("b")  # shared, no alloc


def test_backend_resolution_counter_memoized():
    n0 = dispatch.n_backend_resolutions()
    b1 = dispatch.kernel_backend()
    n1 = dispatch.n_backend_resolutions()
    b2 = dispatch.kernel_backend()    # memo hit: same args
    n2 = dispatch.n_backend_resolutions()
    assert b1 == b2
    assert n1 >= n0
    assert n2 == n1                   # a hit never counts as a resolution

