"""The program's own record of a profiler window (``observe.WindowRecord``)
and the spans inside ``ContinuousScheduler.step()``: what they nest under,
that their counts agree with ``ServeStats`` and with the chunks admitted,
that the record lands on the profiler trace's clock through its
``profile_window`` anchor, that compiles are named by program, and that
the jitted programs keep the module names the benchmark's readers match.
"""
import glob
import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels import dispatch
from repro.runtime import observe
from repro.runtime import scheduler as SCH
from repro.runtime import serve_loop as SL
from repro.runtime.scheduler import (ContinuousScheduler, LogicalClock,
                                     Request, ServeConfig)
from repro.runtime.telemetry import EventLog

from test_scheduler import _TOY_S, _toy_requests, toy_decode_fns

STEP_SPANS = ("admit_chunk", "prefill", "admit_sync", "harvest_sync",
              "stage2_bucket_dispatch", "finish_tick_sync",
              "pool_tick_fused", "pool_tick", "ring_enqueue")
REASONS = ("full", "eager", "forced", "backpressure")
N_TOKS = [7, 3, 5, 1, 7, 2, 6, 4]


@pytest.fixture(scope="module")
def prompt(tiny_cfg):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(5), (8, 8), 0,
                                         tiny_cfg.vocab))


@pytest.fixture(scope="module")
def paged_fns(tiny_cfg, tiny_params, tiny_spec):
    return SL.decode_stage_fns(tiny_params, tiny_cfg, tiny_spec,
                               page_size=4)


def _sched(fns, c_thr, n_slots=4, capacity=2, queue_depth=2, **kw):
    sc = ServeConfig(capacity=capacity, queue_depth=queue_depth,
                     c_thr=c_thr, max_pending=2)
    return ContinuousScheduler(fns, sc, n_slots=n_slots, max_len=24,
                               clock=LogicalClock(), **kw)


def _submit(s, prompt, n_toks):
    for i, n in enumerate(n_toks):
        s.submit(Request(sample_id=i, prompt=prompt[i], n_tokens=n))


def _steps(s):
    while s.step() != "idle":
        pass


def _finish(s):
    while s._pending:
        s._harvest_one()
    return s.results


def _ancestors(rec, i):
    names, p = [], rec.spans[i][3]
    while p >= 0:
        names.append(rec.spans[p][0])
        p = rec.spans[p][3]
    return names


# ---------------------------------------------------------------------------
# the record's arithmetic on a hand-made record
# ---------------------------------------------------------------------------

def test_record_helpers_by_hand():
    rec = observe.WindowRecord()
    rec.spans = [
        ["profile_window", 0, 1000, -1, {}],
        ["sched_step", 100, 500, 0, {}],
        ["admit_chunk", 120, 300, 1, {}],
        ["admit_sync", 200, 260, 2, {}],          # a grandchild
        ["finish_tick_sync", 250, 350, 1, {}],    # overlaps admit_sync
        ["stage2_bucket_dispatch", 360, 380, 1, {"reason": "eager"}],
        ["sched_step", 600, 700, 0, {}],
        ["stage2_bucket_dispatch", 610, 620, 6, {"reason": "full"}],
    ]
    rec.end_ns = 1000
    assert rec.total_s("sched_step") == pytest.approx(500e-9)
    # [200, 350] is covered once though two excluded spans overlap there
    assert rec.self_s("sched_step", exclude=(
        "admit_sync", "finish_tick_sync")) == pytest.approx(350e-9)
    assert rec.self_s("sched_step") == pytest.approx(500e-9)
    assert rec.count("stage2_bucket_dispatch") == 2
    assert rec.count("stage2_bucket_dispatch", reason="eager") == 1
    assert rec.count("nothing") == 0 and rec.self_s("nothing") == 0.0


# ---------------------------------------------------------------------------
# window off: nothing recorded, the shared null context
# ---------------------------------------------------------------------------

def test_step_records_nothing_without_a_window():
    before = observe.last_window()
    n_before = len(before.spans)
    s = ContinuousScheduler(toy_decode_fns(40),
                            ServeConfig(capacity=2, queue_depth=2,
                                        c_thr=0.5),
                            n_slots=3, max_len=_TOY_S + 6,
                            clock=LogicalClock())
    for r in _toy_requests([5, 3, 6]):
        s.submit(r)
    s.run()
    assert observe.last_window() is before
    assert len(before.spans) == n_before
    assert not observe.profiling_active()
    assert observe.annotate("sched_step") is observe.annotate(
        "stage2_bucket_dispatch", reason="eager", take=2)


def test_window_stopped_by_ticks_inside_a_step(tmp_path):
    """``n_ticks`` stops the window from the tick event, inside a step: the
    anchor closes there, the step's span closes after it (clipped to the
    stop), nothing later is recorded, and the record is written."""
    events = EventLog(cap=4096)
    s = ContinuousScheduler(toy_decode_fns(40),
                            ServeConfig(capacity=2, queue_depth=2,
                                        c_thr=0.5),
                            n_slots=3, max_len=_TOY_S + 6,
                            clock=LogicalClock(), events=events)
    for r in _toy_requests([5, 3, 6]):
        s.submit(r)
    with observe.ProfileWindow(str(tmp_path), n_ticks=2,
                               events=events) as win:
        s.run()
    rec = win.record
    assert not observe.profiling_active()
    assert rec.count("finish_tick_sync") == 2
    assert rec.end_ns == rec.spans[0][2]
    assert all(row[2] is not None for row in rec.spans)
    last_step = max(i for i, row in enumerate(rec.spans)
                    if row[0] == "sched_step")
    assert rec.spans[last_step][2] > rec.end_ns      # closed after the stop
    assert rec.total_s("sched_step") <= rec.total_s("profile_window")
    lines = (tmp_path / observe.SPANS_FILE).read_text().splitlines()
    assert len(lines) == len(rec.spans) + len(rec.compiles)


# ---------------------------------------------------------------------------
# a tiny scheduler run inside a window
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def windowed(paged_fns, prompt, tmp_path_factory):
    """The same traffic with the window off, then on; hard share about a
    half so that full, eager and forced buckets all occur."""
    c_thr = 0.6
    off = _sched(paged_fns, c_thr)
    _submit(off, prompt, N_TOKS)
    _steps(off)
    want = _finish(off)

    s = _sched(paged_fns, c_thr)
    chunks = []
    admit = s._admit_chunk

    def spy(reqs):
        chunks.append(len(reqs))
        admit(reqs)
    s._admit_chunk = spy
    _submit(s, prompt, N_TOKS)
    logdir = str(tmp_path_factory.mktemp("window"))
    b0 = s.stats.n_buckets
    with observe.ProfileWindow(logdir) as win:
        _steps(s)
    n_buckets = s.stats.n_buckets - b0
    got = _finish(s)
    return {"want": want, "got": got, "rec": win.record, "logdir": logdir,
            "chunks": chunks, "n_buckets": n_buckets}


def test_window_streams_bitwise_equal(windowed):
    assert windowed["got"] == windowed["want"]
    assert all(None not in v for v in windowed["got"].values())


def test_window_spans_nest_under_sched_step(windowed):
    rec = windowed["rec"]
    assert observe.last_window() is rec
    assert rec.spans[0][0] == "profile_window" and rec.spans[0][3] == -1
    seen = set()
    for i, row in enumerate(rec.spans):
        if row[0] in STEP_SPANS:
            seen.add(row[0])
            assert "sched_step" in _ancestors(rec, i), row
            assert row[1] <= row[2] <= rec.end_ns
    assert {"admit_chunk", "prefill", "admit_sync", "harvest_sync",
            "stage2_bucket_dispatch", "finish_tick_sync",
            "pool_tick_fused"} <= seen
    for i, row in enumerate(rec.spans):
        if row[0] in ("prefill", "admit_sync"):
            assert rec.spans[row[3]][0] == "admit_chunk"


def test_window_bucket_reasons_sum_to_buckets(windowed):
    rec = windowed["rec"]
    n = rec.count("stage2_bucket_dispatch")
    assert n == windowed["n_buckets"] > 0
    by_reason = {r: rec.count("stage2_bucket_dispatch", reason=r)
                 for r in REASONS}
    assert sum(by_reason.values()) == n, by_reason
    assert by_reason["full"] > 0 and by_reason["eager"] > 0
    takes = [row[4]["take"] for row in rec.spans
             if row[0] == "stage2_bucket_dispatch"]
    assert all(1 <= t <= 2 for t in takes)


def test_backpressure_and_forced_buckets_named(paged_fns, prompt, tmp_path):
    """All-hard traffic on 6 slots through a ring of 4 rows with the
    eager rule off: the ring fills inside a tick (``backpressure``) and a
    step finds every busy slot parked (``forced``); the reasons still sum
    to the buckets dispatched."""
    s = _sched(paged_fns, c_thr=1.1, n_slots=6, eager_drain_below=0)
    _submit(s, prompt, [5, 4, 3, 5, 2, 5])
    b0 = s.stats.n_buckets
    with observe.ProfileWindow(str(tmp_path)) as win:
        _steps(s)
    rec = win.record
    by_reason = {r: rec.count("stage2_bucket_dispatch", reason=r)
                 for r in REASONS}
    assert by_reason["backpressure"] > 0 and by_reason["forced"] > 0
    assert sum(by_reason.values()) == s.stats.n_buckets - b0
    assert s.stats.n_stalls == by_reason["backpressure"]


def test_window_admit_chunks_counted(windowed):
    rec = windowed["rec"]
    assert rec.count("admit_chunk") == len(windowed["chunks"]) > 1
    assert sum(row[4]["n"] for row in rec.spans
               if row[0] == "admit_chunk") == len(N_TOKS)
    assert rec.count("sched_step") > 0
    assert 0.0 < rec.self_s("sched_step", exclude=(
        "finish_tick_sync", "admit_sync", "harvest_sync")) \
        < rec.total_s("sched_step") <= rec.total_s("profile_window")


def test_window_writes_program_spans(windowed):
    path = f"{windowed['logdir']}/{observe.SPANS_FILE}"
    lines = [json.loads(x) for x in open(path).read().splitlines()]
    spans = [x for x in lines if x["kind"] == "span"]
    assert len(spans) == len(windowed["rec"].spans)
    assert spans[0]["name"] == "profile_window" and spans[0]["t0"] == 0.0
    assert set(lines[0]) == {"kind", "name", "sid", "replica", "t0", "t1",
                             "args"}


def test_window_record_on_the_trace_clock(windowed):
    """Each span's offset from the anchor in the record agrees with its
    offset in the window's xplane within 0.5 ms."""
    from jax.profiler import ProfileData
    rec = windowed["rec"]
    paths = sorted(glob.glob(f"{windowed['logdir']}/**/*.xplane.pb",
                             recursive=True))
    data = ProfileData.from_file(paths[-1])
    names = {row[0] for row in rec.spans}
    events = {}
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in names:
                    events.setdefault(ev.name, []).append(
                        (ev.start_ns, ev.end_ns))
    for v in events.values():
        v.sort()
    anchor = events["profile_window"][0][0]
    by_name = {}
    for row in rec.spans:
        by_name.setdefault(row[0], []).append(row)
    base = rec.spans[0][1]
    n = 0
    for name, rows in by_name.items():
        assert len(events[name]) == len(rows), name
        for row, (s, e) in zip(rows, events[name]):
            assert abs((row[1] - base) - (s - anchor)) < 5e5, name
            assert abs((row[2] - base) - (e - anchor)) < 5e5, name
            n += 1
    assert n == len(rec.spans) > 10


# ---------------------------------------------------------------------------
# compiles by program
# ---------------------------------------------------------------------------

def test_compile_in_window_named_by_program(tmp_path):
    @jax.jit
    def spans_probe_program(x):
        return x * 2 + 1

    spans_probe_program(jnp.ones(3))          # this shape compiles first
    n0 = observe.backend_compiles()["spans_probe_program"][0]
    with observe.ProfileWindow(str(tmp_path)) as win:
        spans_probe_program(jnp.ones(5))      # a new shape: a new compile
    progs = [p for p, _secs, _t in win.record.compiles]
    assert "spans_probe_program" in progs
    n, secs = observe.backend_compiles()["spans_probe_program"]
    assert n == n0 + 1 and secs > 0
    reg = observe.MetricsRegistry()
    observe.StatsSampler(reg).sample()
    got = observe.parse_exposition(reg.exposition())
    assert got['repro_backend_compiles_total{program="spans_probe_program"}'] \
        == n


# ---------------------------------------------------------------------------
# the module names the benchmark's trace readers match
# ---------------------------------------------------------------------------

def _abstract(tree):
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                        tree)


def test_program_module_names_pinned(paged_fns, prompt):
    seen = {}

    def spy(name, fn):
        def call(*a):
            seen.setdefault(name, _abstract(a))
            return fn(*a)
        return call
    fns = paged_fns._replace(
        admit_pages=spy("admit_pages", paged_fns.admit_pages),
        s2_paged=spy("s2_paged", paged_fns.s2_paged))
    s = _sched(fns, c_thr=1.1, n_slots=2)        # every token to stage 2
    _submit(s, prompt, [3, 3])
    s.step()
    s.step()
    assert {"admit_pages", "s2_paged"} <= set(seen)

    def module(lowered):
        return lowered.as_text().splitlines()[0].split()[1]
    pf = paged_fns.prefill
    assert module(pf.func.lower(*pf.args, jnp.asarray(prompt[:2]),
                                max_len=24)) == "@jit_pf"
    assert module(paged_fns.admit_pages.lower(*seen["admit_pages"])) == \
        "@jit_admit_pages"
    s2 = paged_fns.s2_paged
    assert module(s2.func.lower(*s2.args, *seen["s2_paged"])) == \
        "@jit_s2_paged"
    lanes = (s._tok, s._c1, s._pos, s._active_lane, s._start_lane,
             s._budget_lane)
    tick = SCH._pool_tick_fused.lower(
        *lanes, s.ring.ensure(s._ring_row_spec), s._rows, s.c_thr,
        s1=SCH._tick_fn(s.fns.s1_raw), backend=dispatch.kernel_backend())
    assert module(tick) == "@jit__pool_tick_fused"
