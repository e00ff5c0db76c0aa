"""Observability plane for the early-exit serving fleet.

Three layers, all zero-cost when unused:

1. **Request-span tracing** — :class:`Tracer` subscribes to the scheduler /
   router / fault ``EventLog`` feeds and assembles per-request span trees
   (submit -> queue-wait -> admit -> decode -> stage-2 park episodes ->
   finish, with route/preempt/migrate/fault instants as annotations).
   Export as JSONL (one span or annotation per line) or as Chrome
   ``trace_event`` JSON so a whole fleet run opens in Perfetto /
   ``chrome://tracing``.

2. **Metrics export** — :class:`MetricsRegistry` with a FROZEN name+label
   schema (:data:`METRICS_SCHEMA`, key set locked in tests like the
   ServeStats v3 dict), fed by :class:`StatsSampler` over ``ServeStats`` /
   ``FleetStats`` plus kernel-backend resolutions and backend compiles
   by program (one ``jax.monitoring`` listener).
   Prometheus text exposition via :func:`MetricsRegistry.exposition`, a
   zero-dependency stdlib HTTP endpoint (:class:`MetricsServer`) and a
   one-shot :func:`dump_metrics` file mode.

3. **Profiler hooks** — :func:`annotate` wraps host-side hot sections in
   ``jax.profiler.TraceAnnotation`` (only while a :class:`ProfileWindow`
   is active; a shared nullcontext otherwise), and :class:`ProfileWindow`
   opens an opt-in ``jax.profiler`` trace capture for N scheduler ticks
   so TPU runs produce attributable xprof timelines. While a window is
   open each annotated section is also a row of the window's
   :class:`WindowRecord` (:func:`last_window`), which maps onto the trace
   through its ``profile_window`` anchor span and is written beside the
   trace as ``program_spans.jsonl``. The jitted bodies themselves carry
   ``jax.named_scope`` labels (trace-time metadata, zero runtime cost).

The tracing layer never touches device values: it rides the host-side
event feed the scheduler already maintains, so token streams are bitwise
unchanged with observability on, and the overhead gate in
``benchmarks/serve_observed.py`` holds goodput at >= 0.95x unobserved.
"""

from __future__ import annotations

import contextlib
import json
import os
import threading
import time
from typing import Dict, List, Optional, Tuple

import jax

__all__ = [
    "Tracer", "MetricsRegistry", "StatsSampler", "MetricsServer",
    "ProfileWindow", "METRICS_SCHEMA", "annotate", "profiling_active",
    "parse_exposition", "dump_metrics", "export_events_jsonl",
    "WindowRecord", "last_window", "backend_compiles",
]


# ---------------------------------------------------------------------------
# Layer 1: request-span tracing
# ---------------------------------------------------------------------------

# Synthetic Chrome "thread" ids for non-request tracks.
_TID_SCHED = 1_000_000   # scheduler tick / bucket track
_TID_CTRL = 1_000_001    # migration / fault / router control track


_EV_STRIP = ("seq", "t", "event", "sid")


def _ev_args(ev: dict) -> dict:
    """An event's payload fields (everything but the envelope keys)."""
    return {k: v for k, v in ev.items() if k not in _EV_STRIP}


class Tracer:
    """Assembles per-request span trees from ``EventLog`` feeds.

    Attach to any number of scheduler and router feeds; events arrive
    synchronously (the ``EventLog`` subscriber contract) so assembly is
    single-threaded with the emitter. Spans close in wall-clock time
    (``ev["t"]``); the scheduler's logical clock never leaks into traces.

    Span kinds per request ``sid``:

    - ``request``    submit -> finish (the root; exactly one per sid)
    - ``queue_wait`` submit -> admit
    - ``decode``     admit -> finish
    - ``stage2_wait`` park -> bucket dispatch (zero or more episodes)

    Annotations (instants): ``route``, ``preempt``, ``requeue``, ``tick``,
    ``bucket``, ``migrate_*``, ``inject``/``retry`` fault events, and any
    unrecognized tag (kept, never dropped, so feeds stay lossless).
    """

    def __init__(self):
        # Hot-path storage is tuples referencing the ALREADY-allocated event
        # dicts, not fresh per-span dicts: the assembly callback runs inside
        # the scheduler's emit, and every container allocated there feeds
        # gc generations that then rescan the whole retained trace during
        # the serving run. Dict views materialize lazily via the ``spans`` /
        # ``annotations`` properties (export time, off the hot path).
        self._span_rows: List[tuple] = []      # (name, sid, t0, t1, rep, pay)
        self._ann_rows: List[tuple] = []       # (name, sid, t, rep, tid, ev)
        self._open: Dict[object, dict] = {}    # sid -> open-state record
        self._done: set = set()                # sids with closed roots
        self._orphans: set = set()             # events for never-submitted sids
        self._feeds: List[tuple] = []          # (log, callback)
        self._lock = threading.Lock()

    # -- feed attachment ----------------------------------------------------

    def attach(self, log, *, replica: int = 0):
        """Subscribe to an ``EventLog``; events are labeled ``replica``."""
        cb = lambda ev, _r=replica: self.on_event(ev, _r)  # noqa: E731
        log.subscribe(cb)
        self._feeds.append((log, cb))
        return self

    def attach_scheduler(self, sched, *, replica: int = 0):
        """Attach a scheduler's event feed (requires ``events=`` wiring)."""
        if getattr(sched, "events", None) is None:
            raise ValueError("scheduler has no event feed: build it with "
                             "events=EventLog(...) to trace it")
        return self.attach(sched.events, replica=replica)

    def attach_router(self, router, *, replica: int = -1):
        """Attach a ``FleetRouter``'s feed (route/preempt instants; the
        router's submit seeds the root span before any replica sees it)."""
        return self.attach(router.events, replica=replica)

    def attach_faults(self, log=None, *, replica: int = -1):
        """Attach the fault-injection log (``faults.LOG`` by default)."""
        if log is None:
            from repro.runtime import faults
            log = faults.LOG
        return self.attach(log, replica=replica)

    def close(self) -> None:
        """Unsubscribe from every attached feed."""
        for log, cb in self._feeds:
            try:
                log.unsubscribe(cb)
            except ValueError:
                pass
        self._feeds = []

    # -- assembly -----------------------------------------------------------

    def on_event(self, ev: dict, replica: int = 0) -> None:
        with self._lock:
            self._on_event(ev, replica)

    def _on_event(self, ev: dict, replica: int) -> None:
        tag = ev.get("event")
        t = ev["t"]
        sid = ev.get("sid")
        if tag == "submit":
            st = self._open.get(sid)
            if st is None and sid not in self._done:
                # Router and scheduler both emit submit; first one wins so
                # the root covers the full fleet-level lifetime.
                self._open[sid] = {"t_submit": t, "t_admit": None,
                                   "t_park": None, "replica": replica,
                                   "parks": 0, "ev": ev}
            return
        if tag == "admit":
            st = self._need(sid, t, replica)
            if st is None:
                return
            st["replica"] = replica
            if st["t_admit"] is None:
                self._span_rows.append(
                    ("queue_wait", sid, st["t_submit"], t, replica, None))
                st["t_admit"] = t
                st["slot"] = ev.get("slot")
            return
        if tag == "park":
            # batched: one event per tick carrying every newly parked sid
            for s in ev.get("sids", () if sid is None else (sid,)):
                st = self._need(s, t, replica)
                if st is not None and st["t_park"] is None:
                    st["t_park"] = t
            return
        if tag == "bucket":
            for s in ev.get("sids", ()):
                st = self._open.get(s)
                if st is not None and st["t_park"] is not None:
                    self._span_rows.append(
                        ("stage2_wait", s, st["t_park"], t, st["replica"],
                         ev.get("take")))
                    st["t_park"] = None
                    st["parks"] += 1
            self._ann_rows.append(("bucket", None, t, replica, _TID_SCHED,
                                   ev))
            return
        if tag == "finish":
            st = self._need(sid, t, replica)
            if st is None:
                return
            if st["t_park"] is not None:    # parked at finish: close episode
                self._span_rows.append(
                    ("stage2_wait", sid, st["t_park"], t, st["replica"],
                     None))
                st["parks"] += 1
            t_admit = st["t_admit"] if st["t_admit"] is not None else t
            self._span_rows.append(
                ("decode", sid, t_admit, t, st["replica"], st["parks"]))
            self._span_rows.append(
                ("request", sid, st["t_submit"], t, st["replica"],
                 (st["ev"], ev)))
            del self._open[sid]
            self._done.add(sid)
            return
        if tag == "tick":
            self._ann_rows.append(("tick", None, t, replica, _TID_SCHED, ev))
            return
        # route / preempt / requeue / degrade / restore / migrate_* /
        # inject / retry / anything future: keep as an annotation.
        self._ann_rows.append(
            (tag, sid, t, replica, _TID_CTRL if sid is None else None, ev))

    def _need(self, sid, t, replica) -> Optional[dict]:
        st = self._open.get(sid)
        if st is None:
            if sid not in self._done:
                self._orphans.add(sid)
            return None
        return st

    # -- materialized views (export time, off the hot path) -----------------

    @property
    def spans(self) -> List[dict]:
        out = []
        for name, sid, t0, t1, replica, payload in self._span_rows:
            if name == "request":
                sub_ev, fin_ev = payload
                args = _ev_args(sub_ev)
                for k in ("n_decisions", "n_hard"):
                    if k in fin_ev:
                        args[k] = fin_ev[k]
            elif name == "decode":
                args = {"n_parks": payload}
            elif name == "stage2_wait" and payload is not None:
                args = {"take": payload}
            else:
                args = {}
            out.append({"kind": "span", "name": name, "sid": sid,
                        "replica": replica, "t0": t0, "t1": t1, "args": args})
        return out

    @property
    def annotations(self) -> List[dict]:
        return [{"kind": "instant", "name": name, "sid": sid,
                 "replica": replica, "t": t, "tid": tid,
                 "args": _ev_args(ev)}
                for name, sid, t, replica, tid, ev in self._ann_rows]

    # -- completeness -------------------------------------------------------

    def finished_sids(self) -> set:
        return set(self._done)

    def open_sids(self) -> set:
        return set(self._open)

    def orphan_sids(self) -> set:
        return set(self._orphans)

    def completeness(self, expect_sids=None) -> dict:
        """Structural audit of the assembled trees.

        Every finished request must have exactly one ``request`` root, all
        its other spans nested inside the root interval, no orphan events,
        and (when ``expect_sids`` is given) cover exactly that id set.
        """
        roots: Dict[object, List[tuple]] = {}
        children: Dict[object, List[tuple]] = {}
        for row in self._span_rows:
            (roots if row[0] == "request" else children).setdefault(
                row[1], []).append(row)
        bad_roots = sorted(str(s) for s, r in roots.items() if len(r) != 1)
        missing = sorted(str(s) for s in self._done if s not in roots)
        nested = True
        for sid, kids in children.items():
            r = roots.get(sid)
            if r is None:
                nested = False
                continue
            lo, hi = r[0][2], r[0][3]
            for k in kids:
                if not (lo <= k[2] <= k[3] <= hi):
                    nested = False
        uncovered = []
        if expect_sids is not None:
            uncovered = sorted(str(s) for s in expect_sids
                               if s not in self._done)
        complete = (not bad_roots and not missing and nested
                    and not self._orphans and not self._open
                    and not uncovered)
        return {"complete": complete, "n_finished": len(self._done),
                "n_spans": len(self._span_rows),
                "n_annotations": len(self._ann_rows),
                "open": sorted(str(s) for s in self._open),
                "orphans": sorted(str(s) for s in self._orphans),
                "bad_roots": bad_roots, "missing_roots": missing,
                "nested": nested, "uncovered": uncovered}

    def complete(self, expect_sids=None) -> bool:
        return self.completeness(expect_sids)["complete"]

    # -- export -------------------------------------------------------------

    def export_jsonl(self, path: str) -> int:
        """One JSON object per line: all spans then all annotations."""
        n = 0
        with open(path, "w") as f:
            for rec in self.spans + self.annotations:
                f.write(json.dumps(rec, default=str) + "\n")
                n += 1
        return n

    def chrome_trace(self) -> dict:
        """Chrome ``trace_event`` JSON (Perfetto / chrome://tracing).

        pid = replica, tid = request sid (hashed to an int when needed),
        ``ph: "X"`` complete events with microsecond timestamps rebased to
        the first event so coordinates stay small.
        """
        spans, anns = self.spans, self.annotations
        events: List[dict] = []
        t_base = min([s["t0"] for s in spans]
                     + [a["t"] for a in anns], default=0.0)
        pids = set()

        def tid_of(sid):
            if sid is None:
                return _TID_CTRL
            if isinstance(sid, int):
                return sid
            return hash(str(sid)) % 900_000

        for s in spans:
            pid = int(s["replica"])
            pids.add(pid)
            events.append({
                "name": s["name"], "cat": "request", "ph": "X",
                "ts": (s["t0"] - t_base) * 1e6,
                "dur": max((s["t1"] - s["t0"]) * 1e6, 0.0),
                "pid": pid, "tid": tid_of(s["sid"]),
                "args": {"sid": str(s["sid"]), **s["args"]},
            })
        for a in anns:
            pid = int(a["replica"])
            pids.add(pid)
            events.append({
                "name": a["name"], "cat": "annotation", "ph": "i", "s": "p",
                "ts": (a["t"] - t_base) * 1e6, "pid": pid,
                "tid": a["tid"] if a["tid"] is not None else tid_of(a["sid"]),
                "args": {k: str(v) for k, v in a["args"].items()},
            })
        for pid in sorted(pids):
            name = "router" if pid < 0 else f"replica{pid}"
            events.append({"name": "process_name", "ph": "M", "pid": pid,
                           "tid": 0, "args": {"name": name}})
            events.append({"name": "thread_name", "ph": "M", "pid": pid,
                           "tid": _TID_SCHED, "args": {"name": "scheduler"}})
            events.append({"name": "thread_name", "ph": "M", "pid": pid,
                           "tid": _TID_CTRL, "args": {"name": "control"}})
        return {"traceEvents": events, "displayTimeUnit": "ms"}

    def export_chrome_trace(self, path: str) -> int:
        trace = self.chrome_trace()
        with open(path, "w") as f:
            json.dump(trace, f)
        return len(trace["traceEvents"])


def export_events_jsonl(path: str, log, **extra) -> int:
    """Append an ``EventLog``'s retained events to ``path`` as JSONL.

    The shared exporter behind ``faults.flush_log`` and ``--spans-out``
    style dumps: every line is ``{**extra, **event}``. Returns the number
    of lines written. Does NOT clear the log (callers own that)."""
    events = log.as_list()
    if not events:
        return 0
    with open(path, "a") as f:
        for ev in events:
            f.write(json.dumps({**extra, **ev}, default=str) + "\n")
    return len(events)


# ---------------------------------------------------------------------------
# Layer 2: metrics registry + Prometheus exposition
# ---------------------------------------------------------------------------

# FROZEN schema: (name, kind, label names, help). Adding/renaming entries
# requires updating the frozen key-set test in tests/test_observe.py —
# exactly like the ServeStats v3 dict. kind: c=counter g=gauge h=histogram.
METRICS_SCHEMA: Tuple[Tuple[str, str, Tuple[str, ...], str], ...] = (
    ("repro_requests_submitted_total", "c", ("replica",),
     "Requests accepted into a scheduler queue"),
    ("repro_requests_finished_total", "c", ("replica",),
     "Requests fully decoded"),
    ("repro_decisions_total", "c", ("replica",),
     "Exit decisions taken (stage-1 steps)"),
    ("repro_exited_total", "c", ("replica",),
     "Decisions that exited early at stage 1"),
    ("repro_stage2_total", "c", ("replica",),
     "Decisions escalated to stage 2"),
    ("repro_stalls_total", "c", ("replica",),
     "Ring-full backpressure stalls"),
    ("repro_buckets_total", "c", ("replica",),
     "Stage-2 bucket dispatches"),
    ("repro_ring_bytes_moved_total", "c", ("replica",),
     "Bytes moved through the inter-stage ring"),
    ("repro_migrations_total", "c", ("replica",),
     "Completed live migrations"),
    ("repro_migration_rollbacks_total", "c", ("replica",),
     "Live migrations rolled back"),
    ("repro_realized_q", "g", ("replica",),
     "Realized hard fraction q (lifetime)"),
    ("repro_realized_q_ewma", "g", ("replica",),
     "Realized q, exponentially weighted"),
    ("repro_q_drift", "g", ("replica",),
     "realized_q_ewma - provisioned p"),
    ("repro_stage1_occupancy", "g", ("replica",),
     "Busy slot fraction of the stage-1 pool"),
    ("repro_stage2_occupancy", "g", ("replica",),
     "Parked-lane fraction of stage-2 capacity"),
    ("repro_mean_bucket_fill", "g", ("replica",),
     "Mean stage-2 bucket fill fraction"),
    ("repro_slots_busy", "g", ("replica",),
     "Busy decode slots"),
    ("repro_queue_depth", "g", ("replica",),
     "Requests waiting for admission"),
    ("repro_cache_pages_total", "g", ("replica",),
     "Allocatable KV pages in the paged pool"),
    ("repro_cache_pages_in_use", "g", ("replica",),
     "KV pages currently allocated"),
    ("repro_cache_pages_in_use_peak", "g", ("replica",),
     "High-water mark of allocated KV pages (page-pool watermark)"),
    ("repro_cache_hbm_bytes", "g", ("replica",),
     "Bytes resident in the stage-2 KV store"),
    ("repro_page_fragmentation", "g", ("replica",),
     "Allocated-but-unused tail fraction of in-use pages"),
    ("repro_events_dropped_total", "c", ("feed",),
     "EventLog events lost to the cap (FIFO overwrite)"),
    ("repro_routed_total", "c", ("policy",),
     "Router placements by policy"),
    ("repro_preemptions_total", "c", (),
     "Queued-request preemptions (requeue-never-drop)"),
    ("repro_fleet_pending", "g", (),
     "Router-level pending requests"),
    ("repro_backend_resolutions_total", "c", (),
     "kernel_backend() memo misses (fresh resolutions)"),
    ("repro_backend_compiles_total", "c", ("program",),
     "Backend compiles by jitted program (a climbing count after warm-up "
     "is a retrace)"),
    ("repro_request_latency_seconds", "h", ("replica",),
     "Submit-to-finish latency (scheduler clock)"),
)

_BUCKETS = (0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
            0.25, 0.5, 1.0, 2.5, 5.0, 10.0)
_KINDS = {"c": "counter", "g": "gauge", "h": "histogram"}


def _fmt(v: float) -> str:
    if v == float("inf"):
        return "+Inf"
    f = float(v)
    return repr(int(f)) if f == int(f) and abs(f) < 1e15 else repr(f)


class _Metric:
    def __init__(self, name: str, kind: str, labels: Tuple[str, ...],
                 help_: str):
        self.name, self.kind, self.labels, self.help = name, kind, labels, help_
        self.series: Dict[Tuple[str, ...], object] = {}

    def _key(self, labelvals: Dict[str, str]) -> Tuple[str, ...]:
        if set(labelvals) != set(self.labels):
            raise ValueError(
                f"{self.name}: labels must be exactly {self.labels}, "
                f"got {tuple(sorted(labelvals))}")
        return tuple(str(labelvals[k]) for k in self.labels)

    def _labelstr(self, key: Tuple[str, ...]) -> str:
        if not self.labels:
            return ""
        inner = ",".join(f'{k}="{v}"' for k, v in zip(self.labels, key))
        return "{" + inner + "}"

    # counters -------------------------------------------------------------
    def inc(self, amount: float = 1.0, **labels) -> None:
        assert self.kind == "c", self.name
        k = self._key(labels)
        self.series[k] = self.series.get(k, 0.0) + amount

    def set_total(self, value: float, **labels) -> None:
        """Set a sampled monotone total (counters fed from lifetime
        sources like ``ServeStats`` rather than discrete increments)."""
        assert self.kind == "c", self.name
        k = self._key(labels)
        self.series[k] = max(float(value), float(self.series.get(k, 0.0)))

    # gauges ---------------------------------------------------------------
    def set(self, value: float, **labels) -> None:
        assert self.kind == "g", self.name
        self.series[self._key(labels)] = float(value)

    def set_max(self, value: float, **labels) -> None:
        """High-water gauge: keeps the max ever observed."""
        assert self.kind == "g", self.name
        k = self._key(labels)
        self.series[k] = max(float(value), float(self.series.get(k, value)))

    # histograms -----------------------------------------------------------
    def observe(self, value: float, **labels) -> None:
        assert self.kind == "h", self.name
        k = self._key(labels)
        st = self.series.get(k)
        if st is None:
            st = {"buckets": [0] * len(_BUCKETS), "sum": 0.0, "count": 0}
            self.series[k] = st
        v = float(value)
        for i, le in enumerate(_BUCKETS):
            if v <= le:
                st["buckets"][i] += 1
        st["sum"] += v
        st["count"] += 1

    def value(self, **labels) -> float:
        return self.series.get(self._key(labels), 0.0)

    # exposition -----------------------------------------------------------
    def render(self) -> List[str]:
        lines = [f"# HELP {self.name} {self.help}",
                 f"# TYPE {self.name} {_KINDS[self.kind]}"]
        if self.kind == "h":
            for key in sorted(self.series):
                st = self.series[key]
                base = self._labelstr(key)

                def lab(le_s, _b=base):
                    return (_b[:-1] + f',le="{le_s}"}}') if _b \
                        else f'{{le="{le_s}"}}'
                # observe() increments every bucket with v <= le, so the
                # stored counts are already cumulative as Prometheus wants.
                for le, n in zip(_BUCKETS, st["buckets"]):
                    lines.append(f"{self.name}_bucket{lab(_fmt(le))} {n}")
                lines.append(
                    f"{self.name}_bucket{lab('+Inf')} {st['count']}")
                lines.append(f"{self.name}_sum{base} {_fmt(st['sum'])}")
                lines.append(f"{self.name}_count{base} {st['count']}")
        else:
            for key in sorted(self.series):
                lines.append(f"{self.name}{self._labelstr(key)} "
                             f"{_fmt(self.series[key])}")
        return lines


class MetricsRegistry:
    """Closed registry: every metric comes from :data:`METRICS_SCHEMA`.

    Unknown names raise — the exported surface is a frozen contract, like
    the ``ServeStats`` dict key set, so dashboards never silently break."""

    def __init__(self):
        self._metrics: Dict[str, _Metric] = {
            name: _Metric(name, kind, labels, help_)
            for name, kind, labels, help_ in METRICS_SCHEMA}
        self._lock = threading.Lock()

    def get(self, name: str) -> _Metric:
        m = self._metrics.get(name)
        if m is None:
            raise KeyError(f"unknown metric {name!r}: the schema is frozen "
                           f"(see observe.METRICS_SCHEMA)")
        return m

    def __iter__(self):
        return iter(self._metrics.values())

    def exposition(self) -> str:
        with self._lock:
            lines: List[str] = []
            for m in self._metrics.values():
                lines.extend(m.render())
        return "\n".join(lines) + "\n"


def parse_exposition(text: str) -> Dict[str, float]:
    """Parse Prometheus text exposition into ``{name{labels}: value}``.

    Strict enough for the CI smoke: raises ``ValueError`` on any
    non-comment line that is not ``name[{labels}] value``."""
    out: Dict[str, float] = {}
    for lineno, line in enumerate(text.splitlines(), 1):
        line = line.strip()
        if not line or line.startswith("#"):
            continue
        try:
            key, val = line.rsplit(None, 1)
        except ValueError:
            raise ValueError(f"line {lineno}: malformed sample {line!r}")
        name = key.split("{", 1)[0]
        if not name.replace("_", "").replace(":", "").isalnum():
            raise ValueError(f"line {lineno}: bad metric name {name!r}")
        if "{" in key and not key.endswith("}"):
            raise ValueError(f"line {lineno}: unterminated labels {key!r}")
        out[key] = float(val)
    if not out:
        raise ValueError("no samples in exposition")
    return out


# ---------------------------------------------------------------------------
# Cadence sampler over ServeStats / FleetStats + compile counters
# ---------------------------------------------------------------------------

class StatsSampler:
    """Pull-model bridge from live serving objects into the registry.

    ``sample()`` reads every attached source once; ``maybe_sample()``
    honors ``cadence_s`` and is cheap enough to ride the scheduler's
    per-tick event feed (attachment does that automatically when the
    scheduler has an event log)."""

    def __init__(self, registry: MetricsRegistry, cadence_s: float = 0.25):
        self.registry = registry
        self.cadence_s = float(cadence_s)
        self._scheds: List[tuple] = []     # (sched, replica_label)
        self._routers: List[object] = []
        self._logs: List[tuple] = []       # (feed_label, EventLog)
        self._subs: List[tuple] = []       # (log, cb) for detach
        self._last = 0.0
        self.n_samples = 0

    # -- attachment ---------------------------------------------------------

    def attach_scheduler(self, sched, *, replica: int = 0):
        self._scheds.append((sched, str(replica)))
        ev = getattr(sched, "events", None)
        if ev is not None:
            self._logs.append((f"sched{replica}", ev))
            cb = lambda _ev: self.maybe_sample()  # noqa: E731
            ev.subscribe(cb)
            self._subs.append((ev, cb))
        return self

    def attach_router(self, router):
        self._routers.append(router)
        self._logs.append(("router", router.events))
        reg = self.registry
        routed = reg.get("repro_routed_total")
        preempt = reg.get("repro_preemptions_total")

        def cb(ev):
            tag = ev.get("event")
            if tag == "route":
                routed.inc(policy=ev.get("policy", "unknown"))
            elif tag == "preempt":
                preempt.inc()
        router.events.subscribe(cb)
        self._subs.append((router.events, cb))
        return self

    def attach_log(self, label: str, log):
        self._logs.append((label, log))
        return self

    def close(self) -> None:
        for log, cb in self._subs:
            try:
                log.unsubscribe(cb)
            except ValueError:
                pass
        self._subs = []

    # -- sampling -----------------------------------------------------------

    def maybe_sample(self) -> bool:
        now = time.monotonic()
        if now - self._last < self.cadence_s:
            return False
        self.sample()
        return True

    def sample(self) -> None:
        self._last = time.monotonic()
        self.n_samples += 1
        reg = self.registry
        for sched, rep in self._scheds:
            self._sample_sched(reg, sched, rep)
        for router in self._routers:
            reg.get("repro_fleet_pending").set(len(router._pending))
        for label, log in self._logs:
            reg.get("repro_events_dropped_total").set_total(
                log.n_dropped, feed=label)
        from repro.kernels import dispatch as _dispatch
        reg.get("repro_backend_resolutions_total").set_total(
            _dispatch.n_backend_resolutions())
        compiles = reg.get("repro_backend_compiles_total")
        for prog, (n, _secs) in backend_compiles().items():
            compiles.set_total(n, program=prog)

    def _sample_sched(self, reg, sched, rep) -> None:
        st = getattr(sched, "stats", None)
        if st is None:
            return
        reg.get("repro_requests_submitted_total").set_total(
            st.n_finished + len(st.submit_times), replica=rep)
        reg.get("repro_requests_finished_total").set_total(
            st.n_finished, replica=rep)
        reg.get("repro_decisions_total").set_total(
            st.n_decisions, replica=rep)
        reg.get("repro_stage2_total").set_total(st.n_stage2, replica=rep)
        reg.get("repro_exited_total").set_total(st.n_exited, replica=rep)
        reg.get("repro_stalls_total").set_total(st.n_stalls, replica=rep)
        reg.get("repro_buckets_total").set_total(st.n_buckets, replica=rep)
        reg.get("repro_ring_bytes_moved_total").set_total(
            st.ring_bytes_moved, replica=rep)
        reg.get("repro_migrations_total").set_total(
            st.n_migrations, replica=rep)
        reg.get("repro_migration_rollbacks_total").set_total(
            st.n_migration_rollbacks, replica=rep)
        reg.get("repro_realized_q").set(st.realized_q, replica=rep)
        reg.get("repro_realized_q_ewma").set(st.realized_q_ewma, replica=rep)
        reg.get("repro_q_drift").set(st.q_drift, replica=rep)
        reg.get("repro_stage1_occupancy").set(
            st.stage1_occupancy, replica=rep)
        reg.get("repro_stage2_occupancy").set(
            st.stage2_occupancy, replica=rep)
        reg.get("repro_mean_bucket_fill").set(st.mean_bucket_fill,
                                              replica=rep)
        reg.get("repro_cache_pages_total").set(st.cache_pages_total,
                                               replica=rep)
        reg.get("repro_cache_pages_in_use").set(st.cache_pages_in_use,
                                                replica=rep)
        reg.get("repro_cache_pages_in_use_peak").set_max(
            st.cache_pages_in_use, replica=rep)
        reg.get("repro_cache_hbm_bytes").set(st.cache_hbm_bytes, replica=rep)
        reg.get("repro_page_fragmentation").set(st.page_fragmentation,
                                                replica=rep)
        qd = getattr(sched, "queue", None)
        if qd is not None:
            reg.get("repro_queue_depth").set(len(qd), replica=rep)
        busy = getattr(sched, "n_busy", None)
        if busy is not None:
            reg.get("repro_slots_busy").set(
                busy() if callable(busy) else busy, replica=rep)
        # Latency histogram: feed only the tail that arrived since the
        # previous sample (the deque is bounded; n_finished is lifetime).
        key = id(sched)
        seen = getattr(self, "_lat_seen", None)
        if seen is None:
            seen = self._lat_seen = {}
        prev = seen.get(key, 0)
        lat = st.latencies
        new = st.n_finished - prev
        if new > 0:
            hist = reg.get("repro_request_latency_seconds")
            for v in list(lat)[-min(new, len(lat)):]:
                hist.observe(v, replica=rep)
            seen[key] = st.n_finished


# ---------------------------------------------------------------------------
# Zero-dependency HTTP exposition + one-shot dump
# ---------------------------------------------------------------------------

class MetricsServer:
    """``/metrics`` over stdlib ``http.server`` in a daemon thread.

    ``port=0`` binds an ephemeral port; read ``.port`` after ``start()``.
    Each scrape pulls a fresh ``sampler.sample()`` first (pull-model), so
    an idle scheduler still exposes its latest state."""

    def __init__(self, registry: MetricsRegistry,
                 sampler: Optional[StatsSampler] = None,
                 host: str = "127.0.0.1", port: int = 0):
        self.registry, self.sampler = registry, sampler
        self._host, self._port_req = host, port
        self._httpd = None
        self._thread = None

    @property
    def port(self) -> int:
        if self._httpd is None:
            raise RuntimeError("server not started")
        return self._httpd.server_address[1]

    def start(self) -> "MetricsServer":
        import http.server

        registry, sampler = self.registry, self.sampler

        class Handler(http.server.BaseHTTPRequestHandler):
            def do_GET(self):  # noqa: N802 (stdlib API)
                if self.path.split("?")[0] not in ("/metrics", "/"):
                    self.send_error(404)
                    return
                try:
                    if sampler is not None:
                        sampler.sample()
                    body = registry.exposition().encode()
                except Exception as e:  # surface, never hang the scraper
                    self.send_error(500, str(e))
                    return
                self.send_response(200)
                self.send_header("Content-Type",
                                 "text/plain; version=0.0.4; charset=utf-8")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def log_message(self, *a):  # silence per-request stderr noise
                pass

        self._httpd = http.server.ThreadingHTTPServer(
            (self._host, self._port_req), Handler)
        self._httpd.daemon_threads = True
        self._thread = threading.Thread(
            target=self._httpd.serve_forever, name="repro-metrics",
            daemon=True)
        self._thread.start()
        return self

    def stop(self) -> None:
        if self._httpd is not None:
            self._httpd.shutdown()
            self._httpd.server_close()
            self._thread.join(timeout=5)
            self._httpd = self._thread = None

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()


def dump_metrics(registry: MetricsRegistry, path: str,
                 sampler: Optional[StatsSampler] = None) -> str:
    """One-shot exposition to a file (the ``--metrics-dump`` mode)."""
    if sampler is not None:
        sampler.sample()
    text = registry.exposition()
    with open(path, "w") as f:
        f.write(text)
    return text


# ---------------------------------------------------------------------------
# Layer 3: profiler hooks + the window record + compiles by program
# ---------------------------------------------------------------------------

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
WINDOW_ANCHOR = "profile_window"
SPANS_FILE = "program_spans.jsonl"


class WindowRecord:
    """What the program's :func:`annotate` spans and backend compiles
    captured while one :class:`ProfileWindow` was open, stamped with
    ``time.perf_counter_ns()``.

    ``spans`` rows are ``[name, t0_ns, t1_ns, parent, args]`` in the order
    the spans opened; ``parent`` is the row index of the enclosing open
    span (-1 at the top) and ``t1_ns`` is None while a span is open. Row
    0 is the window's anchor span, ``profile_window``, which is also a
    ``TraceAnnotation``: a span's offset from the anchor is its offset on
    the profiler trace's clock. ``compiles`` holds one
    ``(program, seconds, t_ns)`` per backend compile in the window."""

    def __init__(self):
        self.spans: List[list] = []
        self.compiles: List[tuple] = []
        self.end_ns: Optional[int] = None     # set when the window stops
        self._open: List[int] = []            # rows still open, innermost last

    def open(self, name: str, args: dict) -> int:
        i = len(self.spans)
        self.spans.append([name, time.perf_counter_ns(), None,
                           self._open[-1] if self._open else -1, args])
        self._open.append(i)
        return i

    def close(self, i: int) -> None:
        self.spans[i][2] = time.perf_counter_ns()
        if self._open[-1] == i:
            self._open.pop()
        else:                 # the anchor, closed by a stop inside a step
            self._open.remove(i)

    def _t1(self, row) -> int:
        """A span's end, clipped to the window's: a span still open when
        the window stopped counts up to the stop."""
        t1 = row[2]
        if self.end_ns is not None and (t1 is None or t1 > self.end_ns):
            return self.end_ns
        return t1 if t1 is not None else time.perf_counter_ns()

    def total_s(self, name: str) -> float:
        """Summed seconds of every span named ``name``."""
        return 1e-9 * sum(max(self._t1(r) - r[1], 0)
                          for r in self.spans if r[0] == name)

    def self_s(self, name: str, exclude: Tuple[str, ...] = ()) -> float:
        """Summed seconds of the spans named ``name``, each less the part
        of it that its descendants named in ``exclude`` cover."""
        rows, spans = {}, self.spans
        for i, r in enumerate(spans):
            if r[0] == name:
                rows[i] = []
        if not rows:
            return 0.0
        if exclude:
            for r in spans:
                if r[0] not in exclude:
                    continue
                p = r[3]
                while p >= 0 and p not in rows:
                    p = spans[p][3]
                if p >= 0:
                    rows[p].append((r[1], self._t1(r)))
        total = 0
        for i, kids in rows.items():
            t0, t1 = spans[i][1], self._t1(spans[i])
            covered, hi = 0, t0
            for s, e in sorted(kids):
                s, e = max(s, hi), min(e, t1)
                if e > s:
                    covered += e - s
                    hi = e
            total += max(t1 - t0 - covered, 0)
        return 1e-9 * total

    def count(self, name: str, **args) -> int:
        """Spans named ``name`` whose arguments include ``args``."""
        return sum(1 for r in self.spans if r[0] == name
                   and all(r[4].get(k) == v for k, v in args.items()))

    def export_jsonl(self, path: str) -> int:
        """The record in ``Tracer.export_jsonl``'s line shape: spans, then
        compiles as instants. Times are seconds from the anchor's start
        (the trace's clock); ``args`` carry the span's ``id`` and its
        ``parent`` id."""
        base = self.spans[0][1] if self.spans else 0
        n = 0
        with open(path, "w") as f:
            for i, r in enumerate(self.spans):
                rec = {"kind": "span", "name": r[0], "sid": None,
                       "replica": 0, "t0": 1e-9 * (r[1] - base),
                       "t1": 1e-9 * (self._t1(r) - base),
                       "args": {"id": i, "parent": r[3], **r[4]}}
                f.write(json.dumps(rec, default=str) + "\n")
                n += 1
            for prog, secs, t in self.compiles:
                rec = {"kind": "instant", "name": "compile", "sid": None,
                       "replica": 0, "t": 1e-9 * (t - base),
                       "tid": _TID_SCHED,
                       "args": {"program": prog, "seconds": secs}}
                f.write(json.dumps(rec) + "\n")
                n += 1
        return n


_WINDOW: Optional[WindowRecord] = None    # the open window's record
_LAST = WindowRecord()                    # the most recent window's record
_NULL_CTX = contextlib.nullcontext()


def profiling_active() -> bool:
    return _WINDOW is not None


def last_window() -> WindowRecord:
    """The record of the most recent :class:`ProfileWindow`, open or
    closed (an empty record before the first)."""
    return _LAST


class _Span:
    """One recorded section: a ``TraceAnnotation`` plus a record row."""
    __slots__ = ("rec", "name", "args", "ann", "row")

    def __init__(self, rec: WindowRecord, name: str, args: dict):
        self.rec, self.name, self.args = rec, name, args

    def __enter__(self):
        self.ann = jax.profiler.TraceAnnotation(self.name, **self.args)
        self.ann.__enter__()
        self.row = self.rec.open(self.name, self.args)
        return self

    def __exit__(self, *exc):
        self.rec.close(self.row)
        self.ann.__exit__(*exc)


def annotate(name: str, **args):
    """Host-side profiler annotation for a hot section.

    A shared nullcontext unless a :class:`ProfileWindow` is open, so the
    steady-state tick pays one global load + one compare. Inside a
    window it becomes ``jax.profiler.TraceAnnotation(name, **args)``, so
    the section shows up on the xprof host timeline, and a row of the
    window's :class:`WindowRecord`."""
    w = _WINDOW
    if w is None:
        return _NULL_CTX
    return _Span(w, name, args)


_COMPILES: Dict[str, List] = {}           # program -> [count, seconds]


def _program_name(fun_name: str) -> str:
    """``jit(pf)`` -> ``pf``: the jitted function's own name."""
    if fun_name.startswith("jit(") and fun_name.endswith(")"):
        return fun_name[4:-1]
    return fun_name


def _on_compile(event: str, secs: float, **kw) -> None:
    if event != COMPILE_EVENT:
        return
    prog = _program_name(str(kw.get("fun_name", "?")))
    c = _COMPILES.setdefault(prog, [0, 0.0])
    c[0] += 1
    c[1] += secs
    w = _WINDOW
    if w is not None:
        w.compiles.append((prog, secs, time.perf_counter_ns()))


def backend_compiles() -> Dict[str, Tuple[int, float]]:
    """Backend compiles since this module loaded, by program: (count,
    seconds). A climbing count after warm-up is a retrace."""
    return {k: (v[0], v[1]) for k, v in _COMPILES.items()}


jax.monitoring.register_event_duration_secs_listener(_on_compile)


class ProfileWindow:
    """Opt-in ``jax.profiler`` capture window (``--profile-dir``).

    Starts a trace into ``logdir`` on ``__enter__`` and opens the anchor
    span ``profile_window``; stops after ``n_ticks`` scheduler ticks when
    given an event feed (counted on ``tick`` events), or at ``__exit__``
    otherwise. While open, :func:`annotate` sections are live and land in
    ``self.record`` (also :func:`last_window`); on stop the record is
    written to ``logdir/program_spans.jsonl``."""

    def __init__(self, logdir: str, n_ticks: Optional[int] = None,
                 events=None):
        self.logdir = logdir
        self.n_ticks = n_ticks
        self.events = events
        self.record: Optional[WindowRecord] = None
        self._ticks = 0
        self._active = False
        self._anchor = None
        self._cb = None

    def __enter__(self):
        global _WINDOW, _LAST
        jax.profiler.start_trace(self.logdir)
        self._active = True
        self.record = _LAST = WindowRecord()
        self._anchor = _Span(self.record, WINDOW_ANCHOR, {}).__enter__()
        _WINDOW = self.record
        if self.events is not None and self.n_ticks is not None:
            def cb(ev):
                if ev.get("event") == "tick":
                    self._ticks += 1
                    if self._ticks >= self.n_ticks:
                        self._stop()
            self._cb = self.events.subscribe(cb)
        return self

    def _stop(self) -> None:
        global _WINDOW
        if not self._active:
            return
        self._active = False
        _WINDOW = None
        self._anchor.__exit__(None, None, None)
        self.record.end_ns = self.record.spans[0][2]
        jax.profiler.stop_trace()
        self.record.export_jsonl(os.path.join(self.logdir, SPANS_FILE))
        if self._cb is not None and self.events is not None:
            try:
                self.events.unsubscribe(self._cb)
            except ValueError:
                pass
            self._cb = None

    def __exit__(self, *exc):
        self._stop()

