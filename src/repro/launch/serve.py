"""Serving driver: ``PYTHONPATH=src python -m repro.launch.serve
--arch qwen2-1.5b --smoke --requests 256 [--mode decode]``.

``--mode prefill`` (default) builds the device-resident two-stage EE
server (stage 1 full rate, stage 2 bucketed at capacity = ceil((p+slack)·B),
hard samples carried between batches in the device ring buffer) and pushes
batched requests with a controlled hard-fraction q.

``--mode decode`` serves open-loop decode requests (Poisson arrivals at
``--arrival-rate``, default: all at t=0) under a scheduling policy:

  * ``--scheduler sync`` (default): static batch formation over the
    step-synchronous ``DecodeServer`` — full-depth prefill per batch, then
    per-token two-stage decode in lockstep, hard tokens' hidden rows +
    stage-2 KV-cache segment rows through the pytree ring into bucketed
    stage-2 dispatches;
  * ``--scheduler continuous``: the slot-based ``ContinuousScheduler``
    (``runtime/scheduler.py``) — a fixed pool of ``--batch`` decode slots
    with per-slot step counters, backfilled from the admission queue; easy
    samples keep decoding through stage 1 while hard tokens wait in the
    ring for bucketed stage-2 dispatch. Trades the sync policy's bitwise
    batch parity for utilization; per-sample token streams stay identical.

Reports goodput (decode tokens/s), per-request latency percentiles and
per-token stats — the runtime half of the ATHEENA pipeline in both
regimes.

``--controller`` attaches the online drift control plane
(``runtime/controller.py``) to the decode scheduler: when the EWMA of the
realized hard rate q drifts persistently outside ``--controller-band``
around the provisioned ``--p``, C_thr is re-solved online from the rolling
confidence reservoir (and the scheduler's drain policy / live-slot cap
adapt from latency+occupancy feedback); past the re-plan band the Eq. (1)
stage re-plan is reported, and APPLIED under ``--controller-replan``: on a
disaggregated continuous scheduler the full chip re-split executes as a
zero-downtime live migration (``runtime/migration.py`` — quiesce /
snapshot / re-place / resume, rolled back on failure), otherwise the
bucket-capacity half applies alone. The controller's state machine report
and the migration counters (``n_migrations``, ``n_migration_rollbacks``,
``migration_pause_p50_ms/p99_ms``) ride in the output JSON.

Fault injection (chaos testing): set ``REPRO_FAULT_PLAN`` to a plan like
``dispatch@3;transfer@2#transient`` (``point@nth[#transient]`` entries —
see ``runtime/faults.py``) to arm deterministic faults at the runtime's
dispatch/enqueue/transfer/migration boundaries; ``REPRO_FAULT_LOG=<path>``
appends the structured injection/retry/rollback log as JSON lines at
exit.

``--disaggregate`` places the two stages on disjoint submeshes (the paper's
§IV spatial apportionment): stage 1 + the exit kernels on the first chips1
devices, the ring + stage 2 on the next chips2, with ``--chips1/--chips2``
defaulting to the p-proportional split of the local device set. Needs >= 2
devices — on a CPU host export
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` first.

Observability (``runtime/observe.py``; all opt-in, zero-cost when off):
``--metrics-port N`` serves Prometheus text exposition on
``127.0.0.1:N/metrics`` for the whole run (0 = ephemeral port; the CLI
self-scrapes once before exit and asserts the exposition parses);
``--metrics-dump FILE`` writes one exposition snapshot at end of run;
``--spans-out FILE`` / ``--trace-out FILE`` export the per-request span
trees as JSONL / Chrome ``trace_event`` JSON (open the latter in Perfetto
or chrome://tracing); ``--profile-dir DIR`` captures a ``jax.profiler``
trace window for the first ``--profile-ticks`` scheduler ticks. Span
tracing and profiling need a scheduler (decode mode); the metrics flags
work in every mode."""
from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time
from typing import Dict, List, Optional

import jax
import numpy as np

from repro.core import early_exit as ee
from repro.core.stage_mesh import StageMeshPlan, stage2_capacity
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import stage_submeshes
from repro.launch.shardings import stage_io_shardable
from repro.models.registry import get_arch, get_smoke, list_archs
from repro.runtime import serve_api
from repro.runtime import serve_loop as SL
from repro.runtime.controller import ControllerConfig, DriftController
from repro.runtime.router import ROUTING_POLICIES, FleetRouter
from repro.runtime.scheduler import Clock, Request, poisson_arrivals
from repro.runtime.stage_executor import StageExecutor, StagePlacement


def make_placement(p: float, batch: int, chips1: Optional[int] = None,
                   chips2: Optional[int] = None,
                   devices=None) -> StagePlacement:
    """Build the disaggregated placement for the serve CLI: explicit chip
    counts when given, otherwise the p-proportional apportionment over the
    local device set. Each stage's IO shards over its submesh 'data' axis
    when the batch divides it (launch.shardings rule)."""
    devs = jax.devices() if devices is None else devices
    plan = StageMeshPlan.resolve(p, len(devs), chips1, chips2)
    m1, m2 = stage_submeshes(plan, devs)
    return StagePlacement(
        StageExecutor(m1, shard_io=stage_io_shardable(m1, batch),
                      name="stage1"),
        StageExecutor(m2, shard_io=stage_io_shardable(m2, batch),
                      name="stage2"))


def serve_requests(sched, prompts: np.ndarray, n_tokens: int,
                   arrival_rate: float = float("inf"), *, first_id: int = 0,
                   profile=None) -> Dict[int, List[int]]:
    """Submit one open-loop decode request per prompt row (sample ids from
    ``first_id``, Poisson arrivals at ``arrival_rate``; inf = all at once)
    and drive ``sched`` until every request finishes, inside ``profile``
    when given. Returns this call's {sample_id: greedy token stream}."""
    arrivals = poisson_arrivals(len(prompts), arrival_rate, seed=2)
    sids = range(first_id, first_id + len(prompts))
    for sid, prompt, t in zip(sids, prompts, arrivals):
        sched.submit(Request(sample_id=sid, prompt=prompt, n_tokens=n_tokens,
                             arrival_time=float(t)))
    with profile if profile is not None else contextlib.nullcontext():
        results = sched.run()
    streams = {sid: results[sid] for sid in sids}
    assert all(len(v) == n_tokens for v in streams.values())
    return streams


def _summarized_stats(stats) -> dict:
    """ServeStats.as_dict with the per-dispatch realized_q series reduced
    to a summary (mean + tail) — one entry per pool tick is a drift-signal
    feed, not a CLI report line."""
    d = stats.as_dict()
    series = d.pop("realized_q_series")
    d["realized_q_series_mean"] = (float(np.mean(series)) if series
                                   else 0.0)
    d["realized_q_series_tail"] = series[-8:]
    return d


def _parse_tenant_slos(spec: Optional[str]) -> dict:
    """'web=gold,offline=batch' -> {'web': 'gold', 'offline': 'batch'}."""
    if not spec:
        return {"default": "standard"}
    out = {}
    for pair in spec.split(","):
        tenant, _, slo = pair.partition("=")
        if not tenant or not slo:
            raise SystemExit(f"--tenant-slos entry {pair!r} is not "
                             f"tenant=slo_class")
        out[tenant.strip()] = slo.strip()
    return out


def _setup_observability(args):
    """Build the observability plane for this run, or None when every flag
    is off (the schedulers then carry no event feed at all)."""
    wants = (args.metrics_port is not None or args.metrics_dump
             or args.trace_out or args.spans_out or args.profile_dir)
    if not wants:
        return None
    from repro.runtime import observe
    from repro.runtime.telemetry import EventLog
    registry = observe.MetricsRegistry()
    return {"observe": observe, "registry": registry,
            "tracer": observe.Tracer(),
            "sampler": observe.StatsSampler(registry),
            "make_events": lambda: EventLog(cap=65536),
            "server": None}


def _start_metrics_server(args, obs):
    """Open the background /metrics endpoint for the run's duration."""
    if obs is None or args.metrics_port is None:
        return
    observe = obs["observe"]
    obs["server"] = observe.MetricsServer(
        obs["registry"], obs["sampler"], port=args.metrics_port).start()
    # stderr: stdout carries the one JSON payload consumers parse
    print(f"# metrics: http://127.0.0.1:{obs['server'].port}/metrics",
          file=sys.stderr)


def _finalize_observability(args, obs, expect_sids=None) -> dict:
    """Final sample + self-scrape + exports. Returns the JSON block the
    payload carries under "observability"."""
    observe = obs["observe"]
    registry, sampler, tracer = obs["registry"], obs["sampler"], obs["tracer"]
    sampler.sample()
    out = {}
    srv = obs["server"]
    if srv is not None:
        import urllib.request
        try:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{srv.port}/metrics",
                    timeout=10) as resp:
                text = resp.read().decode()
            # raises on malformed exposition — the CI smoke contract
            out["metrics_scrape_samples"] = len(
                observe.parse_exposition(text))
            out["metrics_port"] = srv.port
        finally:
            srv.stop()
    if args.metrics_dump:
        observe.dump_metrics(registry, args.metrics_dump)
        out["metrics_dump"] = args.metrics_dump
    sampler.close()
    tracer.close()
    comp = tracer.completeness(expect_sids)
    out["spans_complete"] = comp["complete"]
    out["n_spans"] = comp["n_spans"]
    out["n_span_annotations"] = comp["n_annotations"]
    if args.spans_out:
        out["n_span_lines"] = tracer.export_jsonl(args.spans_out)
        out["spans_out"] = args.spans_out
    if args.trace_out:
        out["n_trace_events"] = tracer.export_chrome_trace(args.trace_out)
        out["trace_out"] = args.trace_out
    return out


def _maybe_profile(args, obs, events):
    """Context for the serving loop: a jax.profiler window when
    --profile-dir is set, nullcontext otherwise."""
    if obs is None or not args.profile_dir:
        return contextlib.nullcontext()
    return obs["observe"].ProfileWindow(args.profile_dir,
                                        n_ticks=args.profile_ticks,
                                        events=events)


def _serve_fleet(args, cfg, spec, params, sc, placement) -> int:
    """Decode serving through a FleetRouter over --replicas continuous
    schedulers sharing one clock; requests cycle over the --tenant-slos
    tenants. Prints the FleetStats schema (per-replica ServeStats
    embedded, q series summarized)."""
    if args.scheduler != "continuous":
        raise SystemExit("--replicas > 1 routes over continuous-scheduler "
                         "replicas; pass --scheduler continuous")
    tenant_slos = _parse_tenant_slos(args.tenant_slos)
    tenants = list(tenant_slos)
    prompts = np.asarray(jax.random.randint(
        jax.random.PRNGKey(1), (args.requests, args.seq), 0, cfg.vocab))
    max_len = args.seq + args.decode_tokens
    clock = Clock()
    obs = _setup_observability(args)
    replicas = [serve_api.build(params, cfg, spec, sc, mode="decode",
                                scheduler="continuous", placement=placement,
                                n_slots=args.batch, max_len=max_len,
                                page_size=args.page_size,
                                n_pages=args.n_pages, clock=clock,
                                events=(obs["make_events"]() if obs
                                        else None))
                for _ in range(args.replicas)]
    router = FleetRouter(replicas, policy=args.routing_policy,
                         provisioned_p=[args.p] * args.replicas)
    if obs is not None:
        for r_i, rep in enumerate(replicas):
            obs["tracer"].attach_scheduler(rep, replica=r_i)
            obs["sampler"].attach_scheduler(rep, replica=r_i)
        obs["tracer"].attach_router(router)
        obs["tracer"].attach_faults()
        obs["sampler"].attach_router(router)
        _start_metrics_server(args, obs)
    arrivals = poisson_arrivals(args.requests, args.arrival_rate, seed=2)
    for i in range(args.requests):
        tenant = tenants[i % len(tenants)]
        router.submit(Request(sample_id=i, prompt=prompts[i],
                              n_tokens=args.decode_tokens,
                              arrival_time=float(arrivals[i]),
                              tenant=tenant,
                              slo_class=tenant_slos[tenant]))
    with _maybe_profile(args, obs, replicas[0].events):
        results = router.run()
    makespan = router.clock.now()
    assert len(results) == args.requests
    assert all(len(v) == args.decode_tokens for v in results.values())
    n_tok = sum(len(v) for v in results.values())
    fleet = router.stats.as_dict()
    fleet["replicas"] = [dict(r, realized_q_series_tail=r.pop(
        "realized_q_series")[-8:]) for r in fleet["replicas"]]
    payload = {"arch": args.arch, "mode": "decode", "scheduler": "fleet",
               "routing_policy": args.routing_policy,
               "n_replicas": args.replicas, "capacity": sc.capacity,
               "n_slots": args.batch, "arrival_rate": args.arrival_rate,
               "goodput_tokens_per_s": n_tok / makespan, **fleet}
    if obs is not None:
        payload["observability"] = _finalize_observability(
            args, obs, expect_sids=set(range(args.requests)))
    print(json.dumps(payload, indent=1, default=float))
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-1.5b", choices=list_archs())
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--mode", default="prefill",
                    choices=("prefill", "decode"))
    ap.add_argument("--requests", type=int, default=256)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--seq", type=int, default=64,
                    help="request length (prompt length in decode mode)")
    ap.add_argument("--decode-tokens", type=int, default=32,
                    help="tokens to generate per request (decode mode)")
    ap.add_argument("--scheduler", default="sync",
                    choices=("sync", "continuous"),
                    help="decode scheduling policy: static batch formation "
                         "over the step-synchronous server, or the "
                         "slot-based continuous scheduler")
    ap.add_argument("--replicas", type=int, default=1,
                    help="serve decode through a FleetRouter over N "
                         "replica schedulers (1 = single-replica, no "
                         "router). Each replica gets its own scheduler; "
                         "all share one clock")
    ap.add_argument("--routing-policy", default="drift_aware",
                    choices=ROUTING_POLICIES,
                    help="fleet routing policy (--replicas > 1): "
                         "round_robin, least_loaded (occupancy + queue "
                         "depth), or drift_aware (match tenant difficulty "
                         "to per-replica provisioned p vs realized q)")
    ap.add_argument("--tenant-slos", default=None,
                    help="comma-separated tenant=slo_class pairs (classes: "
                         "gold/standard/batch), e.g. 'web=gold,batch=batch'."
                         " Requests cycle over the listed tenants; default: "
                         "one 'default' tenant at standard")
    ap.add_argument("--page-size", type=int, default=None,
                    help="enable the paged KV cache with this page size "
                         "(decode modes; seq+decode-tokens must be a "
                         "multiple)")
    ap.add_argument("--n-pages", type=int, default=None,
                    help="page-pool capacity in pages (continuous paged "
                         "mode; default: dense-equivalent "
                         "n_slots*max_len/page — shrink it to trade "
                         "admission backpressure for HBM)")
    ap.add_argument("--arrival-rate", type=float, default=float("inf"),
                    help="open-loop Poisson request rate (req/s) for decode "
                         "mode; inf = all requests arrive at t=0")
    ap.add_argument("--p", type=float, default=0.25,
                    help="design-time hard probability (sizes stage 2)")
    ap.add_argument("--c-thr", type=float, default=0.9)
    ap.add_argument("--controller", action="store_true",
                    help="attach the online drift controller (decode "
                         "mode): closed-loop C_thr re-calibration + "
                         "scheduler autoscaling against the provisioned "
                         "--p")
    ap.add_argument("--controller-band", type=float, default=0.05,
                    help="hysteresis band on |EWMA(q) - p| before the "
                         "controller actuates")
    ap.add_argument("--controller-cooldown", type=int, default=8,
                    help="controller visits to hold after an actuation")
    ap.add_argument("--controller-slo-p99", type=float, default=None,
                    help="p99 latency SLO (s) for the autoscaler's "
                         "live-slot occupancy cap (default: no cap "
                         "control)")
    ap.add_argument("--controller-replan", action="store_true",
                    help="APPLY the stage re-plan at discrete re-plan "
                         "points (default: report only): a zero-downtime "
                         "live migration of the full chip split on a "
                         "disaggregated continuous scheduler, else the "
                         "bucket-capacity half alone")
    ap.add_argument("--disaggregate", action="store_true",
                    help="stage 1 / stage 2 on disjoint submeshes")
    ap.add_argument("--chips1", type=int, default=None,
                    help="stage-1 submesh size (default: p-proportional)")
    ap.add_argument("--chips2", type=int, default=None,
                    help="stage-2 submesh size (default: p-proportional)")
    grp = ap.add_argument_group("observability (runtime/observe.py)")
    grp.add_argument("--metrics-port", type=int, default=None,
                     help="serve Prometheus text exposition on "
                          "127.0.0.1:PORT/metrics for the run (0 = "
                          "ephemeral port, printed at startup); the CLI "
                          "self-scrapes once before exit and asserts the "
                          "exposition parses")
    grp.add_argument("--metrics-dump", default=None, metavar="FILE",
                     help="write one Prometheus exposition snapshot to "
                          "FILE at end of run")
    grp.add_argument("--spans-out", default=None, metavar="FILE",
                     help="export per-request span trees + annotations as "
                          "JSONL (decode schedulers)")
    grp.add_argument("--trace-out", default=None, metavar="FILE",
                     help="export the span trees as Chrome trace_event "
                          "JSON — open in Perfetto / chrome://tracing "
                          "(decode schedulers)")
    grp.add_argument("--profile-dir", default=None, metavar="DIR",
                     help="capture a jax.profiler trace window (xprof) "
                          "into DIR")
    grp.add_argument("--profile-ticks", type=int, default=64,
                     help="scheduler ticks to keep the --profile-dir "
                          "window open (default 64)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    if args.mode == "prefill" and (args.trace_out or args.spans_out
                                   or args.profile_dir):
        raise SystemExit("span tracing / profiling rides the decode "
                         "schedulers' event feed — use --mode decode "
                         "(prefill supports the metrics flags)")

    cfg = get_smoke(args.arch) if args.smoke else get_arch(args.arch)
    spec = ee.default_spec(cfg, c_thr=args.c_thr)
    params = ee.init_ee_params(jax.random.PRNGKey(0), cfg, spec)
    cap = stage2_capacity(args.batch, args.p)
    sc = SL.ServeConfig(capacity=cap, c_thr=args.c_thr)

    placement = None
    if (args.disaggregate or args.chips1 is not None
            or args.chips2 is not None):
        placement = make_placement(args.p, args.batch, args.chips1,
                                   args.chips2)
        print(f"# {placement}")

    if args.mode == "decode" and args.replicas > 1:
        return _serve_fleet(args, cfg, spec, params, sc, placement)

    if args.mode == "decode":
        prompts = np.asarray(jax.random.randint(
            jax.random.PRNGKey(1), (args.requests, args.seq), 0, cfg.vocab))
        max_len = args.seq + args.decode_tokens
        obs = _setup_observability(args)
        sched = serve_api.build(params, cfg, spec, sc, mode="decode",
                                scheduler=args.scheduler,
                                placement=placement, n_slots=args.batch,
                                max_len=max_len,
                                page_size=args.page_size,
                                n_pages=args.n_pages,
                                events=(obs["make_events"]() if obs
                                        else None))
        if obs is not None:
            obs["tracer"].attach_scheduler(sched)
            obs["tracer"].attach_faults()
            obs["sampler"].attach_scheduler(sched)
            _start_metrics_server(args, obs)
        controller = None
        if args.controller:
            controller = DriftController(ControllerConfig(
                provisioned_p=args.p, target_band=args.controller_band,
                release_band=args.controller_band / 2,
                # keep the escalation band valid (>= target) when the user
                # widens the hysteresis band past the 0.15 default
                replan_band=max(0.15, 3 * args.controller_band),
                cooldown_ticks=args.controller_cooldown,
                latency_slo_p99=args.controller_slo_p99,
                apply_replan=args.controller_replan))
            controller.attach(sched)
        results = serve_requests(sched, prompts, args.decode_tokens,
                                 args.arrival_rate,
                                 profile=_maybe_profile(args, obs,
                                                        sched.events))
        makespan = sched.clock.now()
        n_tok = sum(len(v) for v in results.values())
        stats = _summarized_stats(sched.stats)
        payload = {"arch": args.arch, "mode": "decode",
                   "scheduler": args.scheduler, "capacity": cap,
                   "n_slots": args.batch,
                   "arrival_rate": args.arrival_rate,
                   "goodput_tokens_per_s": n_tok / makespan,
                   **stats}
        if controller is not None:
            payload["controller"] = controller.state.as_dict()
        if obs is not None:
            payload["observability"] = _finalize_observability(
                args, obs, expect_sids=set(range(args.requests)))
        print(json.dumps(payload, indent=1, default=float))
        return 0

    obs = _setup_observability(args)
    server = serve_api.build(params, cfg, spec, sc, mode="prefill",
                             scheduler=None, placement=placement)
    if obs is not None:
        obs["sampler"].attach_scheduler(server)   # stats-only (no events)
        _start_metrics_server(args, obs)
    toks = np.asarray(jax.random.randint(
        jax.random.PRNGKey(1), (args.requests, args.seq), 0, cfg.vocab))
    t0 = time.perf_counter()
    results = SL.serve_dataset(server, toks, batch=args.batch)
    dt = time.perf_counter() - t0
    assert len(results) == args.requests
    stats = _summarized_stats(server.stats)
    payload = {"arch": args.arch, "mode": "prefill", "capacity": cap,
               "throughput_samples_per_s": args.requests / dt, **stats}
    if obs is not None:
        payload["observability"] = _finalize_observability(args, obs)
    print(json.dumps(payload, indent=1))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
