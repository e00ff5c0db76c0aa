"""One run of one benchmark cell on the chips of this machine.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Everything a cell is made of is found by name from ``BENCHMARK.json`` at
the checkout's root: the configuration ``configs/<config>.json`` (published
sizes, a ``program`` block of ArchConfig fields for what they do not say,
serving sizes and the comparison's limits), the traffic mix
``traffic/<traffic>.json``, the plain reference
``references/<reference>.py`` the configuration names (which also says how
it reads the model: ``dims`` and ``weights``), and one reader
``metrics/<metric>.py`` per per-layer metric. Adding a cell, a metric or a
configuration of another architecture adds files; nothing here changes.

A run: weights from the seed on the device (one jitted call), warm-up of
every program the cell's traffic reaches (the exit threshold is calibrated
there), the pool filled, then ``--seconds`` of measured window with no
compile in it, then the comparison with the plain reference. The last line
of stdout is one JSON object; the numbers compared with their limits are
the last lines of stderr and the result's last key. With no TPU, or fewer
chips than the cell asks for, it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import warnings  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
# the TPU runtime would otherwise keep its logs under a fixed /tmp path
os.environ.setdefault("TPU_LOG_DIR", "disabled")
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
TRACE_SECONDS = 5.0          # a traced run traces this much of its window
AFTER_CLOSE_S = 60.0         # wait for first tokens of requests due in it
# the judged sample: at most N_JUDGE requests, until it holds JUDGE_TOKENS
# served tokens with JUDGE_HARD of them from stage 2
N_JUDGE, JUDGE_TOKENS, JUDGE_HARD = 8, 512, 32
WARMUP_TOKENS = 6
# host spans that name the device's idle gaps (the innermost one covering
# a gap): the benchmark's own, and the program's inside a scheduler step
HOST_SPANS = ("bench_step", "bench_scan", "bench_sleep",
              "sched_step", "admit_chunk", "prefill", "admit_sync",
              "harvest_sync", "finish_tick_sync", "stage2_bucket_dispatch",
              "pool_tick_fused", "pool_tick", "ring_enqueue")


def _say(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def load_cell(name: str, root: Path = ROOT) -> dict:
    """The cell's entries from BENCHMARK.json with its files loaded."""
    with open(root / "BENCHMARK.json") as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    conf_entry = next(c for c in bench["configs"]
                      if c["name"] == cell["config"])
    with open(root / conf_entry["file"]) as f:
        conf = json.load(f)
    with open(HERE / "traffic" / f"{cell['traffic']}.json") as f:
        mix = json.load(f)

    def applies(m):
        return name in m["workloads"] if "workloads" in m else True

    e2e = [m for m in bench["end_to_end"] if applies(m)]
    moves = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in moves)]
    return {"cell": cell, "conf": conf, "mix": mix, "end_to_end": e2e,
            "per_layer": per_layer}


class CompileCounter:
    """Backend compiles (persistent-cache hits do not compile)."""

    def __init__(self):
        import jax
        self.n, self.secs, self._mark = 0, 0.0, 0

        def on_event(event: str, secs: float, **_) -> None:
            if event == COMPILE_EVENT:
                self.n += 1
                self.secs += secs

        jax.monitoring.register_event_duration_secs_listener(on_event)

    def mark(self) -> None:
        self._mark = self.n

    def since_mark(self) -> int:
        return self.n - self._mark


def _pct(xs, q):
    import numpy as np
    return float(np.percentile(np.asarray(xs), q))


def run_cell(spec: dict, seed: int, seconds: float, trace: bool,
             t_start: float = T_START, devices=None,
             control=None) -> dict:
    """One run; ``control`` (a reference matmul of lower precision) also
    judges that control's tokens on the same sample, for setting limits."""
    import jax
    import numpy as np
    from chipbench import compare, roofline, system, traffic, window
    from chipbench.tracing import Reduced, extract, WINDOW_SPAN
    from repro.runtime import observe

    cell, conf, mix = spec["cell"], spec["conf"], spec["mix"]
    srv = conf["serving"]
    devices = devices or jax.devices()[:cell["chips"]]
    compiles = CompileCounter()
    warnings.filterwarnings("error", message="A large amount of constants")

    cfg = system.arch_config(conf)
    dims = system.model_dims(conf, cfg)
    from repro.core import early_exit as ee
    perm = system.vocab_perm(seed, cfg.vocab)
    params = system.make_weights(
        cfg, ee.EarlyExitSpec(exit_layer=srv["exit_layer"]),
        conf["weights_seed"], perm)
    clock = system.WallClock()
    sched = system.build(conf, mix, params, cfg, clock, devices)
    c_thr = srv["c_thr"]
    system.warm_up(sched, traffic.warmup_requests(
        mix, seed, cfg.vocab, WARMUP_TOKENS), c_thr)
    _say(f"warm-up backend compiles {compiles.n} ({compiles.secs!r} s); "
         f"exit threshold {c_thr!r}")

    reqs = traffic.generate(mix, seed, perm, srv["n_slots"])
    by_sid = {r.sid: r for r in reqs}
    tracker = window.Tracker(by_sid, [r.sid for r in reqs])

    ann = (lambda name: jax.profiler.TraceAnnotation(name)) if trace \
        else (lambda name: observe.annotate(name))
    # the stream runs for the mix's preroll before the window opens, so the
    # pool reaches the steady state the window measures
    clock.set_origin(time.perf_counter() + mix["preroll_s"])
    system.submit(sched, reqs)
    while clock.now() < 0:
        state = sched.step()
        tracker.scan(sched, clock.now())
        if state == "waiting":
            clock.advance_to(min(sched.next_arrival(), 0.0))
    setup_s = time.perf_counter() - t_start
    _say(f"setup_s {setup_s!r}; backend compiles before the window "
         f"{compiles.n} ({compiles.secs!r} s)")

    st = sched.stats
    base = {"dec": st.n_decisions, "exited": st.n_exited,
            "to_stage2": st.n_stage2,
            "buckets": st.n_buckets, "fill": st.bucket_fill_sum,
            "ticks": st.n_stage1_batches, "stalls": st.n_stalls}
    logdir, prof, win_span = None, None, None
    trace_lo = trace_hi = None
    occupancy = []
    compiles.mark()
    if trace:
        logdir = tempfile.mkdtemp(prefix="chipbench_trace_")
        prof = observe.ProfileWindow(logdir)
        prof.__enter__()
        win_span = jax.profiler.TraceAnnotation(WINDOW_SPAN)
        win_span.__enter__()
        trace_lo = clock.now()
    while True:
        with ann("bench_step"):
            state = sched.step()
        now = clock.now()
        with ann("bench_scan"):
            tracker.scan(sched, now)
        occupancy.append(sched.n_busy / srv["n_slots"])
        if prof is not None and now >= TRACE_SECONDS:
            win_span.__exit__(None, None, None)
            prof.__exit__(None, None, None)
            trace_hi, prof = clock.now(), None
        if now >= seconds:
            break
        if state == "waiting":
            with ann("bench_sleep"):
                clock.advance_to(min(sched.next_arrival(), seconds))
        elif state == "idle":
            break
    t_close = clock.now()
    if prof is not None:
        win_span.__exit__(None, None, None)
        prof.__exit__(None, None, None)
        trace_hi = clock.now()
    n_compiles = compiles.since_mark()
    q_ticks = list(st.realized_q_series)[-(st.n_stage1_batches
                                            - base["ticks"]):]
    quarters = [float(np.mean(x)) for x in np.array_split(q_ticks, 4)
                if len(x)]
    counters = {k: v - base[k] for k, v in {
        "dec": st.n_decisions, "exited": st.n_exited,
        "to_stage2": st.n_stage2, "buckets": st.n_buckets,
        "fill": st.bucket_fill_sum, "ticks": st.n_stage1_batches,
        "stalls": st.n_stalls}.items()}
    counters["hard"] = counters["dec"] - counters["exited"]
    # requests due in the window get their first token even after it
    deadline = t_close + AFTER_CLOSE_S
    while (mix["kind"] == "poisson" and clock.now() < deadline
           and tracker.due_without_first(0.0, seconds)):
        if sched.step() == "waiting":
            clock.advance_to(sched.next_arrival())
        tracker.scan(sched, clock.now())
    system.harvest(sched)
    tracker.scan(sched, clock.now())
    _say(f"window {t_close!r} s; backend compiles in the window "
         f"{n_compiles}; decisions {counters['dec']}, hard "
         f"{counters['hard']}, to stage 2 {counters['to_stage2']}, "
         f"buckets {counters['buckets']}, ticks {counters['ticks']}, "
         f"stalls {counters['stalls']}, realized q "
         f"{counters['hard'] / max(counters['dec'], 1)!r}, mean tick q by "
         f"quarter of the window {quarters!r}")

    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}

    # -- end-to-end metrics --------------------------------------------------
    values = {"setup_s": setup_s}
    if mix["kind"] == "backlog":
        values["tokens_per_s"] = tracker.tokens_delivered(0.0, t_close) \
            / t_close
        attempted = sum(1 for t in tracker.admitted_at.values() if t >= 0)
    else:
        ttft = tracker.ttft_s(0.0, seconds)
        itl = tracker.itl_s(0.0, t_close)
        values["ttft_p95_ms"] = 1e3 * _pct(ttft, 95) if ttft else None
        values["itl_p50_ms"] = 1e3 * _pct(itl, 50) if itl else None
        values["itl_p95_ms"] = 1e3 * _pct(itl, 95) if itl else None
        attempted = sum(1 for r in reqs if 0.0 <= r.due < seconds)
        _say(f"requests due in the window {attempted}, first tokens "
             f"{len(ttft)}, inter-token gaps {len(itl)}")
    never = tracker.due_without_first(0.0, seconds) \
        if mix["kind"] == "poisson" else 0

    # -- per-layer metrics (traced run) --------------------------------------
    breakdown = None
    layer_values = {}
    if trace:
        raw = extract(logdir, HOST_SPANS + (WINDOW_SPAN,))
        shutil.rmtree(logdir, ignore_errors=True)
        red = Reduced.of(raw)
        device["busy_s"] = red.busy_s
        device["window_s"] = red.window_s
        breakdown = red.breakdown()
        ctx = {"trace": red, "dims": dims, "conf": conf, "mix": mix,
               "peaks": roofline.peaks(dev.device_kind), "chips": len(devices),
               "counts": tracker.counts_between(trace_lo, trace_hi),
               "counters": counters, "occupancy": occupancy}
        for m in spec["per_layer"]:
            v = system.load_module(
                HERE / "metrics" / f"{m['name']}.py").read(ctx)
            if v is not None:
                layer_values[m["name"]] = {"value": v, "unit": m["unit"]}
        _say(f"trace window {red.window_s!r} s, busy {red.busy_s!r} s "
             f"(per chip {red.busy_s_per_chip!r}); "
             f"device seconds by program " + ", ".join(
                 f"{k} {v!r} ({red.module_n[k]})" for k, v in sorted(
                     red.module_s.items(), key=lambda kv: -kv[1])[:8]))

    # -- correctness: the plain reference on a sample of finished requests --
    results = {sid: list(sched.results[sid]) for sid in tracker.delivered}
    del sched
    gc.collect()
    t_ref = time.perf_counter()
    ref_mod = system.reference(conf)
    ref = compare.Reference(ref_mod, dims,
                            system.reference_weights(conf, params),
                            srv["max_len"], mix["output"]["max"])
    done = [s for s, v in results.items()
            if len(v) == by_sid[s].n_tokens and None not in v]
    rng = np.random.default_rng(np.random.SeedSequence([seed, 0x0C4E]))
    order = []
    if done:
        longest = max(done, key=lambda s: len(by_sid[s].prompt) + len(
            results[s]))
        order = [longest] + [int(s) for s in rng.permutation(
            [s for s in done if s != longest])]
    # the log-confidence band around the exit threshold where either
    # head's token counts, measured for the configuration (PERF.md, 2)
    limit, band = conf["limits"]["max_logit_gap"], conf["limits"]["band"]
    worst, failed, n_judged, n_hard, worst_ctrl = 0.0, 0, 0, 0, 0.0
    worst_flip, worst_ctrl_flip = 0.0, 0.0
    ctrl = None if control is None else compare.Reference(
        ref_mod, dims, ref.w, srv["max_len"], mix["output"]["max"],
        matmul=control)
    sample = []
    for s in order:
        if len(sample) >= N_JUDGE or (n_judged >= JUDGE_TOKENS
                                      and n_hard >= JUDGE_HARD):
            break
        sample.append(s)
        if ctrl is not None:
            jc = compare.judge_control(ref, ctrl, by_sid[s].prompt,
                                       results[s], c_thr, band)
            worst_ctrl = max(worst_ctrl, jc.max_gap)
            worst_ctrl_flip = max(worst_ctrl_flip, jc.flip_lc)
        j = compare.judge(ref, by_sid[s].prompt, results[s], c_thr, band)
        worst = max(worst, j.max_gap)
        worst_flip = max(worst_flip, j.flip_lc)
        failed += j.max_gap > limit or j.flip_lc > band
        n_judged += j.n_tokens
        n_hard += j.n_hard
        _say(f"judged request {s}: prompt {len(by_sid[s].prompt)}, tokens "
             f"{j.n_tokens}, to stage 2 {j.n_hard}, in band "
             f"{j.n_ambiguous}, widest gap {j.max_gap!r} at token "
             f"{j.worst_at} (log confidence to threshold {j.worst_lc!r}), "
             f"flipped decisions {j.n_flips} (largest |log confidence to "
             f"threshold| {j.flip_lc!r})")
    correct = bool(sample) and worst <= limit and worst_flip <= band \
        and never == 0 and n_compiles == 0
    _say(f"reference over {len(sample)} requests, {n_judged} tokens "
         f"({n_hard} to stage 2), "
         f"{time.perf_counter() - t_ref!r} s")

    metrics = {}
    for m in spec["end_to_end" if not trace else "per_layer"]:
        # a quantity split per cell, so that each cell has a bound of its
        # own (``tokens_per_s.qwen3-4b``), is the quantity before the dot
        v = values.get(m["name"].split(".")[0]) if not trace else \
            layer_values.get(m["name"], {}).get("value")
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    checks = {"max_logit_gap": {"value": worst, "limit": limit},
              "decision_flip": {"value": worst_flip, "limit": band},
              "window_compiles": {"value": n_compiles, "limit": 0},
              "due_without_first_token": {"value": never, "limit": 0}}
    for k, v in checks.items():
        _say(f"check {k} {v['value']!r} limit {v['limit']!r}")
    out = {"correct": correct, "attempted": int(attempted),
           "failed": int(failed + never), "metrics": metrics,
           "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    if ctrl is not None:
        out["control_max_logit_gap"] = worst_ctrl
        out["control_decision_flip"] = worst_ctrl_flip
    out["realized_q"] = counters["hard"] / max(counters["dec"], 1)
    out["checks"] = checks
    return out


def start(workload: str):
    """The cell's spec once JAX holds as many TPU chips as the cell asks
    for, with the persistent compile cache on; None (said on stderr) when
    it does not."""
    sys.path[:0] = [str(HERE), str(ROOT / "src")]
    spec = load_cell(workload)
    import jax
    devs = jax.devices()
    need = spec["cell"]["chips"]
    if devs[0].platform != "tpu" or len(devs) < need:
        print(f"bench: needs {need} TPU chip(s); JAX sees {len(devs)} "
              f"{devs[0].platform!r} device(s)", file=sys.stderr)
        return None
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    # every program, however quick to compile, is found again next run
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return spec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    spec = start(args.workload)
    if spec is None:
        return 2
    out = run_cell(spec, args.seed, args.seconds, bool(args.trace))
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
