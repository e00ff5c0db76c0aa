"""Benchmark harness entry point: one benchmark per paper table/figure.

``PYTHONPATH=src python -m benchmarks.run [--fast] [--only NAME] [--json]``

``--json`` emits one machine-readable object on stdout — a schema-versioned
envelope (``schema_version``, the jax backend, a ``fast`` flag) around a
``benches`` map of per-bench wall seconds, pass/fail, and whatever
structured fields the benchmark returned besides its table text — so CI
can record the perf trajectory over time and ``benchmarks/compare.py`` can
gate regressions against a committed baseline. The human tables go to
stderr in that mode.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import traceback

# benchmarks/compare.py validates this before diffing; bump it whenever the
# payload shape changes so a stale baseline fails loudly instead of quietly
# comparing the wrong fields.
# v2: envelope records jax_version / device_count alongside the backend, so
# a perf diff between two CI runs is attributable to the runtime it ran on.
SCHEMA_VERSION = 2


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="fewer SA seeds / smaller serving sets (CI smoke)")
    ap.add_argument("--only", default=None, help="run a single benchmark")
    ap.add_argument("--json", action="store_true",
                    help="machine-readable per-bench results on stdout")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks import (fig9_tap, kernel_dispatch, roofline,
                            serve_drift, serve_fleet, serve_migration,
                            serve_observed, serve_paged, serve_pipeline,
                            table1_resources, table2_overhead,
                            table3_throughput, table4_networks)
    seeds = 1 if args.fast else 3
    benches = [
        ("fig9_tap", lambda: fig9_tap.run(n_seeds=seeds)),
        ("table1_resources", lambda: table1_resources.run(n_seeds=seeds)),
        ("table2_overhead", table2_overhead.run),
        ("table3_throughput", table3_throughput.run),
        ("table4_networks", lambda: table4_networks.run(n_seeds=seeds)),
        ("roofline", roofline.run),
        ("kernel_dispatch", lambda: kernel_dispatch.run(fast=args.fast)),
        ("serve_pipeline", lambda: serve_pipeline.run(fast=args.fast)),
        ("serve_paged", lambda: serve_paged.run(fast=args.fast)),
        ("serve_drift", lambda: serve_drift.run(fast=args.fast)),
        ("serve_migration", lambda: serve_migration.run(fast=args.fast)),
        ("serve_fleet", lambda: serve_fleet.run(fast=args.fast)),
        ("serve_observed", lambda: serve_observed.run(fast=args.fast)),
    ]
    if args.only and args.only not in {n for n, _ in benches}:
        ap.error(f"unknown benchmark {args.only!r}; "
                 f"choose from {[n for n, _ in benches]}")
    text_out = sys.stderr if args.json else sys.stdout
    report = {}
    failures = 0
    for name, fn in benches:
        if args.only and name != args.only:
            continue
        t0 = time.time()
        try:
            out = fn()
            dt = time.time() - t0
            print(out["text"], file=text_out)
            print(f"[{name}: {dt:.1f}s]\n", file=text_out, flush=True)
            report[name] = {"seconds": round(dt, 3), "ok": True,
                            **{k: v for k, v in out.items() if k != "text"}}
        except Exception:
            failures += 1
            report[name] = {"seconds": round(time.time() - t0, 3),
                            "ok": False}
            print(f"[{name}: FAILED]", file=text_out, flush=True)
            traceback.print_exc()
    if args.json:
        import jax
        payload = {"schema_version": SCHEMA_VERSION,
                   "backend": jax.default_backend(),
                   "jax_version": jax.__version__,
                   "device_count": jax.device_count(),
                   "fast": bool(args.fast),
                   "benches": report}
        print(json.dumps(payload, indent=1, default=float))
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
