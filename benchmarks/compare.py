"""CI perf-regression gate: diff a ``benchmarks.run --json`` payload
against the committed baseline and fail on tracked-metric regressions.

    PYTHONPATH=src python -m benchmarks.compare \
        --current bench.json [--baseline benchmarks/baseline_cpu.json] \
        [--out perf_diff.json]

The baseline tracks *machine-robust* metrics — device-vs-host speedup
ratios (both servers run on the same host, so the ratio survives runner
variance), bitwise-parity booleans, and per-bench ok flags — rather than
absolute samples/sec, which CI runner churn would make flaky. Each metric
is a dotted path into the payload's ``benches`` map with a baseline value
and per-metric tolerances (noise is per-metric: latency percentiles swing
far more than speedup ratios, so one global threshold either flaps or
masks regressions). A numeric metric spec supports:

  * ``tolerance`` — relative slack (default 25%): fail when a
    higher-is-better metric drops more than ``tolerance * baseline``, or a
    lower-is-better one grows by the same margin;
  * ``abs_tolerance`` — absolute slack in the metric's own units; the
    allowed band is ``max(tolerance * |baseline|, abs_tolerance)``
    (rtol/atol composition — absolute slack keeps near-zero baselines from
    flapping, relative slack keeps large ones meaningful);
  * ``min`` / ``max`` — hard bounds enforced REGARDLESS of tolerances (a
    contract floor like "goodput ratio >= 1.3x stays >= 1.3x" even when
    the recorded baseline would tolerate lower).

Booleans must match exactly. Absolute wall seconds ride along in the diff
artifact for the perf trajectory but are untracked.

Both files carry ``schema_version`` — a mismatch fails loudly instead of
quietly diffing the wrong fields (regenerate the baseline via
``python -m benchmarks.run --fast --json`` after a schema bump).
"""
from __future__ import annotations

import argparse
import json
import sys
from typing import Any, Optional


def _lookup(tree: dict, path: str) -> Optional[Any]:
    """Resolve a dotted path ('serve_pipeline.q0.3.speedup') against nested
    dicts. Keys themselves may contain dots ('q0.3'), so greedily match the
    longest key prefix at each level."""
    node: Any = tree
    rest = path
    while rest:
        if not isinstance(node, dict):
            return None
        key = None
        for k in sorted(node, key=len, reverse=True):
            if rest == k or rest.startswith(k + "."):
                key = k
                break
        if key is None:
            return None
        node = node[key]
        rest = rest[len(key) + 1:]
    return node


def compare(current: dict, baseline: dict) -> dict:
    """Returns the diff report; report['ok'] is the gate verdict."""
    if current.get("schema_version") != baseline.get("schema_version"):
        return {"ok": False, "schema_mismatch": True,
                "current_schema": current.get("schema_version"),
                "baseline_schema": baseline.get("schema_version"),
                "metrics": {}}
    benches = current.get("benches", {})
    default_tol = float(baseline.get("default_tolerance", 0.25))
    report = {"ok": True, "schema_mismatch": False,
              "backend": current.get("backend"),
              "fast": current.get("fast"), "metrics": {},
              "untracked_seconds": {
                  name: rec.get("seconds")
                  for name, rec in sorted(benches.items())}}

    for path, spec in sorted(baseline.get("metrics", {}).items()):
        got = _lookup(benches, path)
        want = spec.get("value")
        entry = {"baseline": want, "current": got}
        if got is None:
            entry["status"] = "MISSING"
            report["ok"] = False
        elif isinstance(want, bool):
            entry["status"] = "ok" if got == want else "MISMATCH"
            report["ok"] &= got == want
        else:
            tol = float(spec.get("tolerance", default_tol))
            slack = max(tol * abs(want), float(spec.get("abs_tolerance",
                                                        0.0)))
            lower_is_better = spec.get("direction", "higher") == "lower"
            # tolerance bounds the regression direction only; hard min/max
            # clamp BOTH directions regardless of direction or slack
            lo = want + slack if lower_is_better else want - slack
            hi = float("inf")
            if lower_is_better:
                lo, hi = float("-inf"), lo
            if "min" in spec:
                lo = max(lo, float(spec["min"]))
            if "max" in spec:
                hi = min(hi, float(spec["max"]))
            # negated comparison so a NaN measurement FAILS the gate
            # instead of slipping through every < / > comparison as False
            bad = not (lo <= got <= hi)
            entry["delta"] = (got - want) / want if want else 0.0
            entry["bound_low"], entry["bound_high"] = lo, hi
            entry["status"] = "REGRESSION" if bad else "ok"
            report["ok"] &= not bad
        report["metrics"][path] = entry
    return report


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--current", required=True,
                    help="benchmarks.run --json output to gate")
    ap.add_argument("--baseline", default="benchmarks/baseline_cpu.json")
    ap.add_argument("--out", default=None,
                    help="write the diff report here (CI artifact)")
    args = ap.parse_args(argv)

    with open(args.current) as f:
        current = json.load(f)
    with open(args.baseline) as f:
        baseline = json.load(f)
    report = compare(current, baseline)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(report, f, indent=1, default=float)

    if report.get("schema_mismatch"):
        print(f"perf-gate: SCHEMA MISMATCH — current "
              f"{report['current_schema']} vs baseline "
              f"{report['baseline_schema']}; regenerate the baseline")
        return 1
    width = max((len(p) for p in report["metrics"]), default=10)
    for path, e in report["metrics"].items():
        cur = e["current"]
        cur_s = f"{cur:.3f}" if isinstance(cur, float) else str(cur)
        base = e["baseline"]
        base_s = f"{base:.3f}" if isinstance(base, float) else str(base)
        print(f"  {path:<{width}}  current={cur_s:<10} "
              f"baseline={base_s:<10} {e['status']}")
    verdict = "PASS" if report["ok"] else "FAIL"
    print(f"perf-gate: {verdict} "
          f"({sum(e['status'] != 'ok' for e in report['metrics'].values())}"
          f" failing of {len(report['metrics'])} tracked)")
    return 0 if report["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
