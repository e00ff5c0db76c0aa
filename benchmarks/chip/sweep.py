"""Knee sweep for an open-loop cell: the same run at several fixed arrival
rates, in one process, one table row per rate.

    python3 benchmarks/chip/sweep.py --workload qwen2-1.5b.chat.p25 \
        --seconds 10 --rates 4,8,12,16,20,24 [--seed 7]

The knee is the highest rate whose p95 time to first token and median
inter-token gap stay within the limits the traffic file states
(``ttft_limit_ms``, ``itl_limit_ms``) while every request due in the window
gets its first token before the window closes (no growing backlog). The
cell then runs at a fixed rate below it, written into the traffic file as
a number; nothing searches for a rate at run time.
"""
from __future__ import annotations

import argparse
import copy
import json
import sys
import time

import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)
    spec = run.start(args.workload)
    if spec is None:
        return 2
    mix = spec["mix"]
    print(f"limits: ttft_p95 <= {mix['ttft_limit_ms']} ms, itl_p50 <= "
          f"{mix['itl_limit_ms']} ms", flush=True)
    for rate in (float(r) for r in args.rates.split(",")):
        s = copy.deepcopy(spec)
        s["mix"]["rate_per_s"] = rate
        out = run.run_cell(s, args.seed, args.seconds, False,
                           t_start=time.perf_counter())
        m = {k: v["value"] for k, v in out["metrics"].items()}
        late = out["checks"]["due_without_first_token"]["value"]
        ok = (late == 0 and m.get("ttft_p95_ms", 1e9) <= mix["ttft_limit_ms"]
              and m.get("itl_p50_ms", 1e9) <= mix["itl_limit_ms"])
        print(json.dumps({"rate_per_s": rate, "within_limits": ok,
                          "due_in_window": out["attempted"],
                          "no_first_token_by_close": late, **m}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
