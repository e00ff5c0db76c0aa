"""Find a configuration's exit threshold: the cell's own stream at several
fixed thresholds, in one process, one line per threshold with the realized
hard share of the window's exit decisions.

    python3 benchmarks/chip/calibrate.py --workload qwen2-1.5b.offline.p25 \
        --seconds 20 --thresholds 0.18,0.2,0.22,0.24 [--seed 7]

The threshold whose share is nearest the mix's ``p_hard`` goes into the
configuration file (``serving.c_thr``) as a number; runs never calibrate.
A fixed threshold keeps every run's decisions on one rule: the share a
run realizes then follows from the seed's weights and traffic alone.
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
    ap.add_argument("--thresholds", required=True)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)
    spec = run.start(args.workload)
    if spec is None:
        return 2
    for c in (float(x) for x in args.thresholds.split(",")):
        s = copy.deepcopy(spec)
        s["conf"]["serving"]["c_thr"] = c
        out = run.run_cell(s, args.seed, args.seconds, False,
                           t_start=time.perf_counter())
        print(json.dumps({"c_thr": c, "p_hard": s["mix"]["p_hard"],
                          "realized_q": out["realized_q"],
                          **{k: v["value"] for k, v in
                             out["metrics"].items()}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
