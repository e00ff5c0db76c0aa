"""Readings that set a configuration's limit: the program's widest logit
gap on many seeds (the lower reading) and the float8 control's on the same
served streams (the upper reading), all in one process; beside them, each
side's widest flipped decision, which sets the judge's band.

    python3 benchmarks/chip/control.py --workload <cell> --seconds <s> \
        --seeds 1,2,3,...

The control is the plain reference with every matrix product in float8
e4m3 (the step below the configuration's bfloat16): at each decision
position of the sampled requests its own greedy token, from the head its
own confidence picks, is judged against the float32 reference exactly as a
served token is. One JSON line per seed goes to stdout.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time

import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    spec = run.start(args.workload)
    if spec is None:
        return 2
    from chipbench import system
    ref_mod = system.reference(spec["conf"])
    for seed in (int(s) for s in args.seeds.split(",")):
        out = run.run_cell(spec, seed, args.seconds, False,
                           t_start=time.perf_counter(),
                           control=ref_mod.fp8_matmul)
        print(json.dumps({
            "seed": seed, "correct": out["correct"],
            "max_logit_gap": out["checks"]["max_logit_gap"]["value"],
            "control_max_logit_gap": out["control_max_logit_gap"],
            "decision_flip": out["checks"]["decision_flip"]["value"],
            "control_decision_flip": out["control_decision_flip"],
            "metrics": out["metrics"]}), flush=True)
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
