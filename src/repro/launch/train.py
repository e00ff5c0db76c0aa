"""Training driver: ``PYTHONPATH=src python -m repro.launch.train
--arch qwen2-1.5b --smoke --steps 50``.

Runs the EE joint-loss training loop (checkpoint/restart, straggler
mitigation) on the local platform. ``--smoke`` swaps in the reduced
same-family config so the driver runs anywhere; without it the full config
is used (real accelerators). The same step function is what the dry-run
lowers on the production mesh."""
from __future__ import annotations

import argparse
import json

from repro.core import early_exit as ee
from repro.data import pipeline as dp
from repro.launch.compile_cache import enable_compile_cache
from repro.models.registry import get_arch, get_smoke, list_archs
from repro.optim import adamw
from repro.runtime import train_loop as TL


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-1.5b", choices=list_archs())
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train")
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--fail-at", type=int, default=None,
                    help="inject a failure at this step (restart demo)")
    ap.add_argument("--exit-layer", type=int, default=None)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_smoke(args.arch) if args.smoke else get_arch(args.arch)
    spec = (ee.EarlyExitSpec(exit_layer=args.exit_layer)
            if args.exit_layer is not None else ee.default_spec(cfg))
    tc = TL.TrainConfig(
        steps=args.steps, ckpt_every=args.ckpt_every,
        ckpt_dir=f"{args.ckpt_dir}/{args.arch}", log_every=10,
        fail_at_step=args.fail_at,
        optim=adamw.AdamWConfig(lr=args.lr, warmup_steps=min(20, args.steps),
                                total_steps=args.steps))
    stream = dp.LMStreamSpec(global_batch=args.batch, seq_len=args.seq,
                             vocab=cfg.vocab, seed=0)

    def on_step(t, m):
        print(f"step {t:5d}  loss {m['loss']:.4f}  "
              f"ce_exit {m['ce_exit']:.4f}  ce_final {m['ce_final']:.4f}  "
              f"lr {m['lr']:.2e}", flush=True)

    runner = TL.train_with_restarts if args.fail_at is not None else TL.train
    out = runner(cfg, spec, tc, stream_spec=stream) \
        if args.fail_at is not None else \
        TL.train(cfg, spec, tc, stream_spec=stream, on_step=on_step)
    print(json.dumps({"arch": args.arch, "steps": out["step"],
                      "final_loss": out["history"][-1]["loss"]
                      if out["history"] else None,
                      "restarts": out.get("restarts", 0)}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
