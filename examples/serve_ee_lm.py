"""End-to-end driver: serve a small LM with batched requests through the
two-stage Early-Exit pipeline (the paper's deployment scenario), prefill
AND autoregressive decode.

    PYTHONPATH=src python examples/serve_ee_lm.py [--requests 512]

Flow: init a reduced qwen2-family model -> calibrate C_thr on a profiling
batch so p_hard ~ 0.25 -> size the stage-2 bucket from p (+slack) -> serve
batched requests through the device-resident TwoStageServer (fused exit
decision + compaction via the kernel dispatch layer, device ring buffer,
async bucket drains) -> report throughput, realized q, bucket occupancy,
and verify every request got an answer consistent with the one-shot
pipeline. Then the same model generates continuations through the
continuous decode scheduler (per-token exit decisions; hard tokens' hidden
rows + stage-2 block-table rows through the pytree ring, stage 2 over the
paged KV pool) and every token stream is checked against the host-loop
decode reference."""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import early_exit as ee
from repro.core import exit_decision as ed
from repro.core.stage_mesh import stage2_capacity
from repro.models.registry import get_smoke
from repro.runtime import serve_loop as SL

ap = argparse.ArgumentParser()
ap.add_argument("--requests", type=int, default=512)
ap.add_argument("--batch", type=int, default=32)
ap.add_argument("--seq", type=int, default=48)
ap.add_argument("--target-p", type=float, default=0.25)
ap.add_argument("--decode-tokens", type=int, default=16)
args = ap.parse_args()

cfg = get_smoke("qwen2-1.5b")
spec0 = ee.default_spec(cfg)
params = ee.init_ee_params(jax.random.PRNGKey(0), cfg, spec0)

# --- calibrate C_thr on a profiling batch (paper §III-B.1) -------------------
prof_toks = jax.random.randint(jax.random.PRNGKey(1), (256, args.seq), 0,
                               cfg.vocab)
_, _, exit_logits, _ = ee.stage1_prefill(params, cfg, spec0, prof_toks)
c_thr = ed.calibrate_threshold(ed.softmax_confidence(exit_logits),
                               target_exit_rate=1.0 - args.target_p)
spec = ee.EarlyExitSpec(exit_layer=spec0.exit_layer, c_thr=c_thr)
print(f"calibrated C_thr={c_thr:.4f} for target p={args.target_p}")

# --- size stage 2 and build the server --------------------------------------
cap = stage2_capacity(args.batch, args.target_p)
server = SL.build(params, cfg, spec,
                  SL.ServeConfig(capacity=cap, c_thr=c_thr),
                  mode="prefill", scheduler=None)
print(f"stage-2 bucket capacity {cap} (batch {args.batch})")

# --- batched serving ---------------------------------------------------------
toks = np.asarray(jax.random.randint(jax.random.PRNGKey(2),
                                     (args.requests, args.seq), 0, cfg.vocab))
t0 = time.perf_counter()
results = SL.serve_dataset(server, toks, batch=args.batch)
dt = time.perf_counter() - t0
assert len(results) == args.requests, "dropped requests!"

s = server.stats
print(f"served {args.requests} requests in {dt:.2f}s "
      f"({args.requests / dt:,.0f} samples/s on this host)")
print(f"realized q={s.realized_q:.3f}  exited early: {s.n_exited}  "
      f"stage-2: {s.n_stage2}  stalls: {s.n_stalls}  "
      f"mean bucket fill {s.mean_bucket_fill:.2f}")

# --- consistency vs the one-shot fused pipeline ------------------------------
one = ee.serve_batch(params, cfg, spec, jnp.asarray(toks[:args.batch]),
                     capacity=args.batch)
merged = np.asarray(one["logits"])
worst = max(float(np.abs(results[i] - merged[i]).max())
            for i in range(args.batch))
print(f"server vs one-shot pipeline max |delta| over first batch: "
      f"{worst:.2e}")
assert worst < 5e-4

# --- prefill -> decode: per-token EE generation ------------------------------
# The decode threshold is calibrated on the first decode step's exit-head
# confidences (per-token confidence statistics differ from prefill's).
prompts = toks[:args.batch]
dec_conf = SL.decode_step0_confidences(params, cfg, spec, prompts,
                                       max_len=args.seq + 2)
c_thr_dec = ed.calibrate_threshold(dec_conf,
                                   target_exit_rate=1.0 - args.target_p)
spec_dec = ee.EarlyExitSpec(exit_layer=spec0.exit_layer, c_thr=c_thr_dec)
sc_dec = SL.ServeConfig(capacity=cap, c_thr=c_thr_dec)
page = 16
max_len = -(-(args.seq + args.decode_tokens) // page) * page

dec = SL.build(params, cfg, spec_dec, sc_dec, mode="decode",
               n_slots=args.batch, max_len=max_len, page_size=page)
for i, prompt in enumerate(prompts):
    dec.submit(SL.Request(sample_id=i, prompt=prompt,
                          n_tokens=args.decode_tokens))
t0 = time.perf_counter()
gen = dec.run()
dt = time.perf_counter() - t0
n_decode = args.batch * (args.decode_tokens - 1)
s = dec.stats
print(f"decoded {args.decode_tokens} tokens x {args.batch} prompts in "
      f"{dt:.2f}s ({n_decode / dt:,.0f} decode tok/s on this host)")
print(f"decode realized q={s.realized_q:.3f} (per token)  "
      f"token exits: {s.n_exited}  stage-2 tokens: {s.n_stage2}  "
      f"stalls: {s.n_stalls}  mean bucket fill {s.mean_bucket_fill:.2f}")

# every stream equals the host-loop decode reference's
ref = SL.build(params, cfg, spec_dec, sc_dec, mode="decode", scheduler=None,
               host=True).generate(prompts, args.decode_tokens)
for i in range(args.batch):
    assert gen[i] == ref["tokens"][i].tolist(), f"decode drift, request {i}"
print("decode streams identical to the host-loop reference")
print("OK")
