"""Chip smoke: serve full-width qwen2-1.5b on a TPU through the continuous
paged scheduler that ``python -m repro.launch.serve --mode decode
--scheduler continuous --page-size 16`` runs, and check what comes out.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # stage-disaggregated serving vs one chip

One chip: the weights are random (from a seed) at the published widths and
the default bf16 dtypes. The exit threshold is calibrated on measured
stage-1 step-0 confidences so that about a quarter of the tokens are hard,
which makes both the exit branch and the ring/stage-2 branch run. Eight
requests (prompt 64, 32 new tokens, page size 16) are served twice: a cold
pass that compiles and a warm pass that is timed. Every greedy stream must
equal the host-loop oracle's; where one does not, the first divergence and
the oracle's top-2 logit gap there are printed, and it passes only as a
near-tie at bf16 resolution (it is counted and printed).

``--chips 4``: the same requests on one chip, then through the
p-proportional disaggregated placement (stage 1 on three chips, the ring
and stage 2 on the fourth); the streams must be equal, and the devices
each stage's state lives on are printed.

Everything runs in this one process. With no TPU it exits non-zero before
building anything. The numbers it prints are smoke values, not benchmark
numbers. The last line of stdout is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import warnings
from pathlib import Path

ARCH = "qwen2-1.5b"
N_REQUESTS, PROMPT_LEN, NEW_TOKENS, PAGE_SIZE = 8, 64, 32, 16
P_HARD = 0.25           # provisioned hard fraction: sizes stage 2 and c_thr
N_CALIB = 32            # prompts whose step-0 confidences calibrate c_thr
SEED = 0
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def _require(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


def _say(msg: str) -> None:
    print(f"smoke: {msg}", flush=True)


def build_model(cfg, seed: int = SEED):
    """Random EE params for ``cfg`` (jitted init: one program, on device)."""
    import jax
    from repro.core import early_exit as ee
    spec = ee.default_spec(cfg)
    params = jax.jit(lambda k: ee.init_ee_params(k, cfg, spec))(
        jax.random.PRNGKey(seed))
    return params, spec


def prompts_for(cfg, n: int, seed: int):
    import jax
    import numpy as np
    return np.asarray(jax.random.randint(jax.random.PRNGKey(seed),
                                         (n, PROMPT_LEN), 0, cfg.vocab))


def calibrate_c_thr(params, cfg, spec) -> float:
    """c_thr such that a (1 - P_HARD) share of measured step-0 decode
    confidences exits."""
    import jax
    from repro.core.exit_decision import calibrate_threshold
    from repro.runtime import serve_loop as SL
    conf = jax.jit(lambda p, t: SL.decode_step0_confidences(
        p, cfg, spec, t, PROMPT_LEN + NEW_TOKENS))(
            params, prompts_for(cfg, N_CALIB, SEED + 2))
    return calibrate_threshold(conf, 1.0 - P_HARD)


def build_scheduler(params, cfg, c_thr: float, placement=None):
    """The serve CLI's continuous paged decode scheduler, at this smoke's
    sizes."""
    from repro.core import early_exit as ee
    from repro.core.stage_mesh import stage2_capacity
    from repro.runtime import serve_api
    from repro.runtime import serve_loop as SL
    spec = ee.default_spec(cfg, c_thr=c_thr)
    sc = SL.ServeConfig(capacity=stage2_capacity(N_REQUESTS, P_HARD),
                        c_thr=c_thr)
    return serve_api.build(params, cfg, spec, sc, mode="decode",
                           scheduler="continuous", placement=placement,
                           n_slots=N_REQUESTS,
                           max_len=PROMPT_LEN + NEW_TOKENS,
                           page_size=PAGE_SIZE), spec, sc


def check_kernels(sched) -> None:
    """The Pallas kernels are what the tick runs: the backend resolved to
    the compiled kernels, and the compiled tick holds them."""
    from repro.kernels import dispatch
    backend = dispatch.kernel_backend()
    _say(f"kernel backend: {backend}")
    _require(backend == "pallas", f"kernel backend {backend!r}, not pallas")
    has = "tpu_custom_call" in sched.tick_hlo()
    _say(f"compiled pool tick contains tpu_custom_call: {has}")
    _require(has, "the compiled pool tick holds no Pallas kernel")


def bf16_ulp(x: float) -> float:
    import numpy as np
    return float(2.0 ** (np.floor(np.log2(max(abs(x), 1e-30))) - 7))


def compare_to_oracle(streams: dict, oracle: dict) -> int:
    """Every stream against the host-loop oracle's. Returns the number of
    divergences that are near-ties (top-2 gap within 2 bf16 ulps of the
    top logit); any other divergence fails."""
    import numpy as np
    near_ties = 0
    for i, sid in enumerate(sorted(streams)):
        got, want = np.asarray(streams[sid]), oracle["tokens"][i]
        diff = np.nonzero(got != want)[0]
        if diff.size == 0:
            continue
        t = int(diff[0])
        top2 = np.sort(np.asarray(oracle["logits"][i, t], np.float32))[-2:]
        gap, ulp = float(top2[1] - top2[0]), bf16_ulp(float(top2[1]))
        _say(f"divergence: sample {sid} token {t}: served {int(got[t])}, "
             f"oracle {int(want[t])}; oracle top-2 logit gap {gap!r} "
             f"({gap / ulp!r} bf16 ulps)")
        _require(gap <= 2 * ulp, f"sample {sid} diverges at token {t} "
                                 f"without a near-tie")
        near_ties += 1
    return near_ties


def count_compile_seconds() -> list:
    """A one-element list that accumulates the seconds of every backend
    compile from now on (persistent-cache hits do not compile)."""
    import jax
    total = [0.0]

    def on_event(event: str, secs: float, **_) -> None:
        if event == COMPILE_EVENT:
            total[0] += secs

    jax.monitoring.register_event_duration_secs_listener(on_event)
    return total


def one_chip_phase(cfg) -> None:
    import jax
    import numpy as np
    from repro.launch.serve import serve_requests
    from repro.runtime import serve_api

    params, spec0 = build_model(cfg)
    c_thr = calibrate_c_thr(params, cfg, spec0)
    _say(f"c_thr {c_thr!r} calibrated for p = {P_HARD} from {N_CALIB} "
         f"step-0 confidences")
    sched, spec, sc = build_scheduler(params, cfg, c_thr)
    prompts = prompts_for(cfg, N_REQUESTS, SEED + 1)

    t0 = time.perf_counter()
    cold = serve_requests(sched, prompts, NEW_TOKENS)
    cold_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    warm = serve_requests(sched, prompts, NEW_TOKENS, first_id=N_REQUESTS)
    warm_s = time.perf_counter() - t0
    _require([warm[N_REQUESTS + i] for i in range(N_REQUESTS)]
             == [cold[i] for i in range(N_REQUESTS)],
             "the warm pass's streams differ from the cold pass's")
    check_kernels(sched)

    st = sched.stats
    dev = jax.devices()[0]
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    _say("values below are smoke values, not benchmark numbers")
    _say(f"device_kind {dev.device_kind!r}, devices {len(jax.devices())}")
    _say(f"cold pass {cold_s!r} s (compiles included), warm pass "
         f"{warm_s!r} s")
    _say(f"goodput_tokens_per_s {N_REQUESTS * NEW_TOKENS / warm_s!r} "
         f"(warm pass, {N_REQUESTS} requests x {NEW_TOKENS} tokens)")
    _say(f"realized_q {st.realized_q!r}, n_exited {st.n_exited}, "
         f"n_decisions {st.n_decisions}, n_stage2 {st.n_stage2}, "
         f"n_stalls {st.n_stalls}, n_buckets {st.n_buckets}")
    _say(f"peak_bytes_in_use {peak!r}")
    _require(0 < st.n_exited < st.n_decisions,
             "both the exit and the stage-2 branch must run")

    oracle = serve_api.build(params, cfg, spec, sc, mode="decode",
                             scheduler=None, host=True).generate(
                                 prompts, NEW_TOKENS)
    near = compare_to_oracle(cold, oracle)
    n_equal = sum(np.array_equal(cold[i], oracle["tokens"][i])
                  for i in range(N_REQUESTS))
    _say(f"streams vs host-loop oracle: {n_equal}/{N_REQUESTS} equal, "
         f"{near} near-tie divergences")


def _device_ids(tree) -> list:
    import jax
    return sorted({d.id for leaf in jax.tree.leaves(tree)
                   for d in leaf.sharding.device_set})


def disaggregated_phase(cfg, n_chips: int) -> None:
    import jax
    from repro.launch.serve import make_placement, serve_requests

    devices = jax.devices()
    _require(len(devices) == n_chips,
             f"--chips {n_chips} but JAX sees {len(devices)} devices")
    params, spec0 = build_model(cfg)
    c_thr = calibrate_c_thr(params, cfg, spec0)
    _say(f"c_thr {c_thr!r} calibrated for p = {P_HARD}")
    prompts = prompts_for(cfg, N_REQUESTS, SEED + 1)

    single, _, _ = build_scheduler(params, cfg, c_thr)
    want = serve_requests(single, prompts, NEW_TOKENS)
    del single

    placement = make_placement(P_HARD, N_REQUESTS, devices=devices)
    _say(f"placement {placement}")
    split = (placement.ex1.n_devices, placement.ex2.n_devices)
    _say(f"stage split {split[0]}+{split[1]}")
    _require(split == (n_chips - 1, 1), f"expected a {n_chips - 1}+1 split")
    sched, _, _ = build_scheduler(params, cfg, c_thr, placement)
    got = serve_requests(sched, prompts, NEW_TOKENS)

    # state each stage's programs produce over that stage's placed params
    s1 = _device_ids(sched._c1)
    s2 = _device_ids(sched._pool)
    ring = _device_ids(sched.ring._buf)
    _say(f"stage 1 params/cache on devices {s1}; stage 2 params/KV pool "
         f"on {s2}; ring on {ring}")
    _require(s1 == sorted(d.id for d in placement.ex1.devices)
             and s2 == ring == sorted(d.id for d in placement.ex2.devices)
             and not set(s1) & set(s2), "a stage's state is misplaced")
    st = sched.stats
    _say(f"disaggregated: n_exited {st.n_exited}, n_decisions "
         f"{st.n_decisions}, n_stage2 {st.n_stage2}, n_stalls {st.n_stalls}")
    n_equal = sum(got[i] == want[i] for i in range(N_REQUESTS))
    _say(f"streams disaggregated vs one chip: {n_equal}/{N_REQUESTS} equal")
    _require(n_equal == N_REQUESTS,
             "disaggregated streams differ from the one-chip streams")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the stage-disaggregated phase")
    args = ap.parse_args(argv)

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: FAILED: needs a TPU; JAX's first device is "
              f"{dev.platform!r}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models.registry import get_arch
    cache = Path(enable_compile_cache())
    n_cached = len(list(cache.iterdir())) if cache.is_dir() else 0
    # a program that closes over the weights embeds them as constants,
    # gigabytes of host memory per compile: fail here, not out of memory
    warnings.filterwarnings("error", message="A large amount of constants")
    _say(f"device {dev.platform} {dev.device_kind!r} x{len(jax.devices())}")
    _say(f"compile cache {cache} ({n_cached} entries at start)")
    cfg = get_arch(ARCH)
    compile_s = count_compile_seconds()
    if args.chips == 1:
        one_chip_phase(cfg)
    else:
        disaggregated_phase(cfg, args.chips)
    _say(f"compile_s {compile_s[0]!r} (backend compiles, whole run; a smoke "
         f"value, not a benchmark number)")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
