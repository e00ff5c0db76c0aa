"""Sizing rehearsal without a chip: compile a configuration's pool tick,
stage-2 bucket and largest prefill chunk for a described TPU v5e
(``v5e:2x2``), at the configuration's slot count, and print
``memory_analysis()`` of each.

    JAX_PLATFORMS=cpu python3 benchmarks/chip/rehearse.py qwen3-4b \
        [--n-slots 24] [--traffic offline.p25]

A configuration whose serving block names a ``split`` is rehearsed as the
program runs it then: stage 1 (the composed tick and the prefill) on the
first ``chips1`` described chips, the stage-2 bucket on the next
``chips2``, with the byte counts per chip.

Nothing runs, so it says nothing about time; it shows what the chip's
compiler refuses and how many bytes each program needs besides the
weights and the pools it is handed. The kernel backend is pinned to the
compiled Pallas kernels for the tick and the bucket (off a TPU the program
would pick its jnp references); the prefill's attention takes the jnp
path, as the program does whenever JAX's default backend is the CPU.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("config")
    ap.add_argument("--n-slots", type=int, default=None)
    ap.add_argument("--n-pages", type=int, default=None)
    ap.add_argument("--traffic", default="offline.p25")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [str(HERE), str(ROOT / "src")]
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from chipbench import system
    from repro.core import early_exit as ee
    from repro.kernels import dispatch
    from repro.runtime import scheduler as SCH
    from repro.runtime import serve_loop as SL

    jax.config.update("jax_enable_compilation_cache", False)
    with open(HERE / "configs" / f"{args.config}.json") as f:
        conf = json.load(f)
    with open(HERE / "traffic" / f"{args.traffic}.json") as f:
        mix = json.load(f)
    srv = conf["serving"]
    if args.n_slots:
        srv["n_slots"] = args.n_slots
    if args.n_pages:
        srv["n_pages"] = args.n_pages
    cfg = system.arch_config(conf)
    spec = ee.EarlyExitSpec(exit_layer=srv["exit_layer"])
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    dispatch.kernel_backend = lambda backend=None: "pallas"
    shapes = jax.eval_shape(lambda k: ee.init_ee_params(k, cfg, spec),
                            jax.ShapeDtypeStruct((2,), jnp.uint32))
    pl = system.placement(conf, mix, topo.devices)
    if pl is None:
        one = SingleDeviceSharding(topo.devices[0])
        io1 = io2 = lambda lead: one
        params = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one),
            shapes)
    else:
        # described chips hold no buffers: a stage's placement of a tree
        # becomes the same shapes under that stage's shardings
        for ex in (pl.ex1, pl.ex2):
            ex._transfer = lambda tree, shard_of: jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                               sharding=shard_of(x)), tree)
        io1 = lambda lead: pl.ex1.sharding(pl.ex1._io_spec(lead))
        io2 = lambda lead: pl.ex2.sharding(pl.ex2._io_spec(lead))
        split = ee.split_params
        ee.split_params = lambda c, s, p: jax.eval_shape(
            lambda q: split(c, s, q), p)
        params = shapes
    fns = SL.decode_stage_fns(params, cfg, spec, pl, page_size=srv["page_size"])
    if pl is not None:
        params = fns.prefill.args[0]            # as placed on stage 1
    n, L, page = srv["n_slots"], srv["max_len"], srv["page_size"]
    M = L // page
    C = system.stage2_rows(conf, mix)
    S_max = max(mix["prompt"]["buckets"])
    chunk = 1 << (int(mix["max_equal_run"]).bit_length() - 1)

    def put(tree, io):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=io(x.shape[0] if x.ndim else 0)),
            tree)

    caches = jax.eval_shape(lambda t: fns.prefill(t, L),
                            jax.ShapeDtypeStruct((1, S_max), jnp.int32))[1]
    c1_row, rows_row = jax.eval_shape(fns.split, caches)
    c1 = put(jax.eval_shape(lambda r: SCH.seg_pool_like(r, n), c1_row), io1)
    pool = put(jax.eval_shape(
        lambda r: fns.pool_init(r, srv["n_pages"] + 1), rows_row), io2)
    i32 = lambda io, *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=io(s[0]))
    row = {"h": jax.ShapeDtypeStruct((cfg.d_model,), cfg.act_dtype()),
           "cache": jax.ShapeDtypeStruct((M,), jnp.int32),
           "step": jax.ShapeDtypeStruct((), jnp.int32)}
    ring = put(jax.eval_shape(
        lambda: SCH.ring_init(srv["queue_depth"] * C, row)), io2)
    lanes = (i32(io1, n, 1), c1, i32(io1, n),
             jax.ShapeDtypeStruct((n,), jnp.bool_, sharding=io1(n)),
             i32(io1, n), i32(io1, n))
    s1 = SCH._tick_fn(fns.s1_raw)
    tick = (("pool tick (fused)", lambda: SCH._pool_tick_fused.lower(
        *lanes, ring, i32(io1, n, M), 0.5, s1=s1, backend="pallas"))
        if pl is None else
        (f"pool tick (composed, {pl.ex1.n_devices} chips)",
         lambda: SCH._pool_tick.lower(*lanes, 0.5, s1=s1, backend="pallas",
                                      mesh=pl.ex1.mesh)))
    programs = dict([tick, (
        "stage-2 bucket", lambda: fns.s2_paged.func.lower(
            *fns.s2_paged.args,
            jax.ShapeDtypeStruct((C, cfg.d_model), cfg.act_dtype(),
                                 sharding=io2(C)),
            i32(io2, C, M), i32(io2, C), pool)), (
        f"prefill ({chunk} x {S_max})", lambda: fns.prefill.func.lower(
            *fns.prefill.args, i32(io1, chunk, S_max), max_len=L))])
    weights = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))
    pools = sum(x.size * x.dtype.itemsize
                for x in jax.tree.leaves((c1, pool, ring)))
    if pl is not None:
        print(f"split: stage 1 on {pl.ex1.n_devices} chips, stage 2 on "
              f"{pl.ex2.n_devices}; per-chip bytes below are the program's "
              "own, the totals are whole trees")
    print(f"{args.config}: n_slots {n}, n_pages {srv['n_pages']}, bucket "
          f"{C}; weights {weights / 1e9:.3f} GB, slot pool + page pool + "
          f"ring {pools / 1e9:.3f} GB")
    for name, lower in programs.items():
        compiled = lower().compile()
        ma = compiled.memory_analysis()
        has = "tpu_custom_call" in compiled.as_text()
        print(f"  {name}: temp {ma.temp_size_in_bytes / 1e9:.3f} GB, "
              f"argument {ma.argument_size_in_bytes / 1e9:.3f} GB, output "
              f"{ma.output_size_in_bytes / 1e9:.3f} GB, alias "
              f"{ma.alias_size_in_bytes / 1e9:.3f} GB; Pallas kernel "
              f"present: {has}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
