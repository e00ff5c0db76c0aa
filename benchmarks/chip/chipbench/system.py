"""The system under test, as the benchmark builds and drives it: the serving
program's continuous paged scheduler (``serve_api.build(mode="decode",
scheduler="continuous", page_size=...)``) at a configuration file's sizes,
over weights the benchmark makes from the seed.

The weights are the benchmark's own: one jitted call fills the program's
parameter tree (its structure from ``jax.eval_shape`` of the program's
init) with seeded values, and ``reference_weights`` hands the same arrays to
the plain reference under the reference's names. The program never makes a
number the reference reads.
"""
from __future__ import annotations

import time

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import early_exit as ee
from repro.core.stage_mesh import stage2_capacity
from repro.models.config import ArchConfig
from repro.runtime import serve_api
from repro.runtime import serve_loop as SL


def arch_config(conf: dict) -> ArchConfig:
    """The program's ArchConfig from a configuration file (the published
    config's keys, plus the two program switches under ``program``)."""
    prog = conf["program"]
    return ArchConfig(
        name=conf["name"], family="dense",
        n_layers=conf["num_hidden_layers"], d_model=conf["hidden_size"],
        n_heads=conf["num_attention_heads"],
        n_kv_heads=conf["num_key_value_heads"],
        d_ff=conf["intermediate_size"], vocab=conf["vocab_size"],
        head_dim=conf.get("head_dim"), qkv_bias=prog["qkv_bias"],
        qk_norm=prog["qk_norm"], rope_theta=float(conf["rope_theta"]),
        tie_embeddings=conf["tie_word_embeddings"],
        norm_eps=float(conf["rms_norm_eps"]),
        exit_layers=(conf["serving"]["exit_layer"],),
        dtype=conf["torch_dtype"], param_dtype=conf["torch_dtype"])


def model_dims(cfg: ArchConfig) -> dict:
    """What the reference and the FLOP count need to know of the model."""
    return {"n_layers": cfg.n_layers, "d_model": cfg.d_model,
            "n_heads": cfg.n_heads, "n_kv_heads": cfg.n_kv_heads,
            "head_dim": cfg.resolved_head_dim, "d_ff": cfg.d_ff,
            "vocab": cfg.vocab, "eps": cfg.norm_eps,
            "rope_theta": cfg.rope_theta, "qkv_bias": cfg.qkv_bias,
            "qk_norm": cfg.qk_norm, "exit": cfg.exit_layers[0]}


def seed32(seed: int, salt: int) -> int:
    """A 31-bit PRNG seed from any whole number (seeds above 2**31 too)."""
    st = np.random.SeedSequence([seed % (1 << 63), seed >> 63, salt])
    return int(st.generate_state(1)[0] & 0x7FFFFFFF)


# Tied embedding rows at std 0.1 give logits of std about 0.1 * sqrt(d)
# (3.9 at d = 1536): exit confidences then spread from a few percent to
# near one, as a trained model's do. At 0.02 every confidence sat near
# 20 / V and the hard share swung from 0.1 to 0.45 over a 15 % change of
# the threshold (my chip runs), so no threshold held a share steady.
EMBED_STD = 0.1


def _leaf(key, name: str, sd: jax.ShapeDtypeStruct):
    dt = sd.dtype
    if name.endswith("table"):                      # embedding
        return (EMBED_STD * jax.random.normal(key, sd.shape, jnp.float32)
                ).astype(dt)
    if name.endswith("scale"):                      # RMSNorm gains
        return (1.0 + 0.1 * jax.random.normal(key, sd.shape, jnp.float32)
                ).astype(dt)
    if name.split("/")[-1] in ("bq", "bk", "bv"):   # q/k/v biases
        return (0.1 * jax.random.normal(key, sd.shape, jnp.float32)
                ).astype(dt)
    std = 1.0 / np.sqrt(sd.shape[-2])               # matrices: (.., in, out)
    return (std * jax.random.truncated_normal(key, -2.0, 2.0, sd.shape,
                                              jnp.float32)).astype(dt)


def _path(p) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in p)


def vocab_perm(seed: int, vocab: int) -> np.ndarray:
    """The run's renaming of the vocabulary: row r of the run's embedding
    is row ``perm[r]`` of the configuration's."""
    rng = np.random.default_rng(
        np.random.SeedSequence([seed % (1 << 63), seed >> 63, 2]))
    return rng.permutation(vocab).astype(np.int32)


def make_weights(cfg: ArchConfig, spec, weights_seed: int, perm):
    """Every parameter, on the device, in the served dtype, from one jitted
    call: the configuration's model (``weights_seed``) with its vocabulary
    renamed by ``perm`` (the tied embedding's rows permuted). The traffic
    renames its tokens alike, so every seed runs the same model on the same
    requests under other token ids."""
    shapes = jax.eval_shape(lambda k: ee.init_ee_params(k, cfg, spec),
                            jax.ShapeDtypeStruct((2,), jnp.uint32))
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def init(key, perm):
        leaves = []
        for i, (p, sd) in enumerate(flat):
            x = _leaf(jax.random.fold_in(key, i), _path(p), sd)
            if _path(p).endswith("embed/table"):
                x = jnp.take(x, perm, axis=0)
            leaves.append(x)
        return treedef.unflatten(leaves)

    return jax.jit(init)(jax.random.PRNGKey(seed32(weights_seed, 1)),
                         jnp.asarray(perm))


def reference_weights(params) -> dict:
    """The same arrays under the reference's names (no copies)."""
    bb = params["backbone"]
    blk = bb["blocks"][0]
    at, mlp = blk["attn"], blk["mlp"]
    lw = {"norm1": blk["norm1"]["scale"], "wq": at["wq"], "wk": at["wk"],
          "wv": at["wv"], "wo": at["wo"], "norm2": blk["norm2"]["scale"],
          "w_gate": mlp["wi_gate"], "w_up": mlp["wi_up"],
          "w_down": mlp["wo"]}
    for k in ("bq", "bk", "bv"):
        if k in at:
            lw[k] = at[k]
    for k in ("q_norm", "k_norm"):
        if k in at:
            lw[k] = at[k]["scale"]
    return {"embed": bb["embed"]["table"], "layers": lw,
            "exit_norm": params["exit_head"]["norm"]["scale"],
            "final_norm": bb["final_norm"]["scale"]}


class WallClock:
    """The scheduler's clock: ``now()`` is wall seconds since the window's
    origin (negative before it) and ``advance_to`` sleeps. It never skips
    idle time."""

    def __init__(self):
        self.origin = time.perf_counter()

    def set_origin(self, t_perf: float) -> None:
        self.origin = t_perf

    def now(self) -> float:
        return time.perf_counter() - self.origin

    def advance_to(self, t: float) -> None:
        gap = t - self.now()
        if gap > 0:
            time.sleep(gap)


def stage2_rows(conf: dict, mix: dict) -> int:
    """Rows of one stage-2 bucket: the program's provisioning for the
    mix's hard share over the slot pool."""
    return stage2_capacity(conf["serving"]["n_slots"], mix["p_hard"])


def placement(conf: dict, mix: dict, devices):
    """The configuration's stage placement: none (both stages on one chip)
    unless its serving block names a ``split`` {"chips1", "chips2"}; then
    the program's disaggregated placement, stage 1 on the first ``chips1``
    devices and stage 2 on the next ``chips2``."""
    split = conf["serving"].get("split")
    if split is None:
        return None
    from repro.launch.serve import make_placement
    return make_placement(mix["p_hard"], conf["serving"]["n_slots"],
                          split["chips1"], split["chips2"], devices)


def build(conf: dict, mix: dict, params, cfg: ArchConfig, clock, devices):
    """The continuous paged scheduler at the configuration's sizes, on the
    cell's devices."""
    srv = conf["serving"]
    spec = ee.EarlyExitSpec(exit_layer=srv["exit_layer"], c_thr=1.0)
    sc = SL.ServeConfig(capacity=stage2_rows(conf, mix),
                        queue_depth=srv["queue_depth"], c_thr=1.0)
    return serve_api.build(params, cfg, spec, sc, mode="decode",
                           scheduler="continuous",
                           placement=placement(conf, mix, devices),
                           n_slots=srv["n_slots"], max_len=srv["max_len"],
                           page_size=srv["page_size"],
                           n_pages=srv["n_pages"], clock=clock)


def submit(sched, reqs, origin_shift: float = 0.0) -> None:
    from repro.runtime.scheduler import Request
    for r in reqs:
        sched.submit(Request(sample_id=r.sid, prompt=r.prompt,
                             n_tokens=r.n_tokens,
                             arrival_time=r.due + origin_shift))


def warm_up(sched, warm_reqs, c_thr: float) -> None:
    """Drive every program the window will use (each prefill chunk shape,
    the tick, the stage-2 bucket, unpark, page alloc and free) with every
    token sent to stage 2, then set the exit threshold."""
    sched.set_c_thr(1.0)                 # nothing exits: every path runs
    submit(sched, warm_reqs, origin_shift=sched.clock.now() - 1.0)
    sched.drain()
    sched.set_c_thr(c_thr)


def harvest(sched) -> None:
    """Fill every stage-2 token value still pending on the device."""
    while sched._pending:
        sched._harvest_one()
