"""The system under test, as the benchmark builds and drives it: the serving
program's continuous paged scheduler (``serve_api.build(mode="decode",
scheduler="continuous", page_size=...)``) at a configuration file's sizes,
over weights the benchmark makes from the seed.

The weights are the benchmark's own: one jitted call fills the program's
parameter tree (its structure from ``jax.eval_shape`` of the program's
init) with seeded values, and ``reference_weights`` hands the same arrays to
the plain reference under the reference's names. The program never makes a
number the reference reads.

Nothing here knows an architecture: the configuration file's ``program``
block says what the published keys do not (``arch_config``), and the
reference it names says how it reads the model (its ``dims`` and
``weights``), so a configuration of another architecture is files only.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import time
import typing
from pathlib import Path

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import early_exit as ee
from repro.core.stage_mesh import stage2_capacity
from repro.models.config import ArchConfig
from repro.runtime import serve_api
from repro.runtime import serve_loop as SL


# the published config's keys and the ArchConfig field each one sets
PUBLISHED = {"num_hidden_layers": "n_layers", "hidden_size": "d_model",
             "num_attention_heads": "n_heads",
             "num_key_value_heads": "n_kv_heads",
             "intermediate_size": "d_ff", "vocab_size": "vocab",
             "head_dim": "head_dim", "rope_theta": "rope_theta",
             "tie_word_embeddings": "tie_embeddings",
             "rms_norm_eps": "norm_eps",
             "first_k_dense_replace": "first_k_dense"}
FLOATS = ("rope_theta", "norm_eps")
# published keys that set a field of a nested group (DeepSeek's names);
# where the ``program`` block states the same field, the two must agree
PUBLISHED_NESTED = {"kv_lora_rank": ("mla", "kv_lora_rank"),
                    "q_lora_rank": ("mla", "q_lora_rank"),
                    "qk_nope_head_dim": ("mla", "qk_nope_head_dim"),
                    "qk_rope_head_dim": ("mla", "qk_rope_head_dim"),
                    "v_head_dim": ("mla", "v_head_dim"),
                    "n_routed_experts": ("moe", "n_experts"),
                    "num_experts_per_tok": ("moe", "top_k"),
                    "moe_intermediate_size": ("moe", "d_ff_expert"),
                    "n_shared_experts": ("moe", "n_shared")}


def _from_json(cls, kw: dict, where: str):
    """``cls(**kw)`` with each value as its field's type wants it: a JSON
    object for a dataclass field builds that dataclass by its own field
    names, a list becomes a tuple. A key that is not a field raises."""
    hints = typing.get_type_hints(cls)
    out = {}
    for k, v in kw.items():
        if k not in hints:
            raise KeyError(f"{where}: {k!r} is not a field of {cls.__name__}")
        if isinstance(v, dict):
            sub = [t for t in typing.get_args(hints[k])
                   if dataclasses.is_dataclass(t)]
            if not sub:
                raise TypeError(f"{where}: {k!r} is not a dataclass field")
            v = _from_json(sub[0], v, f"{where}.{k}")
        elif isinstance(v, list):
            v = tuple(v)
        out[k] = v
    return cls(**out)


def arch_config(conf: dict) -> ArchConfig:
    """The program's ArchConfig from a configuration file: the published
    config's keys (``PUBLISHED``; a dense decoder unless told otherwise),
    then every key of its ``program`` block by ArchConfig field name, which
    may override them (``family``, ``d_ff``, ...). A nested group (``mla``,
    ``moe``) takes the published keys of ``PUBLISHED_NESTED`` and the
    block's fields; a field stated both ways must agree."""
    where = f"{conf['name']} program"
    kw = {"name": conf["name"], "family": "dense",
          "exit_layers": [conf["serving"]["exit_layer"]]}
    if "torch_dtype" in conf:
        kw["dtype"] = kw["param_dtype"] = conf["torch_dtype"]
    for key, field in PUBLISHED.items():
        if key in conf:
            kw[field] = float(conf[key]) if field in FLOATS else conf[key]
    prog = dict(conf.get("program", {}))
    for key, (group, field) in PUBLISHED_NESTED.items():
        if key not in conf:
            continue
        value = 0 if conf[key] is None else conf[key]     # null: none
        stated = prog.get(group, {})
        if field in stated and stated[field] != value:
            raise ValueError(f"{where}: {group}.{field} {stated[field]!r} "
                             f"but the published {key} is {value!r}")
        prog[group] = {field: value, **stated}
    kw.update(prog)
    return _from_json(ArchConfig, kw, where)


REFERENCES = Path(__file__).resolve().parents[1] / "references"


@functools.lru_cache(maxsize=None)
def load_module(path: Path):
    """A benchmark file found by name (a reference, a metric's reader),
    loaded once per process."""
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reference(conf: dict):
    """The plain reference the configuration names
    (``references/<reference>.py``), loaded once per process."""
    return load_module((REFERENCES / f"{conf['reference']}.py").resolve())


def model_dims(conf: dict, cfg: ArchConfig) -> dict:
    """What the reference and the FLOP count need to know of the model, as
    the configuration's reference reads it (its ``dims``)."""
    return reference(conf).dims(cfg)


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
# an untied output head (d, V) is drawn like the tied table, so that its
# logits spread alike
HEAD = "backbone/head"


def _leaf(key, name: str, sd: jax.ShapeDtypeStruct):
    dt = sd.dtype
    if name.endswith("table") or name == HEAD:      # embedding, head
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
    renamed by ``perm`` on every vocabulary axis (the embedding's rows and,
    where the model is untied, the output head's columns). The traffic
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
            elif _path(p) == HEAD:
                x = jnp.take(x, perm, axis=1)
            leaves.append(x)
        return treedef.unflatten(leaves)

    return jax.jit(init)(jax.random.PRNGKey(seed32(weights_seed, 1)),
                         jnp.asarray(perm))


def reference_weights(conf: dict, params) -> dict:
    """The same arrays under the reference's names, as the configuration's
    reference maps them (its ``weights``; no copies)."""
    return reference(conf).weights(params)


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
