"""The harness knows no architecture: a configuration file's ``program``
block and the reference it names say all that the harness needs.

* The three configuration files give the ArchConfig, reference sizes and
  weight names the harness gave them when it knew only dense Qwen
  decoders (the values below are that harness's, written out).
* A configuration with latent attention, sparse experts after a dense
  layer and an untied head (``data/tiny_mla_moe.json``, its reference
  ``data/mla_moe.py``) runs through the whole harness with no code of its
  own and comes out ``correct``.
* Renaming the vocabulary renames it on every vocabulary axis, tied or
  untied: the same model under other token ids.

    JAX_PLATFORMS=cpu python3 -m pytest -q benchmarks/chip/tests
"""
from __future__ import annotations

import copy
import json
import sys
import time
from pathlib import Path

import numpy as np
import pytest

CHIP = Path(__file__).resolve().parents[1]
DATA = Path(__file__).resolve().parent / "data"
sys.path[:0] = [str(CHIP), str(CHIP.parents[1] / "src"), str(CHIP / "tests")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import run  # noqa: E402
import tiny  # noqa: E402
from chipbench import system  # noqa: E402
from repro.core import early_exit as ee  # noqa: E402
from repro.models import transformer as T  # noqa: E402
from repro.models.config import ArchConfig, MLAConfig, MoEConfig  # noqa: E402

SEED = 2**31 + 7919
QWEN2 = ArchConfig(
    name="qwen2-1.5b", family="dense", n_layers=28, d_model=1536, n_heads=12,
    n_kv_heads=2, d_ff=8960, vocab=151936, head_dim=None, qkv_bias=True,
    qk_norm=False, rope_theta=1000000.0, tie_embeddings=True, norm_eps=1e-06,
    exit_layers=(14,), dtype="bfloat16", param_dtype="bfloat16")
QWEN3 = ArchConfig(
    name="qwen3-4b", family="dense", n_layers=36, d_model=2560, n_heads=32,
    n_kv_heads=8, d_ff=9728, vocab=151936, head_dim=128, qkv_bias=False,
    qk_norm=True, rope_theta=1000000.0, tie_embeddings=True, norm_eps=1e-06,
    exit_layers=(18,), dtype="bfloat16", param_dtype="bfloat16")
QWEN2_DIMS = {"n_layers": 28, "d_model": 1536, "n_heads": 12,
              "n_kv_heads": 2, "head_dim": 128, "d_ff": 8960,
              "vocab": 151936, "eps": 1e-06, "rope_theta": 1000000.0,
              "qkv_bias": True, "qk_norm": False, "exit": 14}
QWEN3_DIMS = {"n_layers": 36, "d_model": 2560, "n_heads": 32,
              "n_kv_heads": 8, "head_dim": 128, "d_ff": 9728,
              "vocab": 151936, "eps": 1e-06, "rope_theta": 1000000.0,
              "qkv_bias": False, "qk_norm": True, "exit": 18}
# the reference's name of each weight -> its path in the program's tree
BLOCK = ("backbone", "blocks", 0)
COMMON = {"embed": ("backbone", "embed", "table"),
          "exit_norm": ("exit_head", "norm", "scale"),
          "final_norm": ("backbone", "final_norm", "scale"),
          "norm1": BLOCK + ("norm1", "scale"), "wq": BLOCK + ("attn", "wq"),
          "wk": BLOCK + ("attn", "wk"), "wv": BLOCK + ("attn", "wv"),
          "wo": BLOCK + ("attn", "wo"), "norm2": BLOCK + ("norm2", "scale"),
          "w_gate": BLOCK + ("mlp", "wi_gate"),
          "w_up": BLOCK + ("mlp", "wi_up"), "w_down": BLOCK + ("mlp", "wo")}
BIAS = {k: BLOCK + ("attn", k) for k in ("bq", "bk", "bv")}
QK_NORM = {k: BLOCK + ("attn", k, "scale") for k in ("q_norm", "k_norm")}
FILES = {"qwen2-1.5b": (QWEN2, QWEN2_DIMS, {**COMMON, **BIAS}),
         "qwen2-1.5b-split3-1": (QWEN2, QWEN2_DIMS, {**COMMON, **BIAS}),
         "qwen3-4b": (QWEN3, QWEN3_DIMS, {**COMMON, **QK_NORM})}


def _conf(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _at(tree, path):
    for k in path:
        tree = tree[k]
    return tree


def _shapes(cfg):
    spec = ee.EarlyExitSpec(exit_layer=cfg.exit_layers[0])
    return jax.eval_shape(lambda k: ee.init_ee_params(k, cfg, spec),
                          jax.ShapeDtypeStruct((2,), jnp.uint32))


@pytest.mark.parametrize("name", sorted(FILES))
def test_configuration_file_maps_as_before(name):
    conf = _conf(CHIP / "configs" / f"{name}.json")
    want_cfg, want_dims, want_paths = FILES[name]
    cfg = system.arch_config(conf)
    assert cfg == want_cfg
    assert system.model_dims(conf, cfg) == want_dims
    tree = _shapes(cfg)
    w = system.reference_weights(conf, tree)
    flat = {k: v for k, v in w.items() if k != "layers"} | w["layers"]
    assert set(flat) == set(want_paths)
    for k, path in want_paths.items():
        assert flat[k] is _at(tree, path), k      # the same leaf, no copy


def test_reference_loaded_once():
    conf = _conf(CHIP / "configs" / "qwen3-4b.json")
    assert system.reference(conf) is system.reference(dict(conf))


# -- a configuration of another architecture, by files only ---------------

MLA_MOE = _conf(DATA / "tiny_mla_moe.json")


def test_program_block_builds_nested_configs():
    cfg = system.arch_config(MLA_MOE)
    assert (cfg.family, cfg.first_k_dense, cfg.dense_ff, cfg.d_ff) == \
        ("moe", 1, 192, 32)
    assert cfg.mla == MLAConfig(kv_lora_rank=32, q_lora_rank=0,
                                qk_nope_head_dim=16, qk_rope_head_dim=8,
                                v_head_dim=16)
    assert cfg.moe == MoEConfig(n_experts=8, top_k=2, d_ff_expert=32,
                                n_shared=1)
    assert not cfg.tie_embeddings
    hash(cfg)                               # static arguments of the jits


@pytest.mark.parametrize("program, bad", [
    ({"experts": 8}, "'experts'"),
    ({"moe": {"n_experts": 8, "top_k": 2, "d_ff_expert": 32, "shared": 1}},
     "'shared'"),
])
def test_unknown_program_key_raises(program, bad):
    conf = copy.deepcopy(MLA_MOE)
    conf["program"].update(program)
    with pytest.raises(KeyError, match=bad):
        system.arch_config(conf)


def test_published_nested_keys_set_their_groups():
    """DeepSeek's published MLA and expert keys set the ``mla`` and ``moe``
    groups: the file with those groups left out of its ``program`` block
    gives the same ArchConfig."""
    conf = copy.deepcopy(MLA_MOE)
    for group in ("mla", "moe", "first_k_dense"):
        del conf["program"][group]
    assert system.arch_config(conf) == system.arch_config(MLA_MOE)


@pytest.mark.parametrize("group, field, value", [
    ("mla", "kv_lora_rank", 16), ("moe", "top_k", 3),
    ("moe", "d_ff_expert", 64)])
def test_program_disagreeing_with_published_key_raises(group, field, value):
    conf = copy.deepcopy(MLA_MOE)
    conf["program"][group][field] = value
    with pytest.raises(ValueError, match=f"{group}.{field}"):
        system.arch_config(conf)


def _mla_moe_model(perm_seed=None):
    cfg = system.arch_config(MLA_MOE)
    spec = ee.EarlyExitSpec(exit_layer=MLA_MOE["serving"]["exit_layer"])
    perm = np.arange(cfg.vocab, dtype=np.int32) if perm_seed is None \
        else system.vocab_perm(perm_seed, cfg.vocab)
    return cfg, spec, system.make_weights(cfg, spec, 0, perm)


def test_mla_moe_reference_hooks_and_forward():
    cfg, spec, params = _mla_moe_model(7)
    ref = system.reference(MLA_MOE)
    a = system.model_dims(MLA_MOE, cfg)
    w = system.reference_weights(MLA_MOE, params)
    bb = params["backbone"]
    assert w["head"] is bb["head"] and w["embed"] is bb["embed"]["table"]
    assert w["dense"][0]["w_gate"] is bb["first"][0]["mlp"]["wi_gate"]
    assert w["layers"]["e_up"] is bb["blocks"][0]["moe"]["e_up"]
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 40), 0, cfg.vocab)
    want, _ = T.forward(bb, cfg, toks)
    got = ref.full_forward(a, w, toks[0])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want[0]),
                               rtol=1e-4, atol=1e-4)
    _, _, want_ex, _ = ee.stage1_prefill(params, cfg, spec, toks[:, :33])
    _, got_ex = ref.stage1(a, w, toks[0, :33], jnp.asarray([32]))
    np.testing.assert_allclose(np.asarray(got_ex[0]), np.asarray(want_ex[0]),
                               rtol=1e-4, atol=1e-4)


def test_mla_moe_served_through_the_harness():
    """``run_cell`` on the file as it is: the program's continuous paged
    scheduler serves it, some tokens go through stage 2 and the paged MLA
    pool, and the plain reference judges the streams correct."""
    out = run.run_cell(tiny.spec("offline", conf=MLA_MOE), SEED, 1.0, False,
                       t_start=time.perf_counter())
    assert out["correct"], out["checks"]
    assert out["metrics"]["tokens_per_s"]["value"] > 0
    assert 0.05 < out["realized_q"] < 0.95


# -- renaming the vocabulary ----------------------------------------------

def _tied_model(perm_seed=None):
    cfg = system.arch_config(tiny.TINY)
    spec = ee.EarlyExitSpec(exit_layer=tiny.TINY["serving"]["exit_layer"])
    perm = np.arange(cfg.vocab, dtype=np.int32) if perm_seed is None \
        else system.vocab_perm(perm_seed, cfg.vocab)
    return cfg, spec, system.make_weights(cfg, spec, 0, perm)


@pytest.mark.parametrize("model", [_tied_model, _mla_moe_model],
                         ids=["tied", "untied"])
def test_renamed_prompt_gives_renamed_logits(model):
    """Token r of a run is token ``perm[r]`` of the configuration's model:
    the renamed prompt under the renamed weights gives the original logits
    with the vocabulary axis permuted, at the exit head and at the final
    head."""
    cfg, spec, plain = model()
    _, _, renamed = model(SEED)
    perm = system.vocab_perm(SEED, cfg.vocab)
    toks = jax.random.randint(jax.random.PRNGKey(5), (1, 24), 0, cfg.vocab)
    renamed_toks = jnp.asarray(np.argsort(perm))[toks]
    want, _ = T.forward(plain["backbone"], cfg, toks)
    got, _ = T.forward(renamed["backbone"], cfg, renamed_toks)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(want)[..., perm], rtol=0, atol=1e-5)
    _, _, want_ex, _ = ee.stage1_prefill(plain, cfg, spec, toks)
    _, _, got_ex, _ = ee.stage1_prefill(renamed, cfg, spec, renamed_toks)
    np.testing.assert_allclose(np.asarray(got_ex),
                               np.asarray(want_ex)[..., perm], rtol=0,
                               atol=1e-5)
