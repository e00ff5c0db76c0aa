"""DeepSeek-V2-Lite, one chip's share of EP4, through the harness.

* ``configs/deepseek-v2-lite-ep4.json`` states the published config.json
  and the one cut (``moe.n_held``), and maps onto the program's model.
* ``references/deepseek_v2.py`` agrees with the program's forward pass
  on seeded weights at smoke widths (``data/tiny_dsv2.json``: a held share
  of the experts, ``norm_topk_prob`` false, YaRN), a sound run through
  ``run.run_cell`` reads ``correct``, and each departure of the model
  left out of the program underneath the timed path reads not correct:
  YaRN's frequencies, the top-k weights renormalised, YaRN's softmax
  scale, one held expert.
* The new per-layer readers read the program's ``moe_load`` records.

    JAX_PLATFORMS=cpu python3 -m pytest -q benchmarks/chip/tests
"""
from __future__ import annotations

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
from chipbench import roofline, roofline_mla_moe as rmm, system  # noqa: E402
from repro.core import early_exit as ee  # noqa: E402
from repro.models import layers as L  # noqa: E402
from repro.models import mla as mla_mod  # noqa: E402
from repro.models import moe as moe_mod  # noqa: E402
from repro.models import transformer as T  # noqa: E402
from repro.models.config import MoEConfig, RopeScaling  # noqa: E402

SEED = 2**31 + 7919
TINY = json.loads((DATA / "tiny_dsv2.json").read_text())
CONF = json.loads((CHIP / "configs" / "deepseek-v2-lite-ep4.json").read_text())

# deepseek-ai/DeepSeek-V2-Lite config.json, the keys that say the shape
PUBLISHED = {
    "attention_bias": False, "first_k_dense_replace": 1,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 10944,
    "kv_lora_rank": 512, "max_position_embeddings": 163840,
    "model_type": "deepseek_v2", "moe_intermediate_size": 1408,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 64,
    "n_shared_experts": 2, "norm_topk_prob": False,
    "num_attention_heads": 16, "num_experts_per_tok": 6,
    "num_hidden_layers": 27, "num_key_value_heads": 16, "q_lora_rank": None,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 0.707, "mscale_all_dim": 0.707,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "rope_theta": 10000, "routed_scaling_factor": 1,
    "scoring_func": "softmax", "seq_aux": True, "tie_word_embeddings": False,
    "topk_group": 1, "topk_method": "greedy", "v_head_dim": 128,
    "vocab_size": 102400}


def test_configuration_states_the_published_model_and_its_cut():
    for k, v in PUBLISHED.items():
        assert CONF[k] == v, k
    cfg = system.arch_config(CONF)
    assert cfg.moe == MoEConfig(n_experts=64, top_k=6, d_ff_expert=1408,
                                n_shared=2, n_held=16, norm_topk_prob=False)
    assert cfg.rope_scaling == RopeScaling(**PUBLISHED["rope_scaling"])
    assert (cfg.n_layers, cfg.d_model, cfg.vocab, cfg.first_k_dense,
            cfg.dense_ff, cfg.tie_embeddings) == (27, 2048, 102400, 1, 10944,
                                                  False)
    assert cfg.default_exit_layers() == (CONF["serving"]["exit_layer"],)
    bench = json.loads((CHIP.parents[1] / "BENCHMARK.json").read_text())
    entry = next(c for c in bench["configs"] if c["name"] == CONF["name"])
    assert entry["reduced"] == ["moe.n_held"]
    shapes = jax.eval_shape(
        lambda k: ee.init_ee_params(k, cfg, ee.EarlyExitSpec(exit_layer=13)),
        jax.ShapeDtypeStruct((2,), jnp.uint32))
    blk = shapes["backbone"]["blocks"][0]
    assert blk["moe"]["router"].shape == (26, 2048, 64)
    assert blk["moe"]["e_gate"].shape == (26, 16, 2048, 1408)


def _tiny_model(perm_seed=None):
    cfg = system.arch_config(TINY)
    spec = ee.EarlyExitSpec(exit_layer=TINY["serving"]["exit_layer"])
    perm = np.arange(cfg.vocab, dtype=np.int32) if perm_seed is None \
        else system.vocab_perm(perm_seed, cfg.vocab)
    return cfg, spec, system.make_weights(cfg, spec, 0, perm)


def test_reference_matches_the_program_forward():
    cfg, spec, params = _tiny_model(7)
    assert cfg.moe.held < cfg.moe.n_experts and cfg.rope_scaling
    ref = system.reference(TINY)
    a = system.model_dims(TINY, cfg)
    w = system.reference_weights(TINY, params)
    bb = params["backbone"]
    assert w["layers"]["e_down"] is bb["blocks"][0]["moe"]["e_down"]
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 40), 0, cfg.vocab)
    want, _ = T.forward(bb, cfg, toks)
    got = ref.full_forward(a, w, toks[0])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want[0]),
                               rtol=1e-4, atol=1e-4)
    _, _, want_ex, _ = ee.stage1_prefill(params, cfg, spec, toks[:, :33])
    _, got_ex = ref.stage1(a, w, toks[0, :33], jnp.asarray([32]))
    np.testing.assert_allclose(np.asarray(got_ex[0]), np.asarray(want_ex[0]),
                               rtol=1e-4, atol=1e-4)


def test_reference_yarn_is_the_published_formula():
    """The reference's own YaRN at the published sizes: frequencies kept
    below dimension 10, divided by 40 from 23, and the 1.5896 scale."""
    a = system.model_dims(CONF, system.arch_config(CONF))
    ref = system.reference(CONF)
    inv = np.asarray(ref.rope_inv_freq(a))
    plain = 1.0 / 10000.0 ** (np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(inv[:10], plain[:10], rtol=1e-6)
    np.testing.assert_allclose(inv[23:], plain[23:] / 40, rtol=1e-6)
    assert ref.softmax_scale(a) * np.sqrt(192) == pytest.approx(1.5896,
                                                                abs=1e-4)


def _run(spec=None):
    spec = spec or tiny.spec("offline", conf=TINY)
    return run.run_cell(spec, SEED, 1.0, False, t_start=time.perf_counter())


def test_sound_run_is_correct():
    out = _run()
    assert out["correct"], out["checks"]
    assert 0.05 < out["realized_q"] < 0.95


def _yarn_left_out(mp):
    mp.setattr(mla_mod, "apply_rope",
               lambda x, pos, theta, scaling=None: L.apply_rope(x, pos, theta))


def _topk_renormalised(mp):
    orig = moe_mod.route

    def route(params, cfg, x):
        idx, w, aux = orig(params, cfg, x)
        return idx, w / jnp.sum(w, -1, keepdims=True), aux
    mp.setattr(moe_mod, "route", route)


def _mscale_left_out(mp):
    mp.setattr(mla_mod, "_yarn_softmax_factor", lambda cfg: 1.0)


def _held_expert_zeroed(mp):
    orig = moe_mod.moe_fwd

    def moe_fwd(params, cfg, x, **kw):
        params = dict(params, e_down=params["e_down"].at[0].set(0))
        return orig(params, cfg, x, **kw)
    mp.setattr(moe_mod, "moe_fwd", moe_fwd)


FAULTS = {"yarn_left_out": _yarn_left_out,
          "topk_renormalised": _topk_renormalised,
          "mscale_left_out": _mscale_left_out,
          "held_expert_zeroed": _held_expert_zeroed}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_is_not_correct(fault, monkeypatch):
    FAULTS[fault](monkeypatch)
    out = _run()
    assert not out["correct"], out["checks"]
    assert out["checks"]["max_logit_gap"]["value"] > \
        out["checks"]["max_logit_gap"]["limit"]


def test_traced_run_reads_the_new_metrics(monkeypatch):
    """A traced run reads every new reader that the CPU trace can feed: the
    program's ``moe_load`` records (expert load, MFU), the programs' device
    time and the scheduler's spans and counters shared with the other cells;
    the ragged-dot and paged-kernel ops exist on the chip."""
    peaks = roofline.peaks
    monkeypatch.setattr(roofline, "peaks",
                        lambda kind: peaks("TPU v5 lite"))
    bench = json.loads((CHIP.parents[1] / "BENCHMARK.json").read_text())
    spec = tiny.spec("offline", conf=TINY)
    spec["per_layer"] = [m for m in bench["per_layer"]
                         if m["name"].endswith(".dsv2-lite")]
    assert len(spec["per_layer"]) == 12
    out = run.run_cell(spec, SEED, 1.5, True, t_start=time.perf_counter())
    got = {k: v["value"] for k, v in out["metrics"].items()}
    for name in ("expert_load", "decode_mfu", "tick_ms", "bucket_ms",
                 "device_idle", "host_ms", "admit_share", "bucket_fill",
                 "eager_bucket_share"):
        assert got[f"{name}.dsv2-lite"] > 0, name
    assert got["expert_load.dsv2-lite"] >= 1.0
    loads = rmm.moe_loads()
    assert {a["program"] for a in loads} == {"tick", "bucket", "prefill"}


def test_grouped_matmul_and_mla_arithmetic():
    m = system.model_dims(CONF, system.arch_config(CONF))
    f, b = rmm.grouped_matmul_cost(m, held_rows=48, touched=15)
    assert f == 48 * 6 * 2048 * 1408
    assert b == 2 * (15 * 3 * 2048 * 1408 + 48 * 3 * (2048 + 1408))
    # the absorbed decode and the decompressed form multiply by the same
    # weights; per position the first works at the latent's width
    assert rmm.mla_token_flops(m, 0.0, True) == \
        rmm.mla_token_flops(m, 0.0, False)
    per_pos = [rmm.mla_token_flops(m, 1.0, a) - rmm.mla_token_flops(m, 0.0, a)
               for a in (True, False)]
    assert per_pos == [2 * 16 * (512 + 64 + 512), 2 * 16 * (128 + 64 + 128)]
    one = rmm.decode_flops(m, 0, 27, 100.0, 1.5)
    assert one == pytest.approx(
        rmm.decode_flops(m, 0, 13, 100.0, 1.5)
        + rmm.decode_flops(m, 13, 27, 100.0, 1.5) - 2 * 102400 * 2048)
    assert rmm.paged_gather_append_bytes(16, 160, 16, (512, 64), 2, 10.0) \
        == 16 * (170 * 16 * 576 * 2 + 576 * 2)
