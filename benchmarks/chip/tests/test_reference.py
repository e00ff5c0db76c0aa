"""The plain reference against the program's own full forward pass, at
smoke widths on the CPU, in float32: the Qwen2 form (q/k/v bias), the
Qwen3 form (RMSNorm on q and k, head size decoupled from d_model), and the
exit head after the exit layer.

    JAX_PLATFORMS=cpu python3 -m pytest -q benchmarks/chip/tests
"""
from __future__ import annotations

import sys
from pathlib import Path

import numpy as np
import pytest

CHIP = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(CHIP), str(CHIP.parents[1] / "src"), str(CHIP / "tests")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from chipbench import system  # noqa: E402
from repro.core import early_exit as ee  # noqa: E402
from repro.models import transformer as T  # noqa: E402
import tiny  # noqa: E402

sys.path.insert(0, str(CHIP / "references"))
import qwen_dense as R  # noqa: E402

FORMS = {
    "qwen2": {"program": {"qkv_bias": True, "qk_norm": False}},
    "qwen3": {"program": {"qkv_bias": False, "qk_norm": True},
              "head_dim": 24},
}


@pytest.fixture(params=sorted(FORMS))
def model(request):
    conf = dict(tiny.TINY, **FORMS[request.param])
    cfg = system.arch_config(conf)
    spec = ee.EarlyExitSpec(exit_layer=conf["serving"]["exit_layer"])
    params = system.make_weights(cfg, spec, 0, system.vocab_perm(7, cfg.vocab))
    return conf, cfg, spec, params


def test_full_forward_matches_program(model):
    conf, cfg, _, params = model
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 40), 0, cfg.vocab)
    want, _ = T.forward(params["backbone"], cfg, toks)
    got = R.full_forward(system.model_dims(conf, cfg),
                         system.reference_weights(conf, params), toks[0])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want[0]),
                               rtol=1e-4, atol=1e-4)


def test_exit_head_matches_program(model):
    conf, cfg, spec, params = model
    toks = jax.random.randint(jax.random.PRNGKey(2), (1, 33), 0, cfg.vocab)
    _, _, want, _ = ee.stage1_prefill(params, cfg, spec, toks)
    rows = jnp.asarray([32])
    _, got = R.stage1(system.model_dims(conf, cfg),
                      system.reference_weights(conf, params), toks[0], rows)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]),
                               rtol=1e-4, atol=1e-4)


def test_exit_gap_keys_are_zero_and_counted(model):
    """A position without stage-2 keys still takes softmax weight (score 0
    against a zero key) and adds nothing: dropping it from the mask instead
    changes the result."""
    conf, cfg, _, params = model
    a = system.model_dims(conf, cfg)
    w = system.reference_weights(conf, params)
    toks = jax.random.randint(jax.random.PRNGKey(3), (20,), 0, cfg.vocab)
    h, _ = R.stage1(a, w, toks, jnp.asarray([19]))
    keep_all = jnp.ones(20, bool)
    keep_gap = keep_all.at[10].set(False)
    rows = jnp.asarray([19])
    full = R.stage2(a, w, h, keep_all, rows)
    gap = R.stage2(a, w, h, keep_gap, rows)
    assert float(jnp.max(jnp.abs(full - gap))) > 1e-4
