"""Per-architecture smoke tests (reduced configs) + decode/prefill
consistency for the cache machinery."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import early_exit as ee
from repro.models import transformer as T
from repro.models.registry import get_smoke, list_archs

from conftest import assert_finite


def _frontend(cfg, batch, key):
    if cfg.frontend == "vit_stub":
        return jax.random.normal(key, (batch, cfg.n_frontend_tokens,
                                       cfg.d_model)).astype(cfg.act_dtype())
    if cfg.encdec:
        return jax.random.normal(key, (batch, 8, cfg.d_model)
                                 ).astype(cfg.act_dtype())
    return None


@pytest.mark.parametrize("arch", list_archs())
def test_smoke_forward_and_train_step(arch):
    """One forward + one grad step on the reduced config: shapes + finite."""
    cfg = get_smoke(arch)
    spec = ee.default_spec(cfg)
    params = ee.init_ee_params(jax.random.PRNGKey(0), cfg, spec)
    B, S = 2, 16
    key = jax.random.PRNGKey(1)
    tokens = jax.random.randint(key, (B, S), 0, cfg.vocab)
    labels = jax.random.randint(jax.random.fold_in(key, 1), (B, S), 0,
                                cfg.vocab)
    fe = _frontend(cfg, B, jax.random.fold_in(key, 2))

    eh, fh, aux = ee.forward_train(params, cfg, spec, tokens,
                                   frontend_embeds=fe)
    assert eh.shape == (B, S, cfg.d_model)
    assert fh.shape == (B, S, cfg.d_model)
    assert_finite(fh, f"{arch} final_hidden")

    from repro.core import losses

    def loss_fn(p):
        eh, fh, aux = ee.forward_train(p, cfg, spec, tokens,
                                       frontend_embeds=fe)
        loss, _ = losses.branchynet_joint_loss(p, cfg, eh, fh, labels,
                                               spec.loss_weights, aux=aux)
        return loss

    loss, grads = jax.value_and_grad(loss_fn)(params)
    assert bool(jnp.isfinite(loss))
    assert_finite(grads, f"{arch} grads")


@pytest.mark.parametrize("arch", list_archs())
def test_smoke_serve_batch(arch):
    """The full EE pipeline (stage1 -> decision -> buffer -> stage2 ->
    merge) on the reduced config."""
    cfg = get_smoke(arch)
    spec = ee.default_spec(cfg, c_thr=0.5)
    params = ee.init_ee_params(jax.random.PRNGKey(0), cfg, spec)
    B, S = 4, 12
    tokens = jax.random.randint(jax.random.PRNGKey(2), (B, S), 0, cfg.vocab)
    fe = _frontend(cfg, B, jax.random.PRNGKey(3))
    out = ee.serve_batch(params, cfg, spec, tokens, frontend_embeds=fe)
    assert out["logits"].shape == (B, cfg.vocab)
    assert out["exit_mask"].shape == (B,)
    assert int(out["overflow"]) == 0
    assert_finite(out["logits"], f"{arch} serve logits")


def _held_share(arch):
    """DeepSeek-V2-Lite's layout with YaRN and a layer that holds 3 of 8
    routed experts (one holder's share under expert parallelism)."""
    cfg = get_smoke(arch)
    return cfg.replace(moe=dataclasses.replace(cfg.moe, n_experts=8,
                                               n_held=3))


@pytest.mark.parametrize("arch", ["qwen2-1.5b", "qwen3-4b", "mamba2-130m",
                                  "recurrentgemma-9b", "deepseek-v2-lite-16b",
                                  "grok-1-314b", "deepseek-v2-lite-16b:held"])
def test_decode_matches_forward(arch):
    """prefill(t[:n]) + decode_step(t[n]) logits == forward(t[:n+1]) last
    logits — the cache machinery is exact (fp32 smoke configs)."""
    name, _, held = arch.partition(":")
    cfg = _held_share(name) if held else get_smoke(name)
    if held:
        assert cfg.moe.held < cfg.moe.n_experts and cfg.rope_scaling
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    S = 9
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, S + 1), 0,
                                cfg.vocab)
    full, _ = T.forward(params, cfg, tokens)                # (1, S+1, V)
    logits_p, caches, _ = T.prefill(params, cfg, tokens[:, :S],
                                    max_len=S + 4)
    nxt, caches = T.decode_step(params, cfg, tokens[:, S:S + 1], caches,
                                jnp.int32(S))
    np.testing.assert_allclose(np.asarray(nxt[0]), np.asarray(full[0, S]),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("arch", ["qwen2-7b", "seamless-m4t-medium",
                                  "internvl2-2b"])
def test_staged_equals_unstaged(arch):
    """stage1 + stage2 composition == single-pass forward_hidden."""
    cfg = get_smoke(arch)
    spec = ee.default_spec(cfg)
    params = ee.init_ee_params(jax.random.PRNGKey(0), cfg, spec)
    B, S = 2, 8
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab)
    fe = _frontend(cfg, B, jax.random.PRNGKey(2))

    h, _, exit_logits, memory = ee.stage1_prefill(params, cfg, spec, tokens,
                                                  frontend_embeds=fe)
    final_logits, _ = ee.stage2_prefill(params, cfg, spec, h, memory=memory)

    fh, _ = T.forward_hidden(params["backbone"], cfg, tokens,
                             frontend_embeds=fe)
    want = T.head(params["backbone"], cfg, fh[:, -1])
    np.testing.assert_allclose(np.asarray(final_logits), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_cache_shapes_match_init(tiny_cfg):
    shapes = T.cache_shapes(tiny_cfg, 3, 16)
    real = T.init_cache(tiny_cfg, 3, 16)
    js, jr = jax.tree.leaves(shapes), jax.tree.leaves(real)
    assert len(js) == len(jr)
    for s, r in zip(js, jr):
        assert tuple(s.shape) == tuple(r.shape), (s.shape, r.shape)
        assert s.dtype == r.dtype


def test_split_caches_on_shapes_and_arrays(tiny_cfg, tiny_spec):
    for caches in (T.cache_shapes(tiny_cfg, 2, 8),
                   T.init_cache(tiny_cfg, 2, 8)):
        s1, s2 = ee.split_caches(tiny_cfg, tiny_spec, caches)
        n1 = jax.tree.leaves(s1["blocks"])[0].shape[0]
        n2 = jax.tree.leaves(s2["blocks"])[0].shape[0]
        assert n1 + n2 == tiny_cfg.n_layers  # pattern len 1 => superblocks
