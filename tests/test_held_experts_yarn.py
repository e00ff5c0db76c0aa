"""DeepSeek-V2-Lite's departures from the repo's first MoE and MLA layers:
the dropless layer over a held share of the experts, the
``norm_topk_prob`` switch, YaRN rope scaling, and the MoE load counters the
serving programs return."""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.archs import DEEPSEEK_V2_LITE
from repro.models import layers as L
from repro.models import mla as mla_mod
from repro.models import moe as moe_mod
from repro.models.config import ArchConfig, MoEConfig, RopeScaling
from repro.models.registry import get_smoke

D, F, E, K = 32, 16, 8, 3


def _cfg(**moe_kw) -> ArchConfig:
    m = dict(n_experts=E, top_k=K, d_ff_expert=F, n_shared=1)
    m.update(moe_kw)
    return ArchConfig(name="moe-tiny", family="moe", n_layers=2, d_model=D,
                      n_heads=4, n_kv_heads=4, d_ff=F, vocab=64,
                      moe=MoEConfig(**m), dtype="float32",
                      param_dtype="float32")


def _layer(cfg, seed=0):
    return moe_mod.init_moe(jax.random.PRNGKey(seed), cfg)


def _x(T, seed=1):
    return jax.random.normal(jax.random.PRNGKey(seed), (1, T, D), jnp.float32)


def _share(params, lo, hi, shared):
    """One holder's params: experts [lo, hi), the router whole, the shared
    experts only where ``shared``."""
    p = {k: params[k][lo:hi] for k in ("e_gate", "e_up", "e_down")}
    p["router"] = params["router"]
    if shared:
        p["shared"] = params["shared"]
    return p


@pytest.mark.parametrize("norm", [True, False])
def test_every_expert_held_equals_dense_oracle(norm):
    cfg = _cfg(norm_topk_prob=norm)
    params, x = _layer(cfg), _x(40)
    got, aux, _ = moe_mod.moe_fwd(params, cfg, x)
    want, aux_d = moe_mod.moe_fwd_dense(params, cfg, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    assert float(aux) == float(aux_d)


def test_four_shares_sum_to_the_uncut_layer():
    """Expert parallelism over four holders: each holds a quarter of the
    experts and routes over all of them; their parts, with the shared
    experts (which every holder computes alike) counted once, add up to
    the layer that holds every expert."""
    whole = _cfg()
    params, x = _layer(whole), _x(48)
    want, _, _ = moe_mod.moe_fwd(params, whole, x)
    quarter = _cfg(n_held=E // 4, n_shared=0)
    parts = []
    for i in range(4):
        lo = i * (E // 4)
        p = _share(params, lo, lo + E // 4, shared=False)
        y, _, load = moe_mod.moe_fwd(p, quarter, x, first=lo)
        parts.append(y)
        assert int(load[0]) == 48 * K
    shared = L.mlp(params["shared"], x, "swiglu")
    np.testing.assert_allclose(np.asarray(sum(parts) + shared),
                               np.asarray(want), rtol=1e-5, atol=1e-5)


def test_share_matches_dense_oracle_over_its_experts():
    cfg = _cfg(n_held=3)
    full = _layer(_cfg())
    params = _share(full, 2, 5, shared=True)
    x = _x(40)
    got, _, _ = moe_mod.moe_fwd(params, cfg, x, first=2)
    want, _ = moe_mod.moe_fwd_dense(params, cfg, x, first=2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_token_output_does_not_depend_on_its_batch():
    """No expert has a capacity: a token routed with 63 others, all sent to
    the same experts, gets the output it gets alone."""
    cfg = _cfg(n_held=4)
    params = _layer(cfg)
    x = _x(64)
    crowd = jnp.broadcast_to(x[:, :1], x.shape)     # every token the same
    alone, _, _ = moe_mod.moe_fwd(params, cfg, x[:, :1])
    y, _, load = moe_mod.moe_fwd(params, cfg, crowd)
    np.testing.assert_allclose(np.asarray(y[0]),
                               np.broadcast_to(np.asarray(alone[0]),
                                               (64, D)),
                               rtol=1e-5, atol=1e-5)
    # the 64 copies each send their held assignments to the same experts
    assert int(load[3]) == 64


def test_norm_topk_prob_false_keeps_the_softmax_weights():
    on, off = _cfg(norm_topk_prob=True), _cfg(norm_topk_prob=False)
    params, x = _layer(on), _x(24)
    _, w_on, _ = moe_mod.route(params, on, x[0])
    _, w_off, _ = moe_mod.route(params, off, x[0])
    np.testing.assert_allclose(np.asarray(w_on.sum(-1)), 1.0, rtol=1e-6)
    assert float(w_off.sum(-1).max()) < 1.0
    np.testing.assert_allclose(
        np.asarray(w_on), np.asarray(w_off / w_off.sum(-1, keepdims=True)),
        rtol=1e-6)
    probs = jax.nn.softmax(x[0] @ params["router"], -1)
    np.testing.assert_allclose(np.asarray(w_off),
                               np.sort(np.asarray(probs), -1)[:, ::-1][:, :K],
                               rtol=1e-6)
    y_on, _, _ = moe_mod.moe_fwd(params, on, x)
    y_off, _, _ = moe_mod.moe_fwd(params, off, x)
    assert not np.allclose(np.asarray(y_on), np.asarray(y_off))


def test_load_counters():
    cfg = _cfg(n_held=3)
    params = _layer(cfg)
    x = _x(50)
    first = 2
    idx, _, _ = moe_mod.route(params, cfg, x[0])
    local = np.asarray(idx).reshape(-1) - first
    sizes = np.bincount(local[(local >= 0) & (local < 3)], minlength=3)
    _, _, load = moe_mod.moe_fwd(params, cfg, x, first=first)
    assert dict(zip(moe_mod.LOAD_FIELDS, np.asarray(load).tolist())) == {
        "routed": 50 * K, "held_rows": int(sizes.sum()),
        "touched": int((sizes > 0).sum()), "max_group": int(sizes.max()),
        "layers": 1}


# -- YaRN -------------------------------------------------------------------

YARN = DEEPSEEK_V2_LITE.rope_scaling


def _published_inv_freq(dim, base, s: RopeScaling):
    """DeepSeek-V2's ``DeepseekV2YarnRotaryEmbedding`` written out."""
    def corr_dim(n_rot):
        return (dim * math.log(s.original_max_position_embeddings
                               / (n_rot * 2 * math.pi))) \
            / (2 * math.log(base))
    low = max(math.floor(corr_dim(s.beta_fast)), 0)
    high = min(math.ceil(corr_dim(s.beta_slow)), dim - 1)
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    inter = 1.0 / (s.factor * base ** (np.arange(0, dim, 2,
                                                 dtype=np.float64) / dim))
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    mask = 1.0 - ramp
    return inter * (1 - mask) + extra * mask, (low, high)


def test_yarn_frequencies_follow_the_published_formula():
    want, (low, high) = _published_inv_freq(64, 10000.0, YARN)
    assert (low, high) == (10, 23)
    got = np.asarray(L.rope_freqs(64, 10000.0, YARN))
    np.testing.assert_allclose(got, want, rtol=1e-6)
    plain = np.asarray(L.rope_freqs(64, 10000.0))
    np.testing.assert_array_equal(got[:low], plain[:low])
    np.testing.assert_allclose(got[high:], plain[high:] / 40.0, rtol=1e-6)


def test_yarn_softmax_scale_is_mscale_squared():
    m = 0.1 * 0.707 * math.log(40) + 1.0
    cfg = DEEPSEEK_V2_LITE
    assert mla_mod._yarn_softmax_factor(cfg) == pytest.approx(m * m)
    assert mla_mod._yarn_softmax_factor(cfg) == pytest.approx(1.5896, abs=1e-4)
    # cos and sin keep their size: mscale / mscale_all_dim = 1
    x = jax.random.normal(jax.random.PRNGKey(0), (5, 1, 64))
    y = L.apply_rope(x, jnp.arange(5), 10000.0, YARN)
    np.testing.assert_allclose(np.linalg.norm(np.asarray(y), axis=-1),
                               np.linalg.norm(np.asarray(x), axis=-1),
                               rtol=1e-5)
    assert mla_mod._yarn_softmax_factor(
        cfg.replace(rope_scaling=None)) == 1.0


def test_no_rope_scaling_is_bitwise_the_plain_rope():
    """``rope_scaling=None`` computes what the repo computed before YaRN:
    the plain inverse frequencies and rotation, and the 1/sqrt(D) scale of
    the blocked attention, bit for bit."""
    d, theta = 64, 10000.0
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    np.testing.assert_array_equal(np.asarray(L.rope_freqs(d, theta)),
                                  np.asarray(inv))
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 7, 3, d))
    pos = jnp.broadcast_to(jnp.arange(7), (2, 7))
    ang = pos.astype(jnp.float32)[..., None] * inv
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    want = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    np.testing.assert_array_equal(np.asarray(L.apply_rope(x, pos, theta)),
                                  np.asarray(want))
    q = jax.random.normal(jax.random.PRNGKey(1), (1, 16, 2, 24))
    k = jax.random.normal(jax.random.PRNGKey(2), (1, 16, 2, 24))
    np.testing.assert_array_equal(
        np.asarray(L.blocked_attention(q, k, k)),
        np.asarray(L.blocked_attention(q, k, k, scale=1.0 / math.sqrt(24))))


def test_yarn_reaches_mla_prefill_and_decode():
    """The smoke of the listed model carries YaRN and the switch; its MLA
    prefill and absorbed decode agree with each other, and differ from
    the same weights without YaRN."""
    cfg = get_smoke("deepseek-v2-lite-16b")
    assert cfg.rope_scaling == YARN and not cfg.moe.norm_topk_prob
    p = mla_mod.init_mla(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 9, cfg.d_model))
    full, (lat, kr) = mla_mod.mla_fwd(p, cfg, x)
    cache = {"latent": jnp.pad(lat[:, :8], ((0, 0), (0, 4), (0, 0))),
             "k_rope": jnp.pad(kr[:, :8], ((0, 0), (0, 4), (0, 0)))}
    step, _ = mla_mod.mla_decode(p, cfg, x[:, 8:9], cache, jnp.int32(8))
    np.testing.assert_allclose(np.asarray(step[0, 0]),
                               np.asarray(full[0, 8]), rtol=1e-4, atol=1e-4)
    plain, _ = mla_mod.mla_fwd(p, cfg.replace(rope_scaling=None), x)
    assert not np.allclose(np.asarray(plain), np.asarray(full), atol=1e-4)


def test_smoke_keeps_the_listed_models_departures():
    cfg = get_smoke("deepseek-v2-lite-16b")
    assert dataclasses.replace(cfg.moe, n_experts=64, top_k=6,
                               d_ff_expert=1408, n_shared=2) == \
        DEEPSEEK_V2_LITE.moe


# -- the load counters on the serving path ----------------------------------

def _serve(cfg, exit_layer, tmp_path, c_thr=0.5):
    from repro.core import early_exit as ee
    from repro.runtime import observe
    from repro.runtime import serve_loop as SL
    from repro.runtime.scheduler import (ContinuousScheduler, LogicalClock,
                                         Request, ServeConfig)
    spec = ee.EarlyExitSpec(exit_layer=exit_layer, c_thr=c_thr)
    params = ee.init_ee_params(jax.random.PRNGKey(0), cfg, spec)
    fns = SL.decode_stage_fns(params, cfg, spec, page_size=4)
    s = ContinuousScheduler(fns, ServeConfig(capacity=2, queue_depth=2,
                                             c_thr=c_thr, max_pending=2),
                            n_slots=4, max_len=24, clock=LogicalClock())
    prompts = np.asarray(jax.random.randint(jax.random.PRNGKey(5), (6, 8), 0,
                                            cfg.vocab))
    for i, n in enumerate([6, 3, 5, 4, 6, 2]):
        s.submit(Request(sample_id=i, prompt=prompts[i], n_tokens=n))
    with observe.ProfileWindow(str(tmp_path)) as win:
        while s.step() != "idle":
            pass
        while s._pending:
            s._harvest_one()
    return s, win.record


def test_moe_load_recorded_at_the_existing_syncs(tmp_path):
    """Each tick, bucket and prefill of a model with MoE layers records its
    load counters as a ``moe_load`` span right after the sync it already
    made; no program of a dense model records any."""
    cfg = _held_share_smoke()
    s, rec = _serve(cfg, exit_layer=3, tmp_path=tmp_path)
    rows = [r for r in rec.spans if r[0] == "moe_load"]
    by_prog = {p: [r[4] for r in rows if r[4]["program"] == p]
               for p in ("tick", "bucket", "prefill")}
    assert len(by_prog["tick"]) == rec.count("finish_tick_sync") > 0
    assert len(by_prog["bucket"]) == rec.count("harvest_sync") > 0
    assert len(by_prog["prefill"]) == rec.count("admit_sync") > 0
    # stage 1 runs MoE layers 1-2, stage 2 layers 3-4, prefill all four
    layers = {"tick": 2, "bucket": 2, "prefill": 4}
    for prog, args in by_prog.items():
        for a in args:
            assert set(a) == {"program", *moe_mod.LOAD_FIELDS}
            assert a["layers"] == layers[prog]
            assert a["routed"] % (cfg.moe.top_k * a["layers"]) == 0
            assert 0 <= a["held_rows"] <= a["routed"]
            assert a["touched"] <= cfg.moe.held * a["layers"]
            assert a["max_group"] * a["layers"] >= a["held_rows"] / \
                cfg.moe.held
    tick = by_prog["tick"][0]
    assert tick["routed"] == s.n_slots * cfg.moe.top_k * 2

    dense = ArchConfig(name="dense", family="dense", n_layers=4, d_model=32,
                       n_heads=4, n_kv_heads=2, d_ff=64, vocab=64,
                       dtype="float32", param_dtype="float32")
    _, rec = _serve(dense, exit_layer=2, tmp_path=tmp_path / "dense")
    assert rec.count("finish_tick_sync") > 0
    assert rec.count("moe_load") == 0


def _held_share_smoke():
    cfg = get_smoke("deepseek-v2-lite-16b")
    return cfg.replace(n_layers=5, moe=dataclasses.replace(
        cfg.moe, n_experts=8, n_held=3))
