"""``run_layers`` reads each layer of the stacked superblock params in place
in the inference modes, so a stage that runs part of the stack copies none
of it; ``mode="train"`` still scans over a slice of the stack.

Parity: a stage run on the full param tree equals the same stage run on
its explicitly sliced resident set (``ee.split_params`` with
``param_base_sb``), bitwise, in hidden states, new caches and logits.
Structure: no equation but the layer scan reads a ``blocks`` leaf in the
stage programs' jaxprs, and no buffer of a stage's segment of a block leaf
is in the compiled stage-2 decode program.
"""
import dataclasses
import re

import jax
import jax.extend.core as jex_core
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import early_exit as ee
from repro.models import transformer as T
from repro.models.config import ArchConfig
from repro.models.registry import get_smoke
from repro.runtime import serve_loop as SL

DENSE = ArchConfig(
    name="dense-7", family="dense", n_layers=7, d_model=32, n_heads=4,
    n_kv_heads=2, d_ff=64, vocab=64, dtype="float32", param_dtype="float32",
    tie_embeddings=True)

# plain dense; leading dense layer (+ MoE, MLA); pattern_len 3 + remainder.
# Every stage runs at least two superblocks, so that a segment of the stack
# is not shaped like the one layer each scan iteration reads.
CONFIGS = {
    "dense": (DENSE, 2),
    "first_k_dense": (dataclasses.replace(
        get_smoke("deepseek-v2-lite-16b"), n_layers=5), 3),
    "pattern3_rem": (dataclasses.replace(
        get_smoke("recurrentgemma-9b"), n_layers=14), 6),
}
B, S = 3, 8


def _setup(name):
    cfg, exit_layer = CONFIGS[name]
    spec = ee.EarlyExitSpec(exit_layer=exit_layer, c_thr=0.5)
    params = ee.init_ee_params(jax.random.PRNGKey(0), cfg, spec)
    return cfg, spec, params


def _stage(cfg, spec, stage, p, sliced, mode, h, caches=None, step=None):
    """Run one stage's layers through ``run_layers`` (jitted, as served) and
    its head. ``sliced``: ``p`` is that stage's ``ee.split_params`` set."""
    k = spec.exit_layer
    lo, hi = (0, k) if stage == 1 else (k, cfg.n_layers)
    base = ee._stage2_base_sb(cfg, spec) if stage == 2 else 0
    cache_base = base if mode == "decode" else 0

    @jax.jit
    def run(p, h, caches, step):
        hh, nc, _ = T.run_layers(p["backbone"], cfg, h, lo, hi, mode=mode,
                                 caches=caches, step=step,
                                 cache_base_sb=cache_base,
                                 param_base_sb=base if sliced else 0)
        logits = (ee.exit_head(p, cfg, hh[:, -1]) if stage == 1
                  else T.head(p["backbone"], cfg, hh[:, -1]))
        return hh, nc, logits
    return run(p, h, caches, step)


@pytest.mark.parametrize("mode", ["decode", "prefill"])
@pytest.mark.parametrize("stage", [1, 2])
@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_stage_on_full_tree_equals_sliced_tree(name, stage, mode):
    cfg, spec, params = _setup(name)
    bb = params["backbone"]
    p1, p2 = ee.split_params(cfg, spec, params)
    sliced = p1 if stage == 1 else p2
    key = jax.random.PRNGKey(1)
    tokens = jax.random.randint(key, (B, S + 1), 0, cfg.vocab)
    if mode == "prefill":
        h = T.embed_tokens(bb, cfg, tokens[:, :S])
        if stage == 2:
            h, _, _ = T.run_layers(bb, cfg, h, 0, spec.exit_layer,
                                   mode="prefill")
        caches = step = None
    else:
        _, full, _ = T.prefill(bb, cfg, tokens[:, :S], max_len=S + 4)
        caches = ee.split_caches(cfg, spec, full)[stage - 1]
        h = T.embed_tokens(bb, cfg, tokens[:, S:S + 1])
        if stage == 2:
            h = jax.random.normal(jax.random.fold_in(key, 2), h.shape,
                                  h.dtype)
        step = jnp.int32(S)
    got = _stage(cfg, spec, stage, params, False, mode, h, caches, step)
    want = _stage(cfg, spec, stage, sliced, True, mode, h, caches, step)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def _vars(eqn):
    return {v for v in eqn.invars if not isinstance(v, jex_core.Literal)}


def _top_level_eqns_on(jaxpr, leaf_vars):
    """The top-level equations that read one of ``leaf_vars``."""
    return [e for e in jaxpr.eqns if _vars(e) & leaf_vars]


def _blocks_vars(closed, params):
    """The jaxpr invars of the ``blocks`` leaves of ``params``, the traced
    function's only argument."""
    paths = jax.tree_util.tree_flatten_with_path(params)[0]
    return {closed.jaxpr.invars[i] for i, (path, _) in enumerate(paths)
            if any(getattr(p, "key", None) == "blocks" for p in path)}


@pytest.mark.parametrize("fn", ["stage1_decode", "stage2_decode",
                                "stage1_prefill", "stage2_prefill"])
@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_inference_stages_slice_no_block_leaf(name, fn):
    """The stack enters the layer scan whole: no top-level equation reads a
    ``blocks`` leaf except the scan itself."""
    cfg, spec, params = _setup(name)
    tokens = jnp.zeros((B, S), jnp.int32)
    _, full, _ = T.prefill(params["backbone"], cfg, tokens, max_len=S + 4)
    c1, c2 = ee.split_caches(cfg, spec, full)
    h = jnp.zeros((B, 1, cfg.d_model), cfg.act_dtype())
    calls = {
        "stage1_decode": lambda p: ee.stage1_decode(
            p, cfg, spec, tokens[:, :1], c1, jnp.int32(S)),
        "stage2_decode": lambda p: ee.stage2_decode(
            p, cfg, spec, h, c2, jnp.int32(S)),
        "stage1_prefill": lambda p: ee.stage1_prefill(p, cfg, spec, tokens),
        "stage2_prefill": lambda p: ee.stage2_prefill(
            p, cfg, spec, jnp.zeros((B, S, cfg.d_model), cfg.act_dtype())),
    }
    closed = jax.make_jaxpr(calls[fn])(params)
    leaves = _blocks_vars(closed, params)
    assert len(leaves) == len(jax.tree.leaves(params["backbone"]["blocks"]))
    readers = _top_level_eqns_on(closed.jaxpr, leaves)
    assert [e.primitive.name for e in readers] == ["scan"]
    assert leaves <= _vars(readers[0])


def test_train_mode_still_slices_and_differentiates():
    """``train`` on the full stack differentiates as on the stage's sliced
    resident set: the gradient lands on the stage's rows only and equals
    the sliced set's. (What slicing saves in memory is read from the chip's
    compiler in ``test_tpu_compile.py``.)"""
    cfg, spec, params = _setup("dense")
    bb = params["backbone"]
    k_super = ee._stage2_base_sb(cfg, spec)
    h = jax.random.normal(jax.random.PRNGKey(3), (B, S, cfg.d_model))

    def loss(blocks, base):
        hh, _, _ = T.run_layers({**bb, "blocks": blocks}, cfg, h,
                                spec.exit_layer, cfg.n_layers, mode="train",
                                param_base_sb=base)
        return jnp.sum(hh * hh)

    g_full = jax.jit(jax.grad(lambda b: loss(b, 0)))(bb["blocks"])
    _, p2 = ee.split_params(cfg, spec, params)
    g_sliced = jax.jit(jax.grad(lambda b: loss(b, k_super)))(
        p2["backbone"]["blocks"])
    for gf, gs in zip(jax.tree.leaves(g_full), jax.tree.leaves(g_sliced)):
        gf = np.asarray(gf)
        assert not gf[:k_super].any()
        assert gf[k_super:].any()
        np.testing.assert_array_equal(gf[k_super:], np.asarray(gs))


def _hlo_shapes(hlo):
    """The dims of every array shape in an HLO text, of any element type
    and layout."""
    return {tuple(int(d) for d in dims.split(","))
            for dims in re.findall(r"\b[a-z]+[0-9]*\[([0-9,]+)\]", hlo)}


def _stage2_hlo(cfg, spec, params):
    """The compiled stage-2 decode program on the full tree (one device):
    the paged bucket program where every layer is global attention, else
    the dense one (recurrent and windowed layers keep dense rows)."""
    fns = SL.decode_stage_fns(params, cfg, spec, page_size=4)
    _, full, *_ = fns.prefill(jnp.zeros((B, S), jnp.int32), max_len=S + 4)
    _, rows = fns.split(full)
    h = jnp.zeros((B, cfg.d_model), cfg.act_dtype())
    step = jnp.zeros((B,), jnp.int32)
    if set(cfg.pattern) != {"attn"}:
        s2 = fns.s2
        return s2.func.lower(*s2.args, h, rows, step).compile().as_text()
    pool = fns.pool_init(rows, 8)
    s2 = fns.s2_paged
    bt = jnp.zeros((B, (S + 4) // 4), jnp.int32)
    return s2.func.lower(*s2.args, h, bt, step, pool).compile().as_text()


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_paged_stage2_program_holds_no_weight_segment(name):
    """The compiled stage-2 program on the full tree holds no buffer of
    stage 2's segment of any block leaf, in any element type, layout or
    order of its dimensions, while the whole-stack leaves are there."""
    cfg, spec, params = _setup(name)
    n_seg = cfg.n_superblocks - ee._stage2_base_sb(cfg, spec)
    held = {tuple(sorted(d)) for d in _hlo_shapes(
        _stage2_hlo(cfg, spec, params))}
    for x in jax.tree.leaves(params["backbone"]["blocks"]):
        assert tuple(sorted(x.shape)) in held
        seg = tuple(sorted((n_seg,) + x.shape[1:]))
        assert seg not in held, (x.shape, n_seg)
