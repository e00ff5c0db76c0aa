"""The serving programs read each MoE layer's held experts in place from
the whole stacks, at the layer's offset (``moe.moe_fwd(layer=)``), and
never slice a layer's experts out first: the chip's ragged dot is a custom
call, which cannot fuse the slice, so a slice would be a copy of every
layer's experts in every tick, bucket and prefill."""
import dataclasses
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import early_exit as ee
from repro.models import moe as moe_mod
from repro.models import transformer as T
from repro.models.registry import get_smoke

EXIT = 3           # the dense layer and two MoE layers, then three
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _cfg():
    """A smoke DeepSeek-V2-Lite: one dense layer, five MoE layers, each
    holding three of eight experts."""
    cfg = get_smoke("deepseek-v2-lite-16b")
    return cfg.replace(n_layers=6, moe=dataclasses.replace(
        cfg.moe, n_experts=8, n_held=3))


def _run_on_8_devices(src: str) -> str:
    code = ("import os\n"
            "os.environ['XLA_FLAGS']="
            "'--xla_force_host_platform_device_count=8'\n"
            "import sys; sys.path.insert(0, 'src')\n" + textwrap.dedent(src))
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, cwd=_REPO_ROOT, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    return r.stdout


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs nested in it."""
    for e in jaxpr.eqns:
        yield e
        for v in e.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                if isinstance(sub, jax.extend.core.ClosedJaxpr):
                    sub = sub.jaxpr
                if isinstance(sub, jax.extend.core.Jaxpr):
                    yield from _eqns(sub)


def _programs(cfg):
    """The jaxprs of a prefill, a stage-1 decode tick and a bucket's stage
    2 (on the whole tree and on a stage-2 slice), each with the number of
    superblocks its param tree stacks."""
    spec = ee.EarlyExitSpec(exit_layer=EXIT)
    params = jax.eval_shape(lambda: ee.init_ee_params(
        jax.random.PRNGKey(0), cfg, spec))
    bb = params["backbone"]
    n_sb = cfg.n_superblocks
    tok = jax.ShapeDtypeStruct((2, 4), jnp.int32)
    _, caches, _, _ = jax.eval_shape(
        lambda p, t: T.prefill(p, cfg, t, max_len=8, with_load=True),
        bb, tok)
    c1, c2 = jax.eval_shape(lambda c: ee.split_caches(cfg, spec, c), caches)
    one = jax.ShapeDtypeStruct((2, 1), jnp.int32)
    h = jax.ShapeDtypeStruct((2, 1, cfg.d_model), jnp.float32)
    step = jax.ShapeDtypeStruct((), jnp.int32)
    _, p2 = ee.split_params(cfg, spec, params)
    base = ee._stage2_base_sb(cfg, spec)
    mk = jax.make_jaxpr
    return {
        "prefill": (mk(lambda p, t: T.prefill(
            p, cfg, t, max_len=8, with_load=True))(bb, tok), n_sb),
        "decode": (mk(lambda p, t, c, s: ee.stage1_decode(
            p, cfg, spec, t, c, s, with_load=True))(params, one, c1, step),
            n_sb),
        "stage2": (mk(lambda p, x, c, s: ee.stage2_decode(
            p, cfg, spec, x, c, s, with_load=True))(params, h, c2, step),
            n_sb),
        "stage2_presliced": (mk(lambda p, x, c, s: ee.stage2_decode(
            p, cfg, spec, x, c, s, presliced_params=True,
            with_load=True))(p2, h, c2, step), n_sb - base),
    }


@pytest.mark.parametrize("program", ["prefill", "decode", "stage2",
                                     "stage2_presliced"])
def test_ragged_dots_read_the_whole_stack(program):
    cfg = _cfg()
    jaxpr, n_sb = _programs(cfg)[program]
    G, d, f = cfg.moe.held, cfg.d_model, cfg.moe.d_ff_expert
    eqns = list(_eqns(jaxpr.jaxpr))
    rhs = [e.invars[1].aval.shape for e in eqns
           if e.primitive.name == "ragged_dot_general"]
    assert rhs and {s[0] for s in rhs} == {n_sb * G}
    # no layer's experts are sliced out of a stack
    expert = {(G, d, f), (G, f, d)}
    for e in eqns:
        if e.primitive.name in ("dynamic_slice", "slice", "gather"):
            shape = tuple(e.outvars[0].aval.shape)
            while shape and shape[0] == 1:
                shape = shape[1:]
            assert shape not in expert, (e.primitive.name, shape)


@pytest.mark.parametrize("layer", [0, 2, 4])
def test_layer_at_its_offset_equals_the_layer_alone(layer):
    """First, middle and last MoE layer: read from the whole stacks at the
    layer's (traced) index, the output and counters equal those of the
    layer's own arrays bit for bit, and the dense oracle's."""
    cfg = _cfg()
    blocks = T.init_params(jax.random.PRNGKey(3), cfg)["blocks"][0]["moe"]
    assert blocks["e_gate"].shape[0] == 5
    own = jax.tree.map(lambda x: x[layer], blocks)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 20, cfg.d_model))
    stacked = {k: v if k in moe_mod.EXPERT_STACKS else own[k]
               for k, v in blocks.items()}
    got, aux, load = jax.jit(lambda p, x, i: moe_mod.moe_fwd(
        p, cfg, x, layer=i))(stacked, x, jnp.int32(layer))
    want, aux_w, load_w = jax.jit(
        lambda p, x: moe_mod.moe_fwd(p, cfg, x))(own, x)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(load), np.asarray(load_w))
    assert float(aux) == float(aux_w)
    assert int(load[1]) > 0
    dense, _ = moe_mod.moe_fwd_dense(own, cfg, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(dense),
                               rtol=1e-5, atol=1e-5)


def test_mesh_that_splits_the_experts_gathers_no_stack():
    """Under a published mesh with a model axis the launch cells split the
    stacks' expert dim over it; merging that dim with the layer dim would
    gather every layer's experts, so there each layer's experts are
    indexed as before and the decode and prefill cells gather none."""
    src = """
        import dataclasses, re
        import jax
        from repro.launch import steps as S
        from repro.launch.mesh import make_mesh
        from repro.models.registry import get_smoke
        cfg = get_smoke("deepseek-v2-lite-16b")
        cfg = cfg.replace(n_layers=6, moe=dataclasses.replace(
            cfg.moe, n_experts=8, n_held=None))
        mesh = make_mesh((2, 4), ("data", "model"))
        for make in (S.make_decode_cell, S.make_prefill_cell):
            cell = make(cfg, mesh, seq_len=32, global_batch=8)
            with mesh:
                txt = jax.jit(cell.step_fn, in_shardings=cell.in_shardings
                              ).lower(*cell.args).compile().as_text()
            print(sorted(set(re.findall(
                r"= (\\w+\\[[\\d,]*\\])\\S* all-gather\\(", txt))))
    """
    out = _run_on_8_devices(src).strip().splitlines()
    assert len(out) == 2
    for line in out:
        for shape in ("[5,8,32,64]", "[5,8,64,32]", "[8,32,64]",
                      "[8,64,32]"):
            assert shape not in line, line
