"""Distributed-path correctness: SP/batch-split shard_map attention,
vocab-parallel CE, flash custom-VJP — exercised on an 8-device host mesh in
a subprocess (the main test process must keep 1 device)."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.layers import blocked_attention, flash_attention_diff

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(src: str) -> str:
    code = ("import os\n"
            "os.environ['XLA_FLAGS']="
            "'--xla_force_host_platform_device_count=8'\n"
            "import sys; sys.path.insert(0, 'src')\n" + textwrap.dedent(src))
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, cwd=_REPO_ROOT, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    return r.stdout


# ---------------------------------------------------------------------------
# in-process: flash custom-VJP vs AD-through-blocked (1 device)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("window", [None, 48])
def test_flash_vjp_matches_ad(window):
    k = jax.random.PRNGKey(0)
    B, S, H, KH, D = 2, 160, 4, 2, 16
    q = jax.random.normal(k, (B, S, H, D), jnp.float32)
    kk = jax.random.normal(jax.random.fold_in(k, 1), (B, S, KH, D))
    v = jax.random.normal(jax.random.fold_in(k, 2), (B, S, KH, D))

    def f1(q, kk, v):
        return jnp.sum(jnp.sin(flash_attention_diff(q, kk, v, 0, True,
                                                    window, 64, 32)))

    def f2(q, kk, v):
        return jnp.sum(jnp.sin(blocked_attention(q, kk, v, causal=True,
                                                 window=window, q_block=64,
                                                 kv_block=32)))

    assert abs(float(f1(q, kk, v) - f2(q, kk, v))) < 1e-5
    g1 = jax.grad(f1, argnums=(0, 1, 2))(q, kk, v)
    g2 = jax.grad(f2, argnums=(0, 1, 2))(q, kk, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5)


def test_flash_vjp_q_offset_grad():
    k = jax.random.PRNGKey(1)
    q = jax.random.normal(k, (1, 128, 2, 16))
    kv = jax.random.normal(jax.random.fold_in(k, 1), (1, 128, 2, 16))

    def f1(q):
        return jnp.sum(flash_attention_diff(q[:, 64:], kv, kv, 64, True,
                                            None, 64, 32) ** 2)

    def f2(q):
        return jnp.sum(blocked_attention(q[:, 64:], kv, kv, causal=True,
                                         q_offset=64, q_block=64,
                                         kv_block=32) ** 2)

    np.testing.assert_allclose(np.asarray(jax.grad(f1)(q)),
                               np.asarray(jax.grad(f2)(q)), atol=5e-5)


# ---------------------------------------------------------------------------
# subprocess: shard_map paths on an 8-device mesh
# ---------------------------------------------------------------------------

def test_sp_attention_exact_on_mesh():
    out = _run("""
    import jax, jax.numpy as jnp
    from repro.launch.mesh import make_mesh
    from repro.models import hints
    from repro.models.layers import blocked_attention
    from repro.models.attention import attention_core
    mesh = make_mesh((2, 4), ("data", "model"))
    k = jax.random.PRNGKey(0)
    B, S, H, KH, D = 4, 256, 6, 2, 32
    q = jax.random.normal(k, (B, S, H, D), jnp.float32)
    kk = jax.random.normal(jax.random.fold_in(k, 1), (B, S, KH, D))
    v = jax.random.normal(jax.random.fold_in(k, 2), (B, S, KH, D))
    ref = blocked_attention(q, kk, v, causal=True)
    hints.set_mesh(mesh)
    with mesh:
        sp = jax.jit(lambda a, b, c: attention_core(
            a, b, c, causal=True, window=None, softcap=None))(q, kk, v)
        g = jax.jit(jax.grad(lambda a: jnp.sum(attention_core(
            a, kk, v, causal=True, window=None, softcap=None) ** 2)))(q)
    gr = jax.grad(lambda a: jnp.sum(blocked_attention(
        a, kk, v, causal=True) ** 2))(q)
    print("OUT", float(jnp.abs(sp - ref).max()))
    print("GRAD", float(jnp.abs(g - gr).max()))
    """)
    vals = dict(line.split() for line in out.strip().splitlines())
    assert float(vals["OUT"]) < 1e-5
    assert float(vals["GRAD"]) < 1e-4


def test_vocab_parallel_ce_on_mesh():
    out = _run("""
    import jax, jax.numpy as jnp
    from repro.launch.mesh import make_mesh
    from repro.models import hints
    from repro.core import losses
    from repro.models.config import ArchConfig
    cfg = ArchConfig(name="t", family="dense", n_layers=1, d_model=32,
                     n_heads=2, n_kv_heads=2, d_ff=64, vocab=64,
                     dtype="float32", param_dtype="float32")
    mesh = make_mesh((2, 4), ("data", "model"))
    k = jax.random.PRNGKey(0)
    bb = {"embed": {"table": jax.random.normal(k, (64, 32)) * 0.1}}
    hidden = jax.random.normal(jax.random.fold_in(k, 1), (4, 24, 32))
    labels = jax.random.randint(jax.random.fold_in(k, 2), (4, 24), 0, 64)
    ref = losses.chunked_ce(bb, cfg, hidden, labels, chunk=8)
    g_ref = jax.grad(lambda b: losses.chunked_ce(b, cfg, hidden, labels))(bb)
    hints.set_mesh(mesh)
    with mesh:
        got = jax.jit(lambda b: losses.vocab_parallel_ce(
            b, cfg, hidden, labels, chunk=8))(bb)
        g = jax.jit(jax.grad(lambda b: losses.vocab_parallel_ce(
            b, cfg, hidden, labels, chunk=8)))(bb)
    print("LOSS", abs(float(ref) - float(got)))
    print("GRAD", float(jnp.abs(g["embed"]["table"] -
                                g_ref["embed"]["table"]).max()))
    """)
    vals = dict(line.split() for line in out.strip().splitlines())
    assert float(vals["LOSS"]) < 1e-5
    assert float(vals["GRAD"]) < 1e-5


def test_train_step_on_mesh_matches_single_device():
    """One EE train step on the 8-device mesh (SP attention + VP loss + TP
    shardings active) must match the same step on one device bit-for-bit
    within fp tolerance."""
    out = _run("""
    import jax, jax.numpy as jnp
    from repro.launch.mesh import make_mesh
    from repro.core import early_exit as ee, losses
    from repro.models import hints
    from repro.models.config import ArchConfig
    cfg = ArchConfig(name="t", family="dense", n_layers=2, d_model=32,
                     n_heads=4, n_kv_heads=2, d_ff=64, vocab=64,
                     dtype="float32", param_dtype="float32")
    spec = ee.EarlyExitSpec(exit_layer=1)
    params = ee.init_ee_params(jax.random.PRNGKey(0), cfg, spec)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 256), 0, 64)
    labels = jax.random.randint(jax.random.PRNGKey(2), (8, 256), 0, 64)

    def loss_fn(p):
        eh, fh, aux = ee.forward_train(p, cfg, spec, tokens)
        l, _ = losses.branchynet_joint_loss(p, cfg, eh, fh, labels,
                                            spec.loss_weights, aux=aux)
        return l

    l_single = float(loss_fn(params))
    g_single = jax.grad(loss_fn)(params)
    mesh = make_mesh((2, 4), ("data", "model"))
    hints.set_mesh(mesh)
    with mesh:
        l_mesh = float(jax.jit(loss_fn)(params))
        g_mesh = jax.jit(jax.grad(loss_fn))(params)
    d = max(float(jnp.abs(a - b).max())
            for a, b in zip(jax.tree.leaves(g_single),
                            jax.tree.leaves(g_mesh)))
    print("LOSS", abs(l_single - l_mesh))
    print("GRAD", d)
    """)
    vals = dict(line.split() for line in out.strip().splitlines())
    assert float(vals["LOSS"]) < 1e-4
    assert float(vals["GRAD"]) < 1e-3
