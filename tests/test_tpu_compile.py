"""The main-path Pallas kernels compile for a described TPU v5e at the
published widths of qwen2-1.5b (V = 151936, d = 1536, 2 KV heads x 128).

No chip is needed: the TPU compiler is installed, and it compiles for a
topology that is described rather than attached. The topology is described
inside a module-scoped fixture, never at import time, because only one
process at a time may load the TPU library and every test worker imports
this file. Each test asserts that the compiled program really contains the
Pallas kernel (``tpu_custom_call``), i.e. nothing routed around it. One
more reads the compiler's temporaries for the training gradient, which only
the chip's compiler counts as the chip would.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.configs.archs import QWEN2_1_5B as CFG
from repro.core import early_exit as ee
from repro.core import losses
from repro.kernels import dispatch
from repro.kernels.exit_decision.kernel import exit_decision_pallas
from repro.kernels.flash_attention.kernel import flash_attention_pallas
from repro.kernels.fused_dispatch.kernel import fused_dispatch_pallas
from repro.kernels.gather_compact.kernel import gather_compact_pallas
from repro.kernels.paged_attention.kernel import paged_gather_append_pallas
from repro.models.config import ArchConfig

V, D = CFG.vocab, CFG.d_model
KH, HD = CFG.n_kv_heads, CFG.resolved_head_dim
N_SLOTS = 8                      # the serving pool width the smoke runs
PAGE, MAX_PAGES = 16, 6          # page 16, 96-token rows (64 prompt + 32)
RING = 32                        # queue_depth 4 x capacity 8


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:       # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip can be written to the persistent
    # cache but never read back without one: keep the cache out of it
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was_on)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_exit_decision_compiles(one_chip):
    logits = _spec(one_chip, (32, V), jnp.bfloat16)
    txt = _compiled_text(lambda x: exit_decision_pallas(x, 0.5), logits)
    assert "tpu_custom_call" in txt


def test_gather_compact_compiles(one_chip):
    x = _spec(one_chip, (N_SLOTS, D), jnp.bfloat16)
    mask = _spec(one_chip, (N_SLOTS,), jnp.bool_)
    txt = _compiled_text(lambda a, m: gather_compact_pallas(a, m, N_SLOTS),
                         x, mask)
    assert "tpu_custom_call" in txt


def test_fused_dispatch_compiles(one_chip):
    """The single-submesh tick's dispatch: exit decision + compaction +
    in-place ring merge of the paged payload {h, block-table row, step}."""
    s = lambda shape, dt: _spec(one_chip, shape, dt)
    payload = {"h": s((N_SLOTS, D), jnp.bfloat16),
               "cache": s((N_SLOTS, MAX_PAGES), jnp.int32),
               "step": s((N_SLOTS,), jnp.int32)}
    ring = {"data": {"h": s((RING, D), jnp.bfloat16),
                     "cache": s((RING, MAX_PAGES), jnp.int32),
                     "step": s((RING,), jnp.int32)},
            "ids": s((RING,), jnp.int32),
            "head": s((), jnp.int32), "count": s((), jnp.int32)}
    txt = _compiled_text(
        lambda lg, act, ids, p, r: fused_dispatch_pallas(lg, act, ids, p, r,
                                                         0.5),
        s((N_SLOTS, V), jnp.float32), s((N_SLOTS,), jnp.bool_),
        s((N_SLOTS,), jnp.int32), payload, ring)
    assert "tpu_custom_call" in txt


def test_paged_gather_append_compiles(one_chip):
    s = lambda shape, dt: _spec(one_chip, shape, dt)
    fa = KH * HD                                   # 256 features per token
    n_pages = N_SLOTS * MAX_PAGES + 1
    txt = _compiled_text(
        paged_gather_append_pallas,
        s((n_pages, PAGE, fa), jnp.bfloat16), s((n_pages, PAGE, fa),
                                                jnp.bfloat16),
        s((N_SLOTS, fa), jnp.bfloat16), s((N_SLOTS, fa), jnp.bfloat16),
        s((N_SLOTS, MAX_PAGES), jnp.int32), s((N_SLOTS,), jnp.int32))
    assert "tpu_custom_call" in txt


def test_flash_attention_compiles(one_chip):
    s = lambda shape, dt: _spec(one_chip, shape, dt)
    H = CFG.n_heads
    txt = _compiled_text(lambda q, k, v: flash_attention_pallas(q, k, v),
                         s((1, H, 256, HD), jnp.bfloat16),
                         s((1, KH, 256, HD), jnp.bfloat16),
                         s((1, KH, 256, HD), jnp.bfloat16))
    assert "tpu_custom_call" in txt


def test_stage1_kernels_compile_on_a_stage_submesh(topo):
    """Disaggregated serving at p = 0.25 on four chips runs stage 1 on a
    three-chip submesh. The TPU compiler cannot partition a Mosaic kernel,
    so the composed exit decision + compaction must run per device there
    (``dispatch._on_mesh``)."""
    mesh = Mesh(np.asarray(topo.devices[:3]).reshape(3, 1),
                ("data", "model"))
    rep = NamedSharding(mesh, P())
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=rep)

    def chain(logits, h):
        e, _, _ = dispatch._exit_decision(logits, 0.5, "pallas", mesh)
        return dispatch._gather_compact(h, ~e, N_SLOTS, "pallas", mesh)

    txt = _compiled_text(chain, s((N_SLOTS, V), jnp.float32),
                         s((N_SLOTS, D), jnp.bfloat16))
    assert "tpu_custom_call" in txt


def test_train_gradient_keeps_segment_sized_buffers(one_chip):
    """Training scans each exit's range over a slice of the stacked layers
    (``run_layers`` in ``mode="train"``), so the reverse scans carry
    gradients of that segment only. For this 6-layer float32 model (exit
    after 3) the joint loss's gradient needs 0.267 x the stack's bytes of
    temporaries; indexing the whole stack in place, as inference does,
    carries full-stack gradients through both reverse scans and needs
    0.984 x."""
    cfg = ArchConfig(name="train-6", family="dense", n_layers=6,
                     d_model=512, n_heads=4, n_kv_heads=2, d_ff=2048,
                     vocab=1024, dtype="float32", param_dtype="float32",
                     tie_embeddings=True)
    spec = ee.EarlyExitSpec(exit_layer=3)
    shapes = jax.eval_shape(lambda k: ee.init_ee_params(k, cfg, spec),
                            jax.ShapeDtypeStruct((2,), jnp.uint32))
    params = jax.tree.map(lambda x: _spec(one_chip, x.shape, x.dtype),
                          shapes)
    tokens = _spec(one_chip, (2, 256), jnp.int32)

    def loss(p, tokens):
        eh, fh, aux = ee.forward_train(p, cfg, spec, tokens)
        return losses.branchynet_joint_loss(p, cfg, eh, fh, tokens,
                                            spec.loss_weights, aux=aux)[0]

    temp = jax.jit(jax.grad(loss)).lower(params, tokens).compile(
        ).memory_analysis().temp_size_in_bytes
    stack = sum(x.size * x.dtype.itemsize
                for x in jax.tree.leaves(shapes["backbone"]["blocks"]))
    assert temp < 0.65 * stack, temp / stack
