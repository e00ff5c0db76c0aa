"""Stage-disaggregated serving: StageExecutor placement semantics (the
degenerate single-device executor must be a strict no-op), per-stage param
splitting (bitwise vs full-tree stage fns), and end-to-end bitwise parity
of the disaggregated TwoStageServer and continuous paged decode scheduler
against their single-device runs on an 8-device host platform — in-process when the
suite already runs multi-device (CI disaggregated job), and always via a
subprocess so the tier-1 single-device run covers the path too."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import early_exit as ee
from repro.runtime import serve_loop as SL
from repro.runtime.stage_executor import StageExecutor, StagePlacement

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_multi_device = pytest.mark.skipif(
    jax.device_count() < 8,
    reason="needs 8 host devices (CI disaggregated job sets XLA_FLAGS)")


# ---------------------------------------------------------------------------
# degenerate executor: placement must be the identity
# ---------------------------------------------------------------------------

def test_degenerate_executor_is_identity():
    ex = StageExecutor()
    assert ex.mesh is None and ex.n_devices == 1 and ex.devices == ()
    x = jnp.arange(6.0).reshape(3, 2)
    tree = {"a": x, "b": {"c": jnp.ones((4,))}}
    assert ex.place(tree) is tree                 # no copy, no commitment
    assert ex.place_io(x) is x
    assert ex.sharding() is None


def test_default_placement_degenerate():
    pl = StagePlacement.single_device()
    assert not pl.disaggregated
    assert pl.ex1.mesh is None and pl.ex2.mesh is None
    # servers built with no placement get the degenerate one
    srv = SL.TwoStageServer(None, None, SL.ServeConfig(capacity=2))
    assert srv.ex1.mesh is None and srv.ex2.mesh is None
    assert srv.stats.stage1_chips == 1 and srv.stats.stage2_chips == 1


# ---------------------------------------------------------------------------
# split_params: per-stage residency slices, bitwise-identical programs
# ---------------------------------------------------------------------------

def test_split_params_residency(tiny_cfg, tiny_spec, tiny_params):
    p1, p2 = ee.split_params(tiny_cfg, tiny_spec, tiny_params)
    k_super = (tiny_spec.exit_layer - tiny_cfg.first_k_dense) \
        // tiny_cfg.pattern_len
    n_sb = tiny_cfg.n_superblocks
    for leaf1, leaf2, full in zip(
            jax.tree.leaves(p1["backbone"]["blocks"]),
            jax.tree.leaves(p2["backbone"]["blocks"]),
            jax.tree.leaves(tiny_params["backbone"]["blocks"])):
        assert leaf1.shape[0] == k_super
        assert leaf2.shape[0] == n_sb - k_super
        np.testing.assert_array_equal(np.asarray(full),
                                      np.concatenate([leaf1, leaf2]))
    assert "exit_head" in p1 and "exit_head" not in p2
    assert "final_norm" not in p1["backbone"]
    assert "final_norm" in p2["backbone"]
    assert p2["backbone"]["first"] == [] and p1["backbone"]["rem"] == []
    # tied: the table is the shared unembedding, resident on both
    assert "embed" in p1["backbone"] and "embed" in p2["backbone"]


def test_split_params_untied_embed_stage1_only():
    """Untied models share the 'head' matrix between the two heads; the
    embed table is only read by stage 1's embed_tokens and must not be
    resident on stage 2."""
    from repro.models.config import ArchConfig
    cfg = ArchConfig(name="untied", family="dense", n_layers=4, d_model=32,
                     n_heads=4, n_kv_heads=2, d_ff=64, vocab=64,
                     dtype="float32", param_dtype="float32",
                     tie_embeddings=False)
    spec = ee.EarlyExitSpec(exit_layer=2, c_thr=0.5)
    params = ee.init_ee_params(jax.random.PRNGKey(0), cfg, spec)
    assert "head" in params["backbone"]
    p1, p2 = ee.split_params(cfg, spec, params)
    assert "embed" in p1["backbone"] and "head" in p1["backbone"]
    assert "embed" not in p2["backbone"] and "head" in p2["backbone"]
    # and the sliced programs still run: stage 2 on its slice, bitwise
    toks = jnp.asarray(jax.random.randint(jax.random.PRNGKey(3), (4, 8), 0,
                                          cfg.vocab))
    h, _, _, _ = ee.stage1_prefill(params, cfg, spec, toks)
    ref, _ = ee.stage2_prefill(params, cfg, spec, h)
    got, _ = ee.stage2_prefill(p2, cfg, spec, h, presliced_params=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_split_params_stage_fns_bitwise(tiny_cfg, tiny_spec, tiny_params):
    """The placement-aware _stage_fns (split + presliced params) must equal
    the pre-refactor full-tree jitted stage programs bit for bit — the
    invariant the whole disaggregated path rests on."""
    toks = jnp.asarray(jax.random.randint(jax.random.PRNGKey(0), (6, 8), 0,
                                          tiny_cfg.vocab))

    @jax.jit
    def s1_ref(tokens):          # the pre-split builder's stage-1 program
        h, _, logits, _ = ee.stage1_prefill(tiny_params, tiny_cfg,
                                            tiny_spec, tokens)
        return h, logits

    @jax.jit
    def s2_ref(slab):            # the pre-split builder's stage-2 program
        logits, _ = ee.stage2_prefill(tiny_params, tiny_cfg, tiny_spec,
                                      slab)
        return logits

    s1, s2 = SL._stage_fns(tiny_params, tiny_cfg, tiny_spec)
    h, logits = s1(toks)
    h_ref, logits_ref = s1_ref(toks)
    np.testing.assert_array_equal(np.asarray(h), np.asarray(h_ref))
    np.testing.assert_array_equal(np.asarray(logits), np.asarray(logits_ref))
    np.testing.assert_array_equal(np.asarray(s2(h)),
                                  np.asarray(s2_ref(h_ref)))


def test_split_params_decode_bitwise(tiny_cfg, tiny_spec, tiny_params):
    """stage2_decode over the stage-2 param slice (param_base_sb path) must
    match the full-tree call bit for bit."""
    from repro.models import transformer as T
    prompt = jnp.asarray(jax.random.randint(jax.random.PRNGKey(1), (3, 6), 0,
                                            tiny_cfg.vocab))
    _, caches, _ = T.prefill(tiny_params["backbone"], tiny_cfg, prompt,
                             max_len=8)
    _, c2 = ee.split_caches(tiny_cfg, tiny_spec, caches)
    h = jax.random.normal(jax.random.PRNGKey(2), (3, 1, tiny_cfg.d_model))
    step = jnp.int32(6)
    ref_logits, ref_caches = ee.stage2_decode(tiny_params, tiny_cfg,
                                              tiny_spec, h, c2, step)
    _, p2 = ee.split_params(tiny_cfg, tiny_spec, tiny_params)
    got_logits, got_caches = ee.stage2_decode(p2, tiny_cfg, tiny_spec, h, c2,
                                              step, presliced_params=True)
    np.testing.assert_array_equal(np.asarray(got_logits),
                                  np.asarray(ref_logits))
    for a, b in zip(jax.tree.leaves(got_caches), jax.tree.leaves(ref_caches)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# multi-device placement semantics (in-process; CI disaggregated job)
# ---------------------------------------------------------------------------

def _placement_5_3():
    from repro.core.stage_mesh import StageMeshPlan
    return StagePlacement.from_plan(StageMeshPlan.from_chips(5, 3))


@_multi_device
def test_executor_residency_disjoint():
    pl = _placement_5_3()
    assert pl.disaggregated
    ids1 = {d.id for d in pl.ex1.devices}
    ids2 = {d.id for d in pl.ex2.devices}
    assert not ids1 & ids2 and len(ids1) == 5 and len(ids2) == 3
    x = jnp.ones((6, 4))
    on1 = pl.ex1.place(x)
    assert {d.id for d in on1.sharding.device_set} == ids1
    # cross-executor place IS the stage-boundary device-to-device transfer
    on2 = pl.ex2.place(on1)
    assert {d.id for d in on2.sharding.device_set} == ids2
    np.testing.assert_array_equal(np.asarray(on2), np.asarray(x))


@_multi_device
def test_place_io_shards_when_divisible():
    pl = _placement_5_3()
    batch = jnp.ones((10, 4))        # 10 % dp1(5) == 0 -> sharded
    sharded = pl.ex1.place_io(batch)
    assert not sharded.sharding.is_fully_replicated
    odd = jnp.ones((7, 4))           # 7 % 5 != 0 -> replicated fallback
    repl = pl.ex1.place_io(odd)
    assert repl.sharding.is_fully_replicated


@_multi_device
def test_disagg_server_params_and_ring_resident():
    """Stage-2 params and the ring live on submesh 2; stage-1 params on
    submesh 1."""
    cfg, spec, params, toks = _tiny_setup()
    pl = _placement_5_3()
    sc = SL.ServeConfig(capacity=4, queue_depth=2, c_thr=1.1)  # all hard
    srv = SL.build(params, cfg, spec, sc, mode="prefill", scheduler=None,
                   placement=pl)
    SL.serve_dataset(srv, toks, batch=8)
    ids2 = {d.id for d in pl.ex2.devices}
    assert {d.id
            for d in srv.ring._buf["ids"].sharding.device_set} <= ids2


@_multi_device
def test_disagg_prefill_server_bitwise():
    cfg, spec, params, toks = _tiny_setup()
    sc = SL.ServeConfig(capacity=4, queue_depth=4, c_thr=spec.c_thr)
    build = lambda pl: SL.build(params, cfg, spec, sc, mode="prefill",
                                scheduler=None, placement=pl)
    r_one = SL.serve_dataset(build(None), toks, batch=8)
    dis = build(_placement_5_3())
    r_dis = SL.serve_dataset(dis, toks, batch=8)
    assert set(r_dis) == set(r_one)
    for sid in r_one:
        np.testing.assert_array_equal(r_dis[sid], r_one[sid])
    assert dis.stats.stage1_chips == 5 and dis.stats.stage2_chips == 3


@_multi_device
def test_disagg_continuous_paged_streams():
    """The paged decode scheduler on a 5+3 split serves the same streams
    as on one device, with its page pool on the stage-2 submesh."""
    cfg, spec, params, toks = _tiny_setup()
    sc = SL.ServeConfig(capacity=3, queue_depth=2, c_thr=spec.c_thr)
    streams = []
    for pl in (None, _placement_5_3()):
        sched = SL.build(params, cfg, spec, sc, n_slots=6, max_len=16,
                         page_size=4, placement=pl)
        for i in range(6):
            sched.submit(SL.Request(i, toks[i], 5))
        streams.append(sched.run())
    assert streams[1] == streams[0]
    ids2 = {d.id for d in _placement_5_3().ex2.devices}
    for leaf in jax.tree.leaves(sched._pool):
        assert {d.id for d in leaf.sharding.device_set} <= ids2


def _tiny_setup():
    from repro.models.config import ArchConfig
    cfg = ArchConfig(name="tiny-dense", family="dense", n_layers=4,
                     d_model=32, n_heads=4, n_kv_heads=2, d_ff=64, vocab=64,
                     dtype="float32", param_dtype="float32",
                     tie_embeddings=True)
    spec = ee.EarlyExitSpec(exit_layer=2, c_thr=0.3)
    params = ee.init_ee_params(jax.random.PRNGKey(0), cfg, spec)
    toks = np.asarray(jax.random.randint(jax.random.PRNGKey(9), (24, 8), 0,
                                         cfg.vocab))
    return cfg, spec, params, toks


# ---------------------------------------------------------------------------
# subprocess: the acceptance bar on every tier-1 run, q in {0.1, 0.3, 0.5}
# (the main test process must keep 1 device — conftest contract)
# ---------------------------------------------------------------------------

def test_disaggregated_parity_subprocess():
    """Disaggregated TwoStageServer AND continuous paged decode scheduler
    against their single-device runs at q ∈ {0.1, 0.3, 0.5} under
    --xla_force_host_platform_device_count=8, q-proportional chip splits.

    Where the stage-1 IO is replicated (q = 0.1, 0.3: the batch of 8 does
    not divide the stage-1 submesh) every device runs the single-device
    program and the prefill servers are bitwise identical. At q = 0.5 the
    4+4 split shards the batch, so each device runs stage 1 at a
    per-device batch of 2, and the CPU backend's matmuls round differently
    at batch 2 than at batch 8 (sharded stage 1 == single-device batch-2
    stage 1 bitwise; both differ from batch 8 in the last ulp). There the
    prefill contract is the same argmax per sample. The decode contract is
    the same token streams at every q."""
    code = ("import os\n"
            "os.environ['XLA_FLAGS']="
            "'--xla_force_host_platform_device_count=8'\n"
            "import sys; sys.path.insert(0, 'src')\n" + textwrap.dedent("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.core import early_exit as ee
    from repro.core import exit_decision as ed
    from repro.core.stage_mesh import StageMeshPlan
    from repro.models.config import ArchConfig
    from repro.launch.serve import serve_requests
    from repro.runtime import serve_loop as SL
    from repro.runtime.stage_executor import StagePlacement

    cfg = ArchConfig(name="t", family="dense", n_layers=4, d_model=32,
                     n_heads=4, n_kv_heads=2, d_ff=64, vocab=64,
                     dtype="float32", param_dtype="float32",
                     tie_embeddings=True)
    spec0 = ee.EarlyExitSpec(exit_layer=2, c_thr=0.5)
    params = ee.init_ee_params(jax.random.PRNGKey(0), cfg, spec0)
    toks = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (32, 8), 0,
                                         cfg.vocab))
    _, _, exit_logits, _ = ee.stage1_prefill(params, cfg, spec0,
                                             jnp.asarray(toks))
    conf = ed.softmax_confidence(exit_logits)
    dconf = SL.decode_step0_confidences(params, cfg, spec0, toks[:8],
                                        max_len=8 + 5)
    for q in (0.1, 0.3, 0.5):
        pl = StagePlacement.from_plan(
            StageMeshPlan.proportional(q, jax.device_count()))
        c_thr = float(jnp.quantile(conf, q))
        spec = ee.EarlyExitSpec(exit_layer=2, c_thr=c_thr)
        sc = SL.ServeConfig(capacity=4, queue_depth=2, c_thr=c_thr)
        r1, r2 = (SL.serve_dataset(
            SL.build(params, cfg, spec, sc, mode="prefill", scheduler=None,
                     placement=p), toks, batch=8) for p in (None, pl))
        sharded = not pl.ex1.place_io(jnp.zeros(8)).sharding \\
            .is_fully_replicated
        assert sharded == (q == 0.5), q
        assert set(r1) == set(r2)
        if sharded:
            assert all(np.argmax(r1[i]) == np.argmax(r2[i]) for i in r1), q
        else:
            assert all(np.array_equal(r1[i], r2[i]) for i in r1), q
        cd = float(jnp.quantile(dconf, q))
        dspec = ee.EarlyExitSpec(exit_layer=2, c_thr=cd)
        dsc = SL.ServeConfig(capacity=3, queue_depth=2, c_thr=cd)
        o1, o2 = (serve_requests(
            SL.build(params, cfg, dspec, dsc, n_slots=8, max_len=16,
                     page_size=4, placement=p), toks[:8], 5)
            for p in (None, pl))
        assert o1 == o2, q
        print("q", q, "OK", "sharded" if sharded else "replicated")
    print("PARITY_ALL_OK")
    """))
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, cwd=_REPO_ROOT, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "PARITY_ALL_OK" in r.stdout
