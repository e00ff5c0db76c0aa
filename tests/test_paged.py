"""Paged KV cache: the block-table decode path end to end.

Layers under test, bottom up: the ``paged_attention`` kernel family
(gather + tail-page append, interpret kernel vs jnp ref, bitwise), the
model decode paths (``attention_decode``/``mla_decode`` paged vs dense —
bitwise, because both route the gathered cache through ONE masked decode
core), the continuous scheduler (paged streams == the dense host oracle
across the calibrated q grid, incl. ring wraparound/overflow and a
page-constrained pool that exercises admission backpressure; fns it
cannot page rejected), the ``PageAllocator`` invariants
(deterministic sweep always; hypothesis when available), live migration
(paged pool re-placed, rollback restores allocator state exactly), and an
8-device disaggregated subprocess bar.
"""
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import early_exit as ee
from repro.kernels import dispatch
from repro.kernels.paged_attention import (paged_gather_append_pallas,
                                           paged_gather_append_ref)
from repro.models import attention as A
from repro.models import mla as M
from repro.models.config import ArchConfig, MLAConfig
from repro.runtime import faults
from repro.runtime import serve_loop as SL
from repro.runtime.migration import MigrationPlan
from repro.runtime.scheduler import (ContinuousScheduler, LogicalClock,
                                     PageAllocator, Request, ServeConfig,
                                     _alloc_row, _free_row)

_REPO_ROOT = str(Path(__file__).resolve().parent.parent)


# ---------------------------------------------------------------------------
# kernel family: interpret kernel vs jnp ref, bitwise
# ---------------------------------------------------------------------------

def _rand_case(key, B, M_pages, page, n_pages, fa, fb):
    ka, kb, kc, kd, ke = jax.random.split(key, 5)
    a_pool = jax.random.normal(ka, (n_pages, page) + fa, jnp.float32)
    b_pool = jax.random.normal(kb, (n_pages, page) + fb, jnp.float32)
    # page 0 is NULL: all zeros by contract
    a_pool = a_pool.at[0].set(0.0)
    b_pool = b_pool.at[0].set(0.0)
    a_new = jax.random.normal(kc, (B,) + fa, jnp.float32)
    b_new = jax.random.normal(kd, (B,) + fb, jnp.float32)
    # each row owns a disjoint page run, null-padded to a random prefix
    perm = 1 + jax.random.permutation(ke, n_pages - 1)[:B * M_pages]
    bt = perm.reshape(B, M_pages).astype(jnp.int32)
    owned = jax.random.randint(ke, (B,), 1, M_pages + 1)
    bt = jnp.where(jnp.arange(M_pages)[None, :] < owned[:, None], bt, 0)
    pos = jax.random.randint(kc, (B,), 0, owned * page).astype(jnp.int32)
    return a_pool, b_pool, a_new, b_new, bt, pos


@pytest.mark.parametrize("B,M_pages,page,fa,fb", [
    (4, 3, 4, (16,), (16,)),         # flattened GQA-shaped K/V (KH*hd)
    (2, 2, 8, (16,), (4,)),          # MLA-shaped (latent, rope)
    (6, 4, 2, (4,), (4,)),
])
def test_kernel_matches_ref_bitwise(B, M_pages, page, fa, fb):
    n_pages = 1 + B * M_pages + 3                # +3 unowned pages
    args = _rand_case(jax.random.PRNGKey(B * 7 + page), B, M_pages, page,
                      n_pages, fa, fb)
    ref = paged_gather_append_ref(*args)
    got = paged_gather_append_pallas(*args, interpret=True)
    for r, g in zip(ref, got):
        np.testing.assert_array_equal(np.asarray(r), np.asarray(g))
    # the NULL page is never written
    assert not np.asarray(got[2][0]).any()
    assert not np.asarray(got[3][0]).any()


def test_sentinel_pos_drops_append():
    """Rows at pos >= M*page (parked/flush sentinels) must gather without
    appending — the pools come back byte-identical."""
    B, M_pages, page, n_pages = 3, 2, 4, 1 + 6
    a_pool, b_pool, a_new, b_new, bt, _ = _rand_case(
        jax.random.PRNGKey(0), B, M_pages, page, n_pages, (8,), (8,))
    pos = jnp.full((B,), M_pages * page, jnp.int32)
    ga, gb, ap, bp = paged_gather_append_ref(a_pool, b_pool, a_new, b_new,
                                             bt, pos)
    np.testing.assert_array_equal(np.asarray(ap), np.asarray(a_pool))
    np.testing.assert_array_equal(np.asarray(bp), np.asarray(b_pool))
    got = paged_gather_append_pallas(a_pool, b_pool, a_new, b_new, bt, pos,
                                     interpret=True)
    for r, g in zip((ga, gb, ap, bp), got):
        np.testing.assert_array_equal(np.asarray(r), np.asarray(g))


def test_dispatch_op_routes_backends():
    """The dispatch layer flattens multi-axis feature dims for the kernel
    and restores them — every backend bitwise-identical on GQA shapes."""
    B, M_pages, page, n_pages = 2, 2, 4, 1 + 4
    args = _rand_case(jax.random.PRNGKey(3), B, M_pages, page, n_pages,
                      (2, 4), (2, 4))
    a = dispatch.paged_gather_append_op(*args, donate=False)
    b = dispatch.paged_gather_append_op(*args, backend="interpret",
                                        donate=False)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------------------
# model decode: paged vs dense, bitwise (one masked core, same cache bytes)
# ---------------------------------------------------------------------------

def _decode_parity(init_dense, init_paged, decode, params, cfg, B, d,
                   max_len, page, n_steps, S0):
    key = jax.random.PRNGKey(9)
    dense = init_dense(cfg, B, max_len)
    paged = init_paged(cfg, B, max_len, page, 1 + B * (max_len // page))
    Mp = max_len // page
    bt = 1 + jnp.arange(B * Mp, dtype=jnp.int32).reshape(B, Mp)
    paged = dict(paged, bt=bt)
    pos = jnp.full((B,), S0, jnp.int32)
    for t in range(n_steps):
        x = jax.random.normal(jax.random.fold_in(key, t), (B, 1, d),
                              jnp.float32)
        out_d, dense = decode(params, cfg, x, dense, pos)
        out_p, paged = decode(params, cfg, x, paged, pos)
        np.testing.assert_array_equal(np.asarray(out_d), np.asarray(out_p))
        pos = pos + 1


def test_attention_decode_paged_bitwise():
    cfg = ArchConfig(name="t", family="dense", n_layers=2, d_model=32,
                     n_heads=4, n_kv_heads=2, d_ff=64, vocab=64,
                     dtype="float32", param_dtype="float32")
    params = A.init_attention(jax.random.PRNGKey(0), cfg)
    _decode_parity(A.init_kv_cache, A.init_paged_kv_cache,
                   A.attention_decode, params, cfg, B=3, d=32, max_len=16,
                   page=4, n_steps=10, S0=2)


def test_mla_decode_paged_bitwise():
    cfg = ArchConfig(name="t", family="dense", n_layers=2, d_model=32,
                     n_heads=4, n_kv_heads=4, d_ff=64, vocab=64,
                     dtype="float32", param_dtype="float32",
                     mla=MLAConfig(kv_lora_rank=8, qk_nope_head_dim=4,
                                   qk_rope_head_dim=4, v_head_dim=4))
    params = M.init_mla(jax.random.PRNGKey(0), cfg)
    _decode_parity(M.init_mla_cache, M.init_paged_mla_cache, M.mla_decode,
                   params, cfg, B=3, d=32, max_len=16, page=4, n_steps=10,
                   S0=2)


@pytest.fixture(scope="module")
def prompt(tiny_cfg):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(77), (6, 6), 0,
                                         tiny_cfg.vocab))


# ---------------------------------------------------------------------------
# continuous scheduler: paged streams == the dense host oracle
# ---------------------------------------------------------------------------

N_TOKS = [7, 3, 5, 1, 7, 2]


def _run_sched(fns, sc, prompt, n_toks, *, n_slots, max_len, **kw):
    s = ContinuousScheduler(fns, sc, n_slots=n_slots, max_len=max_len,
                            clock=LogicalClock(), **kw)
    for i, n in enumerate(n_toks):
        s.submit(Request(sample_id=i, prompt=prompt[i], n_tokens=n))
    return s.drain(), s


def _expect(oracle_tokens, n_toks):
    return {i: [int(x) for x in oracle_tokens[i][:n]]
            for i, n in enumerate(n_toks)}


def test_continuous_paged_q_grid(tiny_cfg, tiny_params, tiny_spec, prompt):
    """The acceptance bar, single-device: paged continuous streams equal
    the dense host-loop oracle at calibrated q ∈ {0.1, 0.3, 0.5}."""
    max_len = prompt.shape[1] + max(N_TOKS) + 3   # 16: a page multiple
    conf = np.asarray(SL.decode_step0_confidences(
        tiny_params, tiny_cfg, tiny_spec, prompt, max_len=max_len))
    dense_fns = SL.decode_stage_fns(tiny_params, tiny_cfg, tiny_spec)
    paged_fns = SL.decode_stage_fns(tiny_params, tiny_cfg, tiny_spec,
                                    page_size=4)
    for q in (0.1, 0.3, 0.5):
        c_thr = float(np.quantile(conf, q))
        sc = ServeConfig(capacity=2, queue_depth=2, c_thr=c_thr)
        oracle = SL.HostLoopDecoder(dense_fns, sc).generate(prompt,
                                                            max(N_TOKS))
        want = _expect(oracle["tokens"], N_TOKS)
        res_p, sp = _run_sched(paged_fns, sc, prompt, N_TOKS, n_slots=3,
                               max_len=max_len)
        assert res_p == want, q
        # drained pool: every page came home
        assert sp._alloc.n_free == sp.n_pages
        assert sp.stats.cache_pages_total == sp.n_pages


def test_continuous_paged_ring_overflow(tiny_cfg, tiny_params, tiny_spec,
                                        prompt):
    """All-hard traffic through a ring smaller than the pool: wraparound +
    overflow spill on the paged payload — stalls happen, streams stay
    exact, pages still all come home."""
    sc = ServeConfig(capacity=2, queue_depth=2, c_thr=1.1)
    n_toks = [5] * prompt.shape[0]
    paged_fns = SL.decode_stage_fns(tiny_params, tiny_cfg, tiny_spec,
                                    page_size=4)
    dense_fns = SL.decode_stage_fns(tiny_params, tiny_cfg, tiny_spec)
    oracle = SL.HostLoopDecoder(dense_fns, sc).generate(prompt, 5)
    res, sched = _run_sched(paged_fns, sc, prompt, n_toks,
                            n_slots=prompt.shape[0], max_len=12)
    assert sched.stats.n_stalls > 0
    assert res == _expect(oracle["tokens"], n_toks)
    assert sched._alloc.n_free == sched.n_pages


def test_continuous_paged_tight_pool_backpressure(tiny_cfg, tiny_params,
                                                  tiny_spec, prompt):
    """A pool holding FEWER pages than a full-width store: admission
    backpressures on the free list (head blocks, nothing drops) and the
    streams still match the host oracle."""
    sc = ServeConfig(capacity=2, queue_depth=2, c_thr=0.7)
    dense_fns = SL.decode_stage_fns(tiny_params, tiny_cfg, tiny_spec)
    paged_fns = SL.decode_stage_fns(tiny_params, tiny_cfg, tiny_spec,
                                    page_size=4)
    oracle = SL.HostLoopDecoder(dense_fns, sc).generate(prompt, max(N_TOKS))
    # each request needs at most ceil((6+7-1)/4)=3 pages; 7 pages < 4*4
    res_p, sp = _run_sched(paged_fns, sc, prompt, N_TOKS, n_slots=4,
                           max_len=16, n_pages=7)
    assert res_p == _expect(oracle["tokens"], N_TOKS)
    assert sp._alloc.n_free == 7
    assert sp.stats.n_samples == len(N_TOKS)


def test_continuous_paged_rejects_oversized_request(tiny_cfg, tiny_params,
                                                    tiny_spec, prompt):
    paged_fns = SL.decode_stage_fns(tiny_params, tiny_cfg, tiny_spec,
                                    page_size=4)
    sc = ServeConfig(capacity=2, queue_depth=2, c_thr=0.7)
    s = ContinuousScheduler(paged_fns, sc, n_slots=2, max_len=16,
                            clock=LogicalClock(), n_pages=2)
    s.submit(Request(sample_id=0, prompt=prompt[0], n_tokens=8))
    with pytest.raises(ValueError, match="never be admitted"):
        s.drain()


@pytest.mark.parametrize("fns_page,max_len,match", [
    (None, 16, "lack the paged decode surface"),
    (4, 14, "must be a multiple of page_size"),
], ids=["no_paged_surface", "max_len_not_page_multiple"])
def test_scheduler_rejects_fns_it_cannot_page(tiny_cfg, tiny_params,
                                              tiny_spec, fns_page, max_len,
                                              match):
    """The scheduler serves only the paged store: stage fns built without
    a page size, or a pool width that is not a whole number of pages, are
    refused at construction."""
    fns = SL.decode_stage_fns(tiny_params, tiny_cfg, tiny_spec,
                              page_size=fns_page)
    with pytest.raises(ValueError, match=match):
        ContinuousScheduler(fns, ServeConfig(capacity=2), n_slots=2,
                            max_len=max_len, clock=LogicalClock())


def test_paged_ring_ships_indices_not_rows(tiny_cfg, tiny_params,
                                           tiny_spec, prompt):
    """The perf story the ring gauge tells: the payload hops page INDICES —
    per hard token, the hidden row, the (max_pages,) i32 block-table row
    and the i32 step; no cache row."""
    sc = ServeConfig(capacity=2, queue_depth=2, c_thr=1.1)   # all-hard
    n_toks = [5] * prompt.shape[0]
    paged_fns = SL.decode_stage_fns(tiny_params, tiny_cfg, tiny_spec,
                                    page_size=4)
    _, sp = _run_sched(paged_fns, sc, prompt, n_toks, n_slots=3, max_len=12)
    h_bytes = tiny_cfg.d_model * jnp.dtype(tiny_cfg.act_dtype()).itemsize
    row_bytes = h_bytes + (12 // 4) * 4 + 4
    assert sp.stats.n_stage2 == sum(n - 1 for n in n_toks)
    assert sp.stats.ring_bytes_moved == sp.stats.n_stage2 * row_bytes
    # and the v3 dict carries all of it
    d = sp.stats.as_dict()
    for k in ("cache_pages_total", "cache_pages_in_use", "cache_pages_free",
              "cache_hbm_bytes", "page_fragmentation", "ring_bytes_moved"):
        assert k in d


# ---------------------------------------------------------------------------
# PageAllocator invariants (deterministic sweep; hypothesis when available)
# ---------------------------------------------------------------------------

def _check_alloc_invariants(n_pages, page_size, max_pages, ops_seed,
                            n_ops=60):
    rng = np.random.default_rng(ops_seed)
    alloc = PageAllocator(n_pages, page_size)
    live = {}                                     # handle -> (row, count)
    snap = None
    snap_live_pages = None
    next_h = 0
    for _ in range(n_ops):
        op = rng.integers(0, 4)
        if op == 0:                               # alloc
            count = int(rng.integers(1, max_pages + 1))
            if count > alloc.n_free:
                with pytest.raises(RuntimeError, match="exhausted"):
                    alloc.alloc(count, max_pages=max_pages)
                continue
            row = np.asarray(alloc.alloc(count, max_pages=max_pages))
            assert (row[:count] > 0).all() and (row[count:] == 0).all()
            live[next_h] = (row, count)
            next_h += 1
        elif op == 1 and live:                    # free
            h = list(live)[int(rng.integers(0, len(live)))]
            row, count = live.pop(h)
            alloc.free(jnp.asarray(row), count)
        elif op == 2:                             # snapshot
            snap = alloc.snapshot()
            snap_live_pages = sorted(
                p for row, c in live.values() for p in row[:c])
        elif op == 3 and snap is not None:        # restore + verify exact
            held = sorted(p for row, c in live.values() for p in row[:c])
            alloc.restore(snap)
            # restored free count complements the snapshot's live set
            assert alloc.n_free == alloc.n_pages - len(snap_live_pages)
            # resync the model to the restored reality: drop rows allocated
            # after the snapshot, resurrect nothing (the snapshot's live
            # rows are tracked by the caller in real use — here we just
            # rebuild `live` from the snapshot's complement)
            del held
            live = {i: (np.asarray([p] + [0] * (max_pages - 1)), 1)
                    for i, p in enumerate(snap_live_pages)}
            next_h = len(live)
        # global invariants after every op
        held = [p for row, c in live.values() for p in row[:c]]
        assert len(held) == len(set(held)), "double-allocated page"
        assert 0 not in held, "NULL page allocated"
        assert alloc.n_free + len(held) == alloc.n_pages, "page leak"
        lane_free = set(np.asarray(alloc._lane)[:alloc.n_free].tolist())
        assert len(lane_free) == alloc.n_free
        assert lane_free.isdisjoint(held), \
            "free-list aliases a live block table"
        assert lane_free | set(held) == set(range(1, n_pages + 1)), \
            "free list + live pages != pool"


@pytest.mark.parametrize("seed", range(4))
def test_allocator_invariants_deterministic(seed):
    _check_alloc_invariants(n_pages=13, page_size=4, max_pages=5,
                            ops_seed=seed)


def test_alloc_row_free_row_shapes():
    lane = jnp.arange(1, 9, dtype=jnp.int32)
    row = _alloc_row(lane, 8, 3, max_pages=4)
    np.testing.assert_array_equal(np.asarray(row), [6, 7, 8, 0])
    lane2 = _free_row(lane, 5, row)
    np.testing.assert_array_equal(np.asarray(lane2)[5:], [6, 7, 8])


try:
    from hypothesis import given, settings, strategies as st_h
    _HAVE_HYP = True
except ImportError:                                   # pragma: no cover
    _HAVE_HYP = False


if _HAVE_HYP:
    @settings(max_examples=25, deadline=None)
    @given(n_pages=st_h.integers(2, 40), page_size=st_h.integers(1, 8),
           max_pages=st_h.integers(1, 8), seed=st_h.integers(0, 10_000))
    def test_allocator_invariants_random(n_pages, page_size, max_pages,
                                         seed):
        """No double-allocation, free-list conservation, block tables never
        alias live pages, snapshot/restore exact — under random op traces
        and pool geometries."""
        _check_alloc_invariants(n_pages, page_size,
                                min(max_pages, n_pages), seed, n_ops=40)


# ---------------------------------------------------------------------------
# live migration over the paged pool
# ---------------------------------------------------------------------------

def _mig_sched(fns, *, mig_after, plan, prompt, n_toks, n_pages=None):
    sc = ServeConfig(capacity=2, queue_depth=2, c_thr=0.7)
    sched = ContinuousScheduler(fns, sc, n_slots=3, max_len=16,
                                clock=LogicalClock(), n_pages=n_pages)

    class _Trig:
        ticks = 0

        def on_tick(self, s, nd, nh, conf):
            self.ticks += 1
            if self.ticks == mig_after:
                s.request_migration(plan)
    sched.controller = _Trig()
    for i, n in enumerate(n_toks):
        sched.submit(Request(sample_id=i, prompt=prompt[i], n_tokens=n))
    return sched


def test_paged_migration_stream_equivalence(tiny_cfg, tiny_params,
                                            tiny_spec, prompt):
    """A mid-trace capacity migration over a LIVE paged pool: streams
    bitwise-equal to the unmigrated run, pool and allocator migrated, zero
    rollbacks."""
    paged_fns = SL.decode_stage_fns(tiny_params, tiny_cfg, tiny_spec,
                                    page_size=4)
    base, _ = _run_sched(paged_fns,
                         ServeConfig(capacity=2, queue_depth=2, c_thr=0.7),
                         prompt, N_TOKS, n_slots=3, max_len=16)
    with faults.installed(None):
        sched = _mig_sched(paged_fns, mig_after=3,
                           plan=MigrationPlan(capacity=3, reason="test"),
                           prompt=prompt, n_toks=N_TOKS)
        res = sched.drain()
    assert res == base
    st = sched.stats
    assert st.n_migrations == 1 and st.n_migration_rollbacks == 0
    assert sched._alloc.n_free == sched.n_pages


@pytest.mark.parametrize("point", ["migrate:replace", "migrate:resume"])
def test_paged_migration_rollback_restores_allocator(tiny_cfg, tiny_params,
                                                     tiny_spec, prompt,
                                                     point):
    """A fault mid-migration rolls back with ZERO diffs: streams exact and
    the allocator's free list byte-identical to the pre-migration state
    (the snapshot is a defensive copy, so post-rollback frees cannot
    corrupt it)."""
    paged_fns = SL.decode_stage_fns(tiny_params, tiny_cfg, tiny_spec,
                                    page_size=4)
    base, _ = _run_sched(paged_fns,
                         ServeConfig(capacity=2, queue_depth=2, c_thr=0.7),
                         prompt, N_TOKS, n_slots=3, max_len=16)
    with faults.installed(faults.FaultPlan.parse(f"{point}@1")):
        sched = _mig_sched(paged_fns, mig_after=3,
                           plan=MigrationPlan(capacity=3, reason="test"),
                           prompt=prompt, n_toks=N_TOKS)
        res = sched.drain()
    assert res == base
    st = sched.stats
    assert st.n_migration_rollbacks == 1 and st.n_migrations == 0
    assert sched.sc.capacity == 2                    # old plan restored
    assert sched._alloc.n_free == sched.n_pages      # every page home


@pytest.mark.parametrize("fns_page", [None, 2],
                         ids=["no_paged_surface", "other_page_size"])
def test_paged_migration_rejects_fns_of_another_page_layout(
        tiny_cfg, tiny_params, tiny_spec, prompt, fns_page):
    """Migrating onto stage fns without the paged surface, or with another
    page size, must roll back (the live page pool's layout is not
    convertible mid-serve)."""
    from repro.runtime.stage_executor import StagePlacement
    paged_fns = SL.decode_stage_fns(tiny_params, tiny_cfg, tiny_spec,
                                    page_size=4)
    bad_fns = SL.decode_stage_fns(tiny_params, tiny_cfg, tiny_spec,
                                  page_size=fns_page)
    with faults.installed(None):
        sched = _mig_sched(
            paged_fns, mig_after=3, prompt=prompt, n_toks=N_TOKS,
            plan=MigrationPlan(placement=StagePlacement.single_device(),
                               fns=bad_fns, reason="bad"))
        res = sched.drain()
    base, _ = _run_sched(paged_fns,
                         ServeConfig(capacity=2, queue_depth=2, c_thr=0.7),
                         prompt, N_TOKS, n_slots=3, max_len=16)
    assert res == base
    assert sched.stats.n_migration_rollbacks == 1
    assert sched.fns is paged_fns


# ---------------------------------------------------------------------------
# 8-device disaggregated bar (subprocess on every tier-1 run)
# ---------------------------------------------------------------------------

def test_paged_disaggregated_subprocess():
    """Paged continuous streams equal the dense host oracle, single-device
    and stage-disaggregated, at calibrated q ∈ {0.1, 0.3, 0.5}, under
    --xla_force_host_platform_device_count=8."""
    code = ("import os\n"
            "os.environ['XLA_FLAGS']="
            "'--xla_force_host_platform_device_count=8'\n"
            "import sys; sys.path.insert(0, 'src')\n" + textwrap.dedent("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.core import early_exit as ee
    from repro.core.stage_mesh import StageMeshPlan
    from repro.models.config import ArchConfig
    from repro.runtime import serve_loop as SL
    from repro.runtime.scheduler import (ContinuousScheduler, LogicalClock,
                                         Request)
    from repro.runtime.stage_executor import StagePlacement

    cfg = ArchConfig(name="t", family="dense", n_layers=4, d_model=32,
                     n_heads=4, n_kv_heads=2, d_ff=64, vocab=64,
                     dtype="float32", param_dtype="float32",
                     tie_embeddings=True)
    spec = ee.EarlyExitSpec(exit_layer=2, c_thr=0.5)
    params = ee.init_ee_params(jax.random.PRNGKey(0), cfg, spec)
    prompt = np.asarray(jax.random.randint(jax.random.PRNGKey(77), (6, 6),
                                           0, cfg.vocab))
    n_toks = [5, 3, 5, 1, 4, 2]
    conf = SL.decode_step0_confidences(params, cfg, spec, prompt,
                                       max_len=12)
    dense_fns = SL.decode_stage_fns(params, cfg, spec)

    def run(fns, sc, placement, n_pages=None):
        s = ContinuousScheduler(fns, sc, n_slots=3, max_len=12,
                                placement=placement, clock=LogicalClock(),
                                n_pages=n_pages)
        for i in range(6):
            s.submit(Request(i, prompt[i], n_toks[i]))
        return s.drain()

    for q in (0.1, 0.3, 0.5):
        c_thr = float(jnp.quantile(conf, q))
        sc = SL.ServeConfig(capacity=2, queue_depth=2, c_thr=c_thr)
        oracle = SL.HostLoopDecoder(dense_fns, sc).generate(prompt, 5)
        want = {i: [int(x) for x in oracle["tokens"][i][:n_toks[i]]]
                for i in range(6)}
        pl = StagePlacement.from_plan(
            StageMeshPlan.proportional(max(q, 0.2), jax.device_count()))
        paged_fns = SL.decode_stage_fns(params, cfg, spec, pl, page_size=4)
        assert run(SL.decode_stage_fns(params, cfg, spec, None,
                                       page_size=4),
                   sc, None) == want, ("single", q)
        assert run(paged_fns, sc, pl) == want, ("disagg", q)
        assert run(paged_fns, sc, pl, n_pages=7) == want, ("tight", q)
        print("q", q, "OK")
    print("PAGED_EQUIV_ALL_OK")
    """))
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, cwd=_REPO_ROOT, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "PAGED_EQUIV_ALL_OK" in r.stdout
