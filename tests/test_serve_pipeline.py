"""Device-resident serving pipeline: kernel dispatch parity on the edge
cases the runtime actually hits, ring-buffer semantics, and end-to-end
equivalence of the device server against the seed host-loop path."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import early_exit as ee
from repro.kernels import dispatch
from repro.kernels.exit_decision.kernel import exit_decision_pallas
from repro.kernels.exit_decision.ref import exit_decision_ref
from repro.kernels.gather_compact.ref import gather_compact_ref
from repro.runtime import serve_loop as SL


# ---------------------------------------------------------------------------
# dispatch layer
# ---------------------------------------------------------------------------

@pytest.fixture(autouse=True)
def _hermetic_backend_env(monkeypatch):
    """Keep the suite hermetic to a stray REPRO_KERNEL_BACKEND left in the
    env — EXCEPT the CI interpret job's explicit opt-in, which must reach
    the dispatch layer so the e2e server tests execute the Pallas kernel
    bodies rather than the jnp refs."""
    import os
    if os.environ.get("REPRO_KERNEL_BACKEND") != "interpret":
        monkeypatch.delenv("REPRO_KERNEL_BACKEND", raising=False)


def test_backend_resolution_off_tpu(monkeypatch):
    monkeypatch.delenv("REPRO_KERNEL_BACKEND", raising=False)
    assert dispatch.kernel_backend() == "ref"        # auto on CPU
    with pytest.raises(RuntimeError, match="needs a TPU"):
        dispatch.kernel_backend("pallas")
    assert dispatch.kernel_backend("ref") == "ref"
    with pytest.raises(ValueError):
        dispatch.kernel_backend("vulkan")


def test_set_backend_override(monkeypatch):
    monkeypatch.delenv("REPRO_KERNEL_BACKEND", raising=False)
    dispatch.set_backend("interpret")
    try:
        assert dispatch.kernel_backend() == "interpret"
    finally:
        dispatch.set_backend(None)
    assert dispatch.kernel_backend() == "ref"
    with pytest.raises(ValueError):
        dispatch.set_backend("nope")


def test_dispatch_backends_agree():
    x = jax.random.normal(jax.random.PRNGKey(0), (6, 520)) * 4.0
    mask = jax.random.bernoulli(jax.random.PRNGKey(1), 0.4, (6,))
    for backend in ("interpret", "ref"):
        e, p, c = dispatch.exit_decision_op(x, 0.7, backend=backend)
        er, pr, cr = exit_decision_ref(x, 0.7)
        np.testing.assert_array_equal(np.asarray(e), np.asarray(er))
        np.testing.assert_array_equal(np.asarray(p), np.asarray(pr))
        np.testing.assert_allclose(np.asarray(c), np.asarray(cr),
                                   rtol=1e-5, atol=1e-6)
        s, i, n = dispatch.gather_compact_op(x, mask, 4, backend=backend)
        sr, ir, nr = gather_compact_ref(x, mask, 4)
        np.testing.assert_allclose(np.asarray(s), np.asarray(sr))
        np.testing.assert_array_equal(np.asarray(i), np.asarray(ir))
        assert int(n) == int(nr)


# ---------------------------------------------------------------------------
# kernel parity on runtime edge cases (interpret-mode kernel body vs oracle)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("capacity", [2, 4])
def test_gather_compact_overflow(capacity):
    """n_hard > capacity: slab keeps the first ``capacity`` hard rows in
    order, ids report them, n_hard reports the true (overflowing) count."""
    x = jnp.arange(16, dtype=jnp.float32).reshape(8, 2)
    mask = jnp.array([1, 1, 0, 1, 1, 1, 0, 1], bool)       # 6 hard rows
    for backend in ("interpret", "ref"):
        s, ids, n = dispatch.gather_compact_op(x, mask, capacity,
                                               backend=backend)
        assert int(n) == 6
        hard_rows = [0, 1, 3, 4, 5, 7][:capacity]
        np.testing.assert_array_equal(np.asarray(ids), hard_rows)
        np.testing.assert_allclose(np.asarray(s), np.asarray(x)[hard_rows])


@pytest.mark.parametrize("backend", ["interpret", "ref"])
def test_gather_compact_all_and_none_exit(backend):
    x = jax.random.normal(jax.random.PRNGKey(2), (5, 3))
    none_hard = jnp.zeros((5,), bool)                      # everyone exits
    s, ids, n = dispatch.gather_compact_op(x, none_hard, 5, backend=backend)
    assert int(n) == 0
    np.testing.assert_array_equal(np.asarray(ids), [-1] * 5)
    all_hard = jnp.ones((5,), bool)                        # nobody exits
    s, ids, n = dispatch.gather_compact_op(x, all_hard, 5, backend=backend)
    assert int(n) == 5
    np.testing.assert_array_equal(np.asarray(ids), np.arange(5))
    np.testing.assert_allclose(np.asarray(s), np.asarray(x))


@pytest.mark.parametrize("vocab,block_v", [(300, 128), (520, 256), (97, 128)])
def test_exit_decision_vocab_not_block_multiple(vocab, block_v):
    """Vocab padding in the last tile must not perturb (m, l, argmax)."""
    x = (jax.random.normal(jax.random.PRNGKey(vocab), (9, vocab)) * 5.0
         ).astype(jnp.float32)
    ek, pk, ck = exit_decision_pallas(x, 0.6, block_v=block_v,
                                      interpret=True)
    er, pr, cr = exit_decision_ref(x, 0.6)
    np.testing.assert_array_equal(np.asarray(ek), np.asarray(er))
    np.testing.assert_array_equal(np.asarray(pk), np.asarray(pr))
    np.testing.assert_allclose(np.asarray(ck), np.asarray(cr),
                               rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# device ring buffer
# ---------------------------------------------------------------------------

def _enq(buf, rows, ids, pad_to=None):
    """Helper: enqueue a compacted slab (valid prefix + -1 flush slots)."""
    rows = jnp.asarray(rows, jnp.float32)
    ids = jnp.asarray(ids, jnp.int32)
    if pad_to and rows.shape[0] < pad_to:
        k = pad_to - rows.shape[0]
        rows = jnp.concatenate([rows, jnp.zeros((k,) + rows.shape[1:],
                                                rows.dtype)])
        ids = jnp.concatenate([ids, jnp.full((k,), -1, jnp.int32)])
    return SL.ring_enqueue(buf, rows, ids)


def test_ring_enqueue_drain_basic():
    buf = SL.ring_init(8, (2,), jnp.float32)
    buf = _enq(buf, [[0, 0], [1, 1], [2, 2]], [10, 11, 12], pad_to=4)
    assert int(buf["count"]) == 3
    buf, bucket, ids = SL.ring_drain(buf, 2)
    np.testing.assert_array_equal(np.asarray(ids), [10, 11])
    np.testing.assert_allclose(np.asarray(bucket)[:2], [[0, 0], [1, 1]])
    assert int(buf["count"]) == 1 and int(buf["head"]) == 2
    buf, bucket, ids = SL.ring_drain(buf, 2)          # partial drain
    np.testing.assert_array_equal(np.asarray(ids), [12, -1])
    assert int(buf["count"]) == 0


def test_ring_pytree_payload():
    """The generalized ring carries arbitrary pytrees: every leaf keeps its
    own (size, *row) slab under the shared cursors/id lane, and enqueue/
    drain preserve per-leaf row association by sample id."""
    row = {"h": jax.ShapeDtypeStruct((2,), jnp.float32),
           "cache": {"k": jax.ShapeDtypeStruct((3, 2), jnp.float32),
                     "step": jax.ShapeDtypeStruct((), jnp.int32)}}
    buf = SL.ring_init(4, row)
    slab = {"h": jnp.arange(6, dtype=jnp.float32).reshape(3, 2),
            "cache": {"k": jnp.arange(18, dtype=jnp.float32).reshape(3, 3, 2),
                      "step": jnp.array([7, 8, 9], jnp.int32)}}
    buf = SL.ring_enqueue(buf, slab, jnp.array([10, 11, 12], jnp.int32))
    assert int(buf["count"]) == 3
    buf, bucket, ids = SL.ring_drain(buf, 2)
    np.testing.assert_array_equal(np.asarray(ids), [10, 11])
    np.testing.assert_allclose(np.asarray(bucket["h"]),
                               np.asarray(slab["h"][:2]))
    np.testing.assert_allclose(np.asarray(bucket["cache"]["k"]),
                               np.asarray(slab["cache"]["k"][:2]))
    np.testing.assert_array_equal(np.asarray(bucket["cache"]["step"]), [7, 8])
    assert int(buf["count"]) == 1


def test_ring_wraparound():
    """Writes and reads must wrap modulo the ring size without clobbering
    undrained samples."""
    buf = SL.ring_init(4, (1,), jnp.float32)
    buf = _enq(buf, [[0.0], [1.0], [2.0]], [0, 1, 2])
    buf, _, ids = SL.ring_drain(buf, 2)               # head -> 2
    np.testing.assert_array_equal(np.asarray(ids), [0, 1])
    buf = _enq(buf, [[3.0], [4.0], [5.0]], [3, 4, 5]) # wraps to slots 0,1
    assert int(buf["count"]) == 4
    buf, bucket, ids = SL.ring_drain(buf, 4)
    np.testing.assert_array_equal(np.asarray(ids), [2, 3, 4, 5])
    np.testing.assert_allclose(np.asarray(bucket)[:, 0], [2, 3, 4, 5])


def test_ring_flush_slots_dropped():
    """-1 (flush) slots in the incoming slab must not consume ring space."""
    buf = SL.ring_init(4, (1,), jnp.float32)
    buf = _enq(buf, [[7.0]], [42], pad_to=4)
    assert int(buf["count"]) == 1
    buf, _, ids = SL.ring_drain(buf, 4)
    np.testing.assert_array_equal(np.asarray(ids), [42, -1, -1, -1])


# ---------------------------------------------------------------------------
# end-to-end: device-resident server vs the seed host-loop path
# ---------------------------------------------------------------------------

def _serve_both(params, cfg, spec, sc, toks, batch):
    s1, s2 = SL._stage_fns(params, cfg, spec)
    dev = SL.TwoStageServer(s1, s2, sc)
    host = SL.HostLoopServer(s1, s2, sc)
    return (SL.serve_dataset(dev, toks, batch=batch), dev,
            SL.serve_dataset(host, toks, batch=batch), host)


def test_device_server_matches_host_loop_exactly(tiny_cfg, tiny_params):
    """The tentpole parity bar: merged logits identical (bitwise) between
    the new device-resident path and the seed host loop."""
    spec = ee.EarlyExitSpec(exit_layer=2, c_thr=0.3)
    N, B = 24, 8
    toks = np.asarray(jax.random.randint(jax.random.PRNGKey(9), (N, 8), 0,
                                         tiny_cfg.vocab))
    sc = SL.ServeConfig(capacity=4, queue_depth=4, c_thr=spec.c_thr)
    rd, dev, rh, host = _serve_both(tiny_params, tiny_cfg, spec, sc, toks, B)
    assert set(rd) == set(rh) == set(range(N))
    for sid in range(N):
        np.testing.assert_array_equal(rd[sid], rh[sid])
    assert dev.stats.n_samples == host.stats.n_samples == N
    assert dev.stats.n_exited == host.stats.n_exited
    assert dev.stats.n_stage2 == host.stats.n_stage2


def test_device_server_backpressure_stall(tiny_cfg, tiny_params):
    """All-hard traffic through a ring barely one batch deep: stage 1 must
    stall (full-bucket drains first), never deadlock, never drop."""
    spec = ee.EarlyExitSpec(exit_layer=2, c_thr=1.0)   # nothing exits
    N, B = 15, 3
    toks = np.asarray(jax.random.randint(jax.random.PRNGKey(3), (N, 8), 0,
                                         tiny_cfg.vocab))
    # ring = one bucket of 4: a second all-hard batch of 3 cannot fit behind
    # the 3 residents, so stage 1 must stall and drain a partial bucket
    sc = SL.ServeConfig(capacity=4, queue_depth=1, c_thr=spec.c_thr)
    rd, dev, rh, host = _serve_both(tiny_params, tiny_cfg, spec, sc, toks, B)
    assert set(rd) == set(range(N))
    assert dev.stats.n_stage2 == N and dev.stats.n_exited == 0
    assert dev.stats.n_stalls > 0
    for sid in range(N):
        np.testing.assert_array_equal(rd[sid], rh[sid])


def test_device_server_batch_larger_than_ring(tiny_cfg, tiny_params):
    """An all-hard batch twice the ring size must still serve correctly:
    the enqueue chunks, stalling stage 1 while full buckets drain."""
    spec = ee.EarlyExitSpec(exit_layer=2, c_thr=1.0)   # nothing exits
    N, B = 16, 8
    toks = np.asarray(jax.random.randint(jax.random.PRNGKey(7), (N, 8), 0,
                                         tiny_cfg.vocab))
    sc = SL.ServeConfig(capacity=2, queue_depth=2,     # ring of 4 < B of 8
                        c_thr=spec.c_thr)
    rd, dev, rh, host = _serve_both(tiny_params, tiny_cfg, spec, sc, toks, B)
    assert set(rd) == set(range(N))
    assert dev.stats.n_stage2 == N and dev.stats.n_stalls > 0
    for sid in range(N):
        np.testing.assert_array_equal(rd[sid], rh[sid])


def test_device_server_matches_serve_batch(tiny_cfg, tiny_params):
    """New path vs the one-shot fused pipeline (different jit partitions,
    so allclose rather than bitwise)."""
    spec = ee.EarlyExitSpec(exit_layer=2, c_thr=0.5)
    N = 16
    toks = np.asarray(jax.random.randint(jax.random.PRNGKey(5), (N, 8), 0,
                                         tiny_cfg.vocab))
    server = SL.build_server(tiny_params, tiny_cfg, spec,
                             SL.ServeConfig(capacity=4, c_thr=spec.c_thr))
    results = SL.serve_dataset(server, toks, batch=8)
    one = ee.serve_batch(tiny_params, tiny_cfg, spec, jnp.asarray(toks),
                         capacity=N)
    merged = np.asarray(one["logits"])
    for sid in range(N):
        np.testing.assert_allclose(results[sid], merged[sid], rtol=2e-4,
                                   atol=2e-4)


def test_device_server_bounded_pending(tiny_cfg, tiny_params):
    """With a tiny max_pending, long streams harvest results incrementally
    during submit (bounded device memory) and still match the host loop."""
    spec = ee.EarlyExitSpec(exit_layer=2, c_thr=0.3)
    N, B = 32, 4
    toks = np.asarray(jax.random.randint(jax.random.PRNGKey(11), (N, 8), 0,
                                         tiny_cfg.vocab))
    sc = SL.ServeConfig(capacity=2, queue_depth=4, c_thr=spec.c_thr,
                        max_pending=2)
    rd, dev, rh, host = _serve_both(tiny_params, tiny_cfg, spec, sc, toks, B)
    assert set(rd) == set(range(N))
    assert len(dev._easy) == 0 and len(dev._buckets) == 0
    for sid in range(N):
        np.testing.assert_array_equal(rd[sid], rh[sid])
    # backlog stayed bounded: results already present before the final flush
    s1, s2 = SL._stage_fns(tiny_params, tiny_cfg, spec)
    srv = SL.TwoStageServer(s1, s2, sc)
    partial: dict = {}
    for lo in range(0, N, B):
        srv.submit(toks[lo:lo + B], np.arange(lo, lo + B), partial)
        assert len(srv._easy) + len(srv._buckets) <= sc.max_pending
    assert partial                      # harvested incrementally
    srv.flush(partial)
    assert set(partial) == set(range(N))


def test_serve_stats_running_aggregate():
    """bucket_fill is an O(1) running aggregate, not an unbounded list."""
    st = SL.ServeStats()
    assert st.mean_bucket_fill == 0.0
    for f in (1.0, 0.5, 0.75):
        st.record_bucket(f)
    assert st.n_buckets == 3
    np.testing.assert_allclose(st.mean_bucket_fill, 0.75)
    assert "mean_bucket_fill" in st.as_dict()
    assert not any(isinstance(v, list) for v in vars(st).values())
