"""Fault tolerance + serving runtime: checkpoint restart bit-exactness,
failure injection, straggler mitigation, the two-stage server."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import ckpt as CK
from repro.core import early_exit as ee
from repro.data import pipeline as dp
from repro.runtime import serve_loop as SL
from repro.runtime import train_loop as TL


# ---------------------------------------------------------------------------
# checkpointing
# ---------------------------------------------------------------------------

def test_ckpt_roundtrip_bit_exact(tmp_path):
    tree = {"a": jnp.arange(12, dtype=jnp.float32).reshape(3, 4),
            "b": {"c": jnp.array([1, 2, 3], jnp.int32),
                  "d": jnp.array(2.5, jnp.bfloat16)}}
    CK.save(str(tmp_path), 7, tree, extra={"note": "x"})
    back = CK.restore(str(tmp_path), 7, tree)
    for l1, l2 in zip(jax.tree.leaves(tree), jax.tree.leaves(back)):
        assert l1.dtype == l2.dtype
        np.testing.assert_array_equal(np.asarray(l1, np.float32),
                                      np.asarray(l2, np.float32))


def test_ckpt_latest_and_gc(tmp_path):
    tree = {"x": jnp.zeros((2,))}
    for s in (5, 10, 15, 20):
        CK.save(str(tmp_path), s, tree)
    assert CK.latest_step(str(tmp_path)) == 20
    CK.gc_old(str(tmp_path), keep=2)
    assert CK.latest_step(str(tmp_path)) == 20
    assert CK.restore(str(tmp_path), 20, tree) is not None
    with pytest.raises(Exception):
        CK.restore(str(tmp_path), 5, tree)      # collected


def test_ckpt_incomplete_write_ignored(tmp_path):
    """A checkpoint without its commit marker must be invisible (atomic
    commit protocol)."""
    tree = {"x": jnp.ones((2,))}
    CK.save(str(tmp_path), 3, tree)
    d = os.path.join(str(tmp_path), "step_00000008")
    os.makedirs(d)                               # torn write: dir, no marker
    with open(os.path.join(d, "data.npz"), "wb") as f:
        f.write(b"garbage")
    assert CK.latest_step(str(tmp_path)) == 3


def test_async_checkpointer(tmp_path):
    ck = CK.AsyncCheckpointer(str(tmp_path), keep=2)
    tree = {"x": jnp.full((4,), 3.0)}
    for s in (1, 2, 3):
        ck.save_async(s, tree)
    ck.wait()
    assert CK.latest_step(str(tmp_path)) == 3


# ---------------------------------------------------------------------------
# train loop: restart bit-exactness + straggler mitigation
# ---------------------------------------------------------------------------

def _tc(tmp_path, **kw):
    from repro.optim import adamw
    base = dict(steps=8, ckpt_every=4, ckpt_dir=str(tmp_path), log_every=4,
                optim=adamw.AdamWConfig(lr=1e-3, warmup_steps=2,
                                        total_steps=8))
    base.update(kw)
    return TL.TrainConfig(**base)


def _stream(cfg):
    return dp.LMStreamSpec(global_batch=4, seq_len=16, vocab=cfg.vocab,
                           seed=0)


def test_train_loss_decreases(tiny_cfg, tiny_spec, tmp_path):
    tc = _tc(tmp_path, steps=12, ckpt_every=12, log_every=1,
             optim=__import__("repro.optim.adamw", fromlist=["x"]
                              ).AdamWConfig(lr=5e-3, warmup_steps=1,
                                            total_steps=12))
    out = TL.train(tiny_cfg, tiny_spec, tc, stream_spec=_stream(tiny_cfg))
    hist = out["history"]
    assert hist[-1]["loss"] < hist[0]["loss"]


def test_restart_resumes_bit_exact(tiny_cfg, tiny_spec, tmp_path):
    """Kill at step 5 (after the step-4 checkpoint), restart, and compare
    final params against an uninterrupted run."""
    ref_dir, f_dir = str(tmp_path / "ref"), str(tmp_path / "fail")
    ref = TL.train(tiny_cfg, tiny_spec, _tc(ref_dir),
                   stream_spec=_stream(tiny_cfg))
    out = TL.train_with_restarts(tiny_cfg, tiny_spec,
                                 _tc(f_dir, fail_at_step=5),
                                 stream_spec=_stream(tiny_cfg))
    assert out["restarts"] == 1
    for a, b in zip(jax.tree.leaves(ref["params"]),
                    jax.tree.leaves(out["params"])):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_straggler_backup_fetch(tiny_cfg, tiny_spec, tmp_path):
    """A stalling data fetch times out and the backup batch is used; the
    run completes."""
    tc = _tc(tmp_path, steps=3, ckpt_every=3, fetch_timeout_s=0.05,
             straggler=dp.StragglerModel(stall_prob=1.0, stall_s=0.5,
                                         seed=1))
    out = TL.train(tiny_cfg, tiny_spec, tc, stream_spec=_stream(tiny_cfg))
    assert out["step"] == 3


# ---------------------------------------------------------------------------
# two-stage server
# ---------------------------------------------------------------------------

def test_server_matches_one_shot(tiny_cfg, tiny_params):
    """Server results == serve_batch merged logits for every sample id."""
    spec = ee.EarlyExitSpec(exit_layer=2, c_thr=0.6)
    B, S, N = 4, 8, 16
    toks = np.asarray(jax.random.randint(jax.random.PRNGKey(5), (N, S), 0,
                                         tiny_cfg.vocab))
    server = SL.build(tiny_params, tiny_cfg, spec,
                      SL.ServeConfig(capacity=4, c_thr=spec.c_thr),
                      mode="prefill", scheduler=None)
    results = SL.serve_dataset(server, toks, batch=B)
    assert set(results) == set(range(N))
    assert server.stats.n_samples == N
    assert server.stats.n_exited + server.stats.n_stage2 == N

    one = ee.serve_batch(tiny_params, tiny_cfg, spec, jnp.asarray(toks),
                         capacity=N)
    merged = np.asarray(one["logits"])
    for sid in range(N):
        np.testing.assert_allclose(results[sid], merged[sid], rtol=2e-4,
                                   atol=2e-4)


def test_server_realized_q(tiny_cfg, tiny_params):
    spec = ee.EarlyExitSpec(exit_layer=2, c_thr=0.0)   # everything exits
    server = SL.build(tiny_params, tiny_cfg, spec,
                      SL.ServeConfig(capacity=2, c_thr=0.0),
                      mode="prefill", scheduler=None)
    toks = np.zeros((8, 8), np.int32)
    res = SL.serve_dataset(server, toks, batch=4)
    assert server.stats.realized_q == 0.0
    assert len(res) == 8
