"""Two-stage Early-Exit serving runtime (the paper's Fig. 3 pipeline),
device-resident, for both prefill and autoregressive decode.

Stage 1 (full batch) -> Exit Decision -> Conditional Buffer (compaction into
fixed-capacity hard-sample buckets) -> Stage 2 (buckets only) -> Exit Merge
by Sample ID. Between the stages sits a bounded hard-sample queue — the
conditional buffer's occupancy is the paper's Fig. 7 deadlock/sizing story
and yields the Fig. 4 q-vs-p robustness behaviour:

  q < p : stage 2 under-fed, bucket bubbles, stage 1 limits throughput;
  q > p : queue grows; when full, stage 1 stalls (backpressure) and
          throughput degrades by ~p/q — exactly the shaded band.

**Device residency.** ATHEENA's throughput comes from keeping the exit
machinery on-chip: the FPGA conditional buffer never round-trips a feature
map through host memory. The servers here mirror that:

  * the exit decision + compaction run as ONE jitted step per stage-1 batch
    through the kernel dispatch layer (``kernels.dispatch``): the fused
    ``exit_decision_op`` streams the (B, V) logits from HBM once — no
    materialized softmax — and ``gather_compact_op`` emits the hard-sample
    slab without leaving the device;
  * hard samples carry over between stage-1 batches in a preallocated
    **device-side ring buffer** over an arbitrary **pytree payload**: every
    leaf is a ``(size, *row)`` slab sharing one set of int32 head/count
    cursors and one Sample-ID lane, updated in place by jitted
    ``ring_enqueue`` / ``ring_drain`` steps with ``donate_argnums`` so no
    copy of the queue ever exists. Prefill rings carry the bare hidden slab;
    decode rings carry ``{hidden row, stage-2 block-table row, step}``. The
    pre-device-resident implementation (kept below as ``HostLoopServer``,
    the benchmark baseline) instead synced each hidden row to host, held it
    in a Python ``deque`` and re-stacked it per bucket;
  * drains are asynchronous: stage 2 is dispatched on a bucket and only the
    (ids, logits) futures are retained; nothing calls
    ``block_until_ready``/``np.asarray`` until ``flush()``, so results leave
    the device in one per-bucket transfer and stage 2 overlaps with
    subsequent stage-1 batches. The single host sync per batch is the scalar
    ``n_hard`` needed for backpressure control flow.

**Decode serving.** Autoregressive decode makes the exit decision *per
token* and is served by the slot-based ``ContinuousScheduler``
(``runtime/scheduler.py``): every pool tick runs ``ee.stage1_decode`` on
the slot batch, and only the hard tokens' hidden rows — with their stage-2
block-table rows — travel through the ring into bucketed
``ee.stage2_decode`` dispatches over the paged stage-2 KV pool. This
module builds the jitted stage callables it runs (``decode_stage_fns``)
and keeps ``HostLoopDecoder``, the per-token host loop over the dense
stage-2 cache, as the reference its token streams are checked against. A
token that exits early skips stage 2 entirely, so its stage-2 cache keeps
zeros at that position — the *exit-gap* semantics the scheduler and the
host loop share (cf. the cache-handling challenges in Laskaridis et al.
2021).

**Ring sizing / deadlock avoidance (paper Fig. 7).** The ring holds
``queue_depth * capacity`` samples. A stage-1 batch whose hard count exceeds
the free space enqueues in chunks, stalling stage 1 between chunks while
*full* buckets drain — partial (flush-padded) buckets waste stage-2 capacity
and are used only when no full bucket exists. Any batch size is therefore
correct even against a tiny ring (no deadlock, no drop); an undersized ring
just stalls stage 1 harder — the paper's Fig. 7 minimum-depth sizing is a
throughput constraint, surfaced by ``ServeStats.n_stalls``, not a
correctness one.

**Stage disaggregation.** Every server runs over a ``StagePlacement``
(runtime/stage_executor.py): stage 1 + the exit-decision kernels on one
``StageExecutor``, the pytree ring + stage 2 on the other. With submeshes
carved from a ``StageMeshPlan`` (chips apportioned to each stage in
proportion to p — the paper's §IV spatial resource split), params are
resident per stage (``ee.split_params``) and the hard-sample slab / bucket
results hop between submeshes as ``jax.device_put`` transfers. The default
placement is degenerate (no mesh, placement = identity), so single-device
serving is the same hot loop, bit for bit — parity the disaggregation tests
enforce under ``--xla_force_host_platform_device_count``.

The runtime tracks realized q *per decision* (= per sample for prefill, per
token for decode) and reports per-stage occupancy/stall statistics plus
per-request latency so a deployment can re-plan (``core.stage_mesh``) when
drift is persistent.

The ring primitives, ``RingQueue`` backpressure plumbing, ``ServeConfig``
and ``ServeStats`` live in ``runtime/scheduler.py`` and are re-exported
here, so the prefill server and the decode scheduler share one
implementation.
"""
from __future__ import annotations

import functools
from collections import deque
from typing import Callable, List, NamedTuple, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import early_exit as ee
from repro.core import exit_decision as ed
from repro.kernels import dispatch
from repro.models import transformer as T
from repro.models.config import ArchConfig
from repro.runtime.stage_executor import StagePlacement
from repro.runtime import faults
# the scheduler module owns the shared serving substrate; re-exported names
# keep this module the one import site for serving callers and tests
from repro.runtime.scheduler import (  # noqa: F401  (re-exports)
    ContinuousScheduler, HarvestTimeout, Request, RingQueue, ServeConfig,
    ServeStats, _gather_rows, bounded_wait, ring_drain, ring_enqueue,
    ring_init)
from repro.runtime.serve_api import (  # noqa: F401  (re-exports)
    ReplicaHandle, RequestQueue, build, validate_request)


@functools.partial(jax.jit, static_argnames=("backend", "mesh"))
def _decide_compact(hidden, exit_logits, sample_ids, c_thr, *, backend,
                    mesh=None):
    """Fused exit decision + conditional-buffer compaction, one device
    program shared by every server instance (c_thr is traced, so a new
    threshold never recompiles; the resolved kernel backend is a static
    arg, so a dispatch override is honored rather than baked in at first
    trace). Compaction capacity = the stage-1 batch, so no hard sample is
    ever dropped here; the ring applies backpressure."""
    exit_mask, _, _ = dispatch.exit_decision_op(
        exit_logits, c_thr, backend=backend, mesh=mesh)
    b = hidden.shape[0]
    slab, pos, n_hard = dispatch.gather_compact_op(
        hidden, ~exit_mask, b, backend=backend, mesh=mesh)
    slab_ids = jnp.where(pos >= 0,
                         jnp.take(sample_ids, jnp.maximum(pos, 0)), -1)
    return slab, slab_ids, n_hard, exit_mask


# ---------------------------------------------------------------------------
# device-resident two-stage prefill server
# ---------------------------------------------------------------------------

class TwoStageServer:
    """Batch-level EE server over jitted stage callables, device-resident.

    stage1_fn: tokens (B, S) -> (hidden, exit_logits)
    stage2_fn: hidden slab (C, S, d) -> final logits (C, V)

    ``placement`` decides WHERE: stage 1 (and the exit-decision kernels) on
    ``placement.ex1``, the ring and stage 2 on ``placement.ex2``. With a
    disaggregated placement (StagePlacement.from_plan over disjoint
    submeshes) the callables must close over params placed on their own
    executor (``_stage_fns`` does this), and the hard-slab enqueue becomes
    a device-to-device transfer across the submesh boundary. The default
    placement is the degenerate single-device one — the hot path is then
    identical to a placement-unaware server, bit for bit.

    ``submit`` keeps everything on device: one jitted step runs stage 1 +
    fused exit decision + compaction, the hard slab is enqueued into the
    device ring, and full buckets are dispatched to stage 2 asynchronously.
    Results (easy exit logits, per-bucket stage-2 logits) stay device-side
    as futures until ``flush`` collects them — one transfer per batch /
    bucket, ``block_until_ready`` only at flush.
    """

    def __init__(self, stage1_fn: Callable, stage2_fn: Callable,
                 sc: ServeConfig,
                 placement: Optional[StagePlacement] = None):
        self.sc = sc
        self.placement = placement or StagePlacement.single_device()
        self.ex1 = self.placement.ex1
        self.ex2 = self.placement.ex2    # the ring + stage 2 live here
        self.stats = ServeStats()
        self.stats.record_placement(self.placement)
        self.ring = RingQueue(sc, self.ex2, self.stats)
        self.c_thr = float(sc.c_thr)     # traced: a new value never recompiles
        self.stage1 = stage1_fn
        self.stage2 = stage2_fn
        # pending device futures, collected at flush()
        self._easy: List[Tuple[np.ndarray, jnp.ndarray, jnp.ndarray]] = []
        self._buckets: List[Tuple[jnp.ndarray, jnp.ndarray]] = []

    # -- internal ------------------------------------------------------------

    def _use_fused(self) -> bool:
        """The fused dispatch op (decision + compaction + in-ring enqueue,
        one program) applies when stage 1 and the ring share a submesh; a
        disaggregated placement keeps the composed chain, whose enqueue IS
        the cross-submesh hop."""
        return not self.placement.disaggregated

    def _fused_dispatch_enqueue(self, exit_logits, sample_ids, payload,
                                row_spec):
        """One fused op replaces exit_decision -> gather_compact -> per-leaf
        ring scatter: compacted hard rows land directly in the ring slabs
        at (head+count) offsets, with the ring buffer donated through the
        op. Syncs the scalar n_hard (the same single host sync as the
        composed path), advances the ring's host count mirror, and pushes
        any overflow past the ring's free space through the composed
        backpressure chain (identical stall/drain ordering). Returns
        (exit_mask, n_hard)."""
        ring_buf = self.ring.ensure(row_spec)
        (ring_buf, exit_mask, _, _, src,
         n_hard_dev) = dispatch.fused_dispatch_op(
            exit_logits, None, sample_ids, payload, ring_buf, self.c_thr)
        self.ring.put_buf(ring_buf)
        n_hard = int(n_hard_dev)              # the one host sync
        if n_hard > 0:
            # the enqueue already happened in-op; its fault boundary keeps
            # the composed visit cadence (once per hard batch)
            faults.fault_point("enqueue")
            n_enq = min(n_hard, self.ring.size - self.ring.count)
            self.ring.note_enqueued(n_enq)
            if n_enq < n_hard:                # ring filled mid-batch: spill
                slab = _gather_rows(payload, src)
                ids = jnp.where(src >= 0,
                                jnp.take(sample_ids, jnp.maximum(src, 0)),
                                -1)
                self.ring.enqueue(slab, ids, n_hard, self._drain,
                                  off=n_enq, fire_fault=False)
        return exit_mask, n_hard

    @staticmethod
    def _collect_easy(entry, results: dict) -> None:
        sids, exit_mask, exit_logits = entry
        mask = np.asarray(exit_mask)
        logits = np.asarray(exit_logits)
        for i in np.nonzero(mask)[0]:
            results[int(sids[i])] = logits[i]

    @staticmethod
    def _collect_bucket(entry, results: dict) -> None:
        bucket_ids, logits = entry
        ids = np.asarray(bucket_ids)
        logits = np.asarray(logits)
        for i in np.nonzero(ids >= 0)[0]:
            results[int(ids[i])] = logits[i]

    def _harvest_oldest(self, results: dict) -> None:
        """Collect the oldest pending result groups until the backlog fits
        ``max_pending``. The oldest futures were dispatched many batches
        ago, so this rarely blocks — it just keeps device-side result
        memory O(max_pending * B * V) instead of O(total requests)."""
        while len(self._easy) + len(self._buckets) > self.sc.max_pending:
            if self._easy:
                self._collect_easy(self._easy.pop(0), results)
            else:
                self._collect_bucket(self._buckets.pop(0), results)

    def _drain(self) -> None:
        """Pop one bucket from the ring and dispatch stage 2 (async)."""
        popped = self.ring.pop()
        if popped is None:
            return
        bucket, bucket_ids, _ = popped
        logits = self.stage2(bucket)
        self._buckets.append((bucket_ids, logits))

    # -- public --------------------------------------------------------------

    def submit(self, tokens: np.ndarray, sample_ids: np.ndarray,
               results: dict):
        """Serve one stage-1 batch. Easy samples' exit logits and hard
        samples' hidden rows never leave the device; full buckets drain
        asynchronously whenever available. If the ring cannot absorb the
        batch's hard samples, stage 1 stalls (backpressure) and full buckets
        drain first — partial buckets only when no full one exists.

        ``results`` is filled lazily: entries appear when pending futures
        are harvested (backlog > ``max_pending``) and at ``flush()`` —
        unlike HostLoopServer, a sample's logits are NOT guaranteed to be
        present right after the submit that resolved it."""
        tokens = self.ex1.place_io(jnp.asarray(tokens))
        ids_dev = self.ex1.place_io(jnp.asarray(np.asarray(sample_ids,
                                                           np.int32)))
        hidden, exit_logits = self.stage1(tokens)
        if self._use_fused():
            exit_mask, n_hard = self._fused_dispatch_enqueue(
                exit_logits, ids_dev, hidden,
                jax.ShapeDtypeStruct(hidden.shape[1:], hidden.dtype))
        else:
            slab, slab_ids, n_hard_dev, exit_mask = _decide_compact(
                hidden, exit_logits, ids_dev, self.c_thr,
                backend=dispatch.kernel_backend(), mesh=self.ex1.mesh)
            n_hard = int(n_hard_dev)          # the one host sync per batch
            if n_hard > 0:
                # chunked, stalling (draining) whenever the ring is out of
                # space — the Fig. 7 backpressure story
                self.ring.enqueue(slab, slab_ids, n_hard, self._drain)
        b = int(tokens.shape[0])
        self.stats.n_samples += b
        self.stats.record_decisions(b, n_hard)
        self._easy.append((np.asarray(sample_ids), exit_mask, exit_logits))
        while self.ring.count >= self.sc.capacity:
            self._drain()
        self._harvest_oldest(results)

    def flush(self, results: dict):
        """Drain the ring (partial final bucket included) and collect every
        pending device future into ``results`` — the only point that
        deliberately blocks on the device."""
        while self.ring.count > 0:
            self._drain()
        pending = ([x for t in self._easy for x in t[1:]]
                   + [x for t in self._buckets for x in t])
        if pending:
            jax.block_until_ready(pending)
        for entry in self._easy:
            self._collect_easy(entry, results)
        for entry in self._buckets:
            self._collect_bucket(entry, results)
        self._easy.clear()
        self._buckets.clear()


# ---------------------------------------------------------------------------
# the seed's host-loop server — kept verbatim as the benchmark baseline
# (benchmarks/serve_pipeline.py measures the device-resident speedup
# against it) and as the e2e parity oracle in tests
# ---------------------------------------------------------------------------

class HostLoopServer:
    """Per-sample host-loop EE server (pre-device-resident implementation):
    syncs each hard hidden row to host, queues it in a Python deque and
    re-stacks it per bucket. Same interface as TwoStageServer."""

    def __init__(self, stage1_fn: Callable, stage2_fn: Callable,
                 sc: ServeConfig):
        self.stage1 = stage1_fn
        self.stage2 = stage2_fn
        self.sc = sc
        self.queue: deque = deque()          # (hidden_row, sample_id) pairs
        self.stats = ServeStats()

    def _drain_bucket(self, results: dict):
        """Pop up to ``capacity`` queued hard samples, run stage 2, merge."""
        take = min(len(self.queue), self.sc.capacity)
        if take == 0:
            return
        rows, ids = zip(*[self.queue.popleft() for _ in range(take)])
        slab = jnp.stack(list(rows))
        if take < self.sc.capacity:          # flush slots (paper §III-C.2)
            pad = jnp.broadcast_to(slab[:1],
                                   (self.sc.capacity - take,) + slab.shape[1:])
            slab = jnp.concatenate([slab, pad])
        logits = self.stage2(slab)
        for i, sid in enumerate(ids):
            results[sid] = np.asarray(logits[i])
        self.stats.n_stage2 += take
        self.stats.record_bucket(take / self.sc.capacity)

    def submit(self, tokens: np.ndarray, sample_ids: np.ndarray,
               results: dict):
        """Serve one stage-1 batch; easy samples resolve immediately, hard
        ones enqueue. Buckets drain whenever a full bucket is available; if
        the queue would overflow, drain first (stage-1 backpressure stall)."""
        hidden, exit_logits = self.stage1(jnp.asarray(tokens))
        exit_mask, pred, conf = ed.decision_and_argmax(
            exit_logits, self.sc.c_thr)
        exit_mask = np.asarray(exit_mask)
        self.stats.n_samples += len(sample_ids)
        self.stats.record_decisions(len(sample_ids),
                                    int((~exit_mask).sum()))
        for i, sid in enumerate(sample_ids):
            if exit_mask[i]:
                results[sid] = np.asarray(exit_logits[i])
            else:
                if len(self.queue) >= self.sc.queue_depth * self.sc.capacity:
                    self.stats.n_stalls += 1
                    self._drain_bucket(results)
                self.queue.append((jnp.asarray(hidden[i]), int(sid)))
        while len(self.queue) >= self.sc.capacity:
            self._drain_bucket(results)

    def flush(self, results: dict):
        while self.queue:
            self._drain_bucket(results)


# ---------------------------------------------------------------------------
# decode-time serving: per-token exit decisions with stage-2 KV-cache
# segments carried through the pytree ring
# ---------------------------------------------------------------------------

def cache_rows_of(seg: dict) -> dict:
    """Re-layout a segment cache pytree (run_layers layout) so every leaf is
    sample-major (batch axis 0): 'blocks' leaves carry a leading superblock
    axis (n_sb, B, ...) -> (B, n_sb, ...); 'first'/'rem' leaves are already
    batch-leading. The result is a valid pytree-ring payload (rows = axis
    0 of every leaf)."""
    return {"first": seg["first"],
            "blocks": jax.tree.map(lambda x: jnp.moveaxis(x, 1, 0),
                                   seg["blocks"]),
            "rem": seg["rem"]}


def cache_of_rows(rows: dict) -> dict:
    """Inverse of ``cache_rows_of``: back to the run_layers layout."""
    return {"first": rows["first"],
            "blocks": jax.tree.map(lambda x: jnp.moveaxis(x, 0, 1),
                                   rows["blocks"]),
            "rem": rows["rem"]}


# ---------------------------------------------------------------------------
# paged stage-2 cache: page pools + block tables instead of dense rows
# ---------------------------------------------------------------------------

def _is_layer_cache(node) -> bool:
    """A per-layer decode cache dict: attention {k, v} or MLA
    {latent, k_rope}. The only cache shapes the paged store accepts."""
    return isinstance(node, dict) and (
        ("k" in node and "v" in node) or "latent" in node)


def _map_layer_caches(node, fn):
    """Apply ``fn`` to every per-layer cache dict in a segment tree,
    preserving the surrounding structure."""
    if _is_layer_cache(node):
        return fn(node)
    if isinstance(node, dict):
        return {k: _map_layer_caches(v, fn) for k, v in node.items()}
    if isinstance(node, (list, tuple)):
        return type(node)(_map_layer_caches(v, fn) for v in node)
    return node


def paged_seg_pool(rows: dict, page_size: int, n_pages: int) -> dict:
    """Zero page-pool tree (run_layers layout) templated on a sample-major
    stage-2 rows tree. 'blocks' leaves (B, n_sb, max_len, *F) become
    (n_sb, n_pages, page, *F) pools; 'rem' layer leaves (B, max_len, *F)
    become (n_pages, page, *F). Every leaf's position axis must be the SAME
    max_len, a multiple of ``page_size`` — windowed ring caches, recurrent
    state and cross-attention memory are not pageable and raise."""
    if rows["first"]:
        raise ValueError("stage-2 rows carry no 'first' caches; got a "
                         "non-empty first segment — not pageable")
    lens = set()

    def _pool_leaf(x, lead):
        L = x.shape[1 + lead]
        if L % page_size != 0:
            raise ValueError(f"cache position axis {L} is not a multiple of "
                             f"page_size={page_size} — not pageable")
        lens.add(L)
        head = (x.shape[1],) if lead else ()
        return jnp.zeros(head + (n_pages, page_size) + x.shape[2 + lead:],
                         x.dtype)

    def _check(node, lead):
        if not _is_layer_cache(node):
            raise ValueError(f"non-attention cache {jax.tree.structure(node)}"
                             " — not pageable (windowed/recurrent/cross "
                             "layers have no paged store yet)")
        if "bt" in node:
            raise ValueError("rows template is already paged")
        return {k: _pool_leaf(v, lead) for k, v in node.items()}

    pool = {"first": [],
            "blocks": _map_layer_caches(rows["blocks"],
                                        lambda d: _check(d, 1)),
            "rem": _map_layer_caches(rows["rem"], lambda d: _check(d, 0))}
    if len(lens) > 1:
        raise ValueError(f"inconsistent cache position axes {sorted(lens)} "
                         "— not pageable")
    return pool


def _inject_bt(pool: dict, bt: jnp.ndarray) -> dict:
    """Add the block table to every layer-cache dict of a pool tree:
    'rem' layers get ``bt`` (B, M) directly; 'blocks' layers get it
    broadcast over their leading superblock axis (scanned per layer)."""
    def blocks_fn(d):
        n_sb = next(iter(d.values())).shape[0]
        return dict(d, bt=jnp.broadcast_to(bt[None], (n_sb,) + bt.shape))

    return {"first": pool["first"],
            "blocks": _map_layer_caches(pool["blocks"], blocks_fn),
            "rem": _map_layer_caches(pool["rem"],
                                     lambda d: dict(d, bt=bt))}


def _strip_bt(seg: dict) -> dict:
    """Inverse of ``_inject_bt``: drop the block-table leaves so the pool
    tree keeps one structure (the table lane is scheduler state)."""
    return _map_layer_caches(
        seg, lambda d: {k: v for k, v in d.items() if k != "bt"})


@functools.partial(jax.jit, static_argnames=("sentinel",))
def _sanitize_paged_bucket(bt_rows, ids, step, sentinel: int):
    """Flush / stale ring rows (ids < 0) must not touch the shared pool:
    their block tables collapse to the null page and their write position
    to the out-of-range sentinel, so the paged append drops and the gather
    reads zeros. Live rows pass through untouched."""
    bad = ids < 0
    step = jnp.broadcast_to(jnp.asarray(step, jnp.int32), ids.shape)
    return (jnp.where(bad[:, None], 0, bt_rows),
            jnp.where(bad, sentinel, step))


class DecodeFns(NamedTuple):
    """Jitted decode-stage callables shared by the continuous scheduler AND
    the host-loop reference, so the scheduler's per-sample token streams
    can equal the reference's. ``step`` may be the scalar batch position
    (host loop) or a per-row (B,) vector (the scheduler's slot pool).
    ``s1_raw`` is the un-jitted stage-1 body: the pool tick inlines it
    inside its own jitted step (masked cache select around it), which a
    donating jit wrapper would get in the way of. A
    ``jax.tree_util.Partial`` binding the stage-1 params keeps them tick
    ARGUMENTS rather than constants baked into the tick program."""
    prefill: Callable   # (tokens (B,S), max_len static) -> (logits, caches)
    split: Callable     # caches -> (stage1_caches, stage2_cache_rows)
    s1: Callable        # (tok (B,1), c1, step) -> (h (B,d), c1', exit_logits)
    s2: Callable        # (h (C,d), cache_rows, step) -> (logits, new_rows):
                        # the dense stage 2 the host-loop reference runs
    s1_raw: Callable    # s1's body, un-jitted (continuous pool tick)
    # For a model with MoE layers, prefill, s1/s1_raw, s2 and s2_paged
    # return one more value last: the MoE layers' summed load counters
    # (moe.LOAD_FIELDS), fetched with what the host already syncs on.
    # The paged stage-2 surface the scheduler serves with (None when built
    # without a page_size, which only the host-loop reference accepts): the
    # stage-2 cache lives in page pools + per-slot block tables, and the
    # ring's cache payload is the (max_pages,) i32 table row.
    page_size: Optional[int] = None
    s2_paged: Optional[Callable] = None   # (h, bt, step, pool) -> (logits, pool')
    pool_init: Optional[Callable] = None  # (rows template, n_pages) -> pool
    admit_pages: Optional[Callable] = None  # (pool, rows, bt_rows) -> pool'


def decode_stage_fns(params, cfg: ArchConfig, spec: ee.EarlyExitSpec,
                     placement: Optional[StagePlacement] = None,
                     page_size: Optional[int] = None) -> DecodeFns:
    """Jitted decode callables with per-stage residency: the one-shot
    full-depth prefill (and its cache split) runs on ex1 with the full
    param tree, per-step stage 1 takes the same tree on ex1, and the
    bucketed stage-2 decode takes the stage-2 slice on ex2. Degenerate
    placement = everything on the default device, the same programs as
    before.

    Params enter every program as an ARGUMENT (bound with
    ``functools.partial`` / ``jax.tree_util.Partial``), never as a closure:
    a jit that closes over an array embeds it in the program as a constant,
    which at published widths is gigabytes of host memory per program."""
    pl = placement or StagePlacement.single_device()
    # ex1 holds the FULL tree (the one-shot prefill needs every layer);
    # per-step stage 1 takes the same placed tree rather than a second
    # stage-1 slice, so stage-1 params are resident once, not twice.
    # The stage-2 slice is only cut (a copy of its superblock leaves) when
    # there is a second submesh to put it on.
    presliced = pl.disaggregated
    p_full = pl.ex1.place(params)
    if presliced:
        _, p2 = ee.split_params(cfg, spec, params)
        p2 = pl.ex2.place(p2)
    else:
        p2 = params

    # MoE load counters ride along; a dense model's stage calls stay as
    # they were
    load = {"with_load": True} if cfg.moe is not None else {}

    @functools.partial(jax.jit, static_argnames=("max_len",))
    def pf(p, tokens, max_len: int):
        logits, caches, _, *lc = T.prefill(p["backbone"], cfg, tokens,
                                           max_len=max_len, **load)
        return (logits, caches, *lc)

    @jax.jit
    def split(caches):
        c1, c2 = ee.split_caches(cfg, spec, caches)
        return c1, cache_rows_of(c2)

    def s1_body(p, tok, c1, step):
        h, nc1, exit_logits, *lc = ee.stage1_decode(p, cfg, spec, tok, c1,
                                                    step, **load)
        return (h[:, 0], nc1, exit_logits, *lc)

    s1 = jax.jit(s1_body, donate_argnums=(2,))

    @functools.partial(jax.jit, donate_argnums=(2,))
    def s2(p, h_rows, cache_rows, step):
        logits, nc, *lc = ee.stage2_decode(p, cfg, spec, h_rows[:, None],
                                           cache_of_rows(cache_rows), step,
                                           presliced_params=presliced,
                                           **load)
        return (logits, cache_rows_of(nc), *lc)

    pf = functools.partial(pf, p_full)
    s1 = functools.partial(s1, p_full)
    s2 = functools.partial(s2, p2)
    s1_raw = jax.tree_util.Partial(s1_body, p_full)
    if page_size is None:
        return DecodeFns(pf, split, s1, s2, s1_raw)
    if page_size < 1:
        raise ValueError(f"page_size must be >= 1, got {page_size}")

    @functools.partial(jax.jit, donate_argnums=(4,))
    def s2_paged(p, h_rows, bt, step, pool):
        logits, nc, *lc = ee.stage2_decode(p, cfg, spec, h_rows[:, None],
                                           _inject_bt(pool, bt), step,
                                           presliced_params=presliced,
                                           **load)
        return (logits, _strip_bt(nc), *lc)

    s2_paged = functools.partial(s2_paged, p2)

    def pool_init(rows, n_pages: int):
        return paged_seg_pool(rows, page_size, n_pages)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def admit_pages(pool, rows, bt_rows):
        """Scatter k admitted rows' DENSE stage-2 caches into their pages.
        rows: sample-major tree, leaves (k, [n_sb,] L, *F); bt_rows:
        (k, M) i32, null (0) tail entries land in the null page — every
        such write carries the dense tail's zeros, so page 0 stays zero."""
        k, M = bt_rows.shape

        def rem_fn(d, r):
            # pool (P, page, *F) <- rows (k, L, *F) paginated to (k*M, ...)
            return jax.tree.map(
                lambda p, x: p.at[bt_rows.reshape(-1)].set(
                    x.reshape((k * M, page_size) + x.shape[2:]),
                    mode="drop"),
                d, r)

        def blocks_fn(d, r):
            # pool (n_sb, P, page, *F) <- rows (k, n_sb, L, *F)
            def leaf(p, x):
                n_sb = x.shape[1]
                x = jnp.moveaxis(x, 0, 1).reshape(
                    (n_sb, k * M, page_size) + x.shape[3:])
                return p.at[:, bt_rows.reshape(-1)].set(x, mode="drop")
            return jax.tree.map(leaf, d, r)

        return {"first": [],
                "blocks": jax.tree.map(blocks_fn, pool["blocks"],
                                       rows["blocks"],
                                       is_leaf=_is_layer_cache),
                "rem": jax.tree.map(rem_fn, pool["rem"], rows["rem"],
                                    is_leaf=_is_layer_cache)}

    return DecodeFns(pf, split, s1, s2, s1_raw, page_size=page_size,
                     s2_paged=s2_paged, pool_init=pool_init,
                     admit_pages=admit_pages)


def decode_step0_confidences(params, cfg: ArchConfig, spec: ee.EarlyExitSpec,
                             prompt, max_len: int) -> jnp.ndarray:
    """Exit-head max-softmax confidences of the FIRST decode step (greedy
    token from the prefill logits): the calibration set for per-token
    thresholds, whose statistics drift from prefill's per-sample
    confidences. prompt: (B, S) int32; max_len sizes the cache pads."""
    prompt = jnp.asarray(prompt)
    S = prompt.shape[1]
    logits, caches, _ = T.prefill(params["backbone"], cfg, prompt,
                                  max_len=max_len)
    c1, _ = ee.split_caches(cfg, spec, caches)
    tok = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
    _, _, exit_logits = ee.stage1_decode(params, cfg, spec, tok, c1,
                                         jnp.int32(S))
    return ed.softmax_confidence(exit_logits)


class HostLoopDecoder:
    """Per-token host-loop decode over the dense stage-2 cache — the
    reference the continuous scheduler's token streams are checked against
    (HostLoopServer-style): syncs the exit mask each step, walks the hard
    tokens in Python, re-stacks each bucket's hidden rows AND cache rows
    sample by sample, and scatters updated cache rows back one sample at a
    time. Shares the jitted stage callables with the scheduler, so the two
    differ only in the exit/scheduling machinery and the cache layout."""

    def __init__(self, fns: DecodeFns, sc: ServeConfig):
        self.fns = fns
        self.sc = sc
        self.stats = ServeStats()

    def generate(self, prompt: np.ndarray, n_tokens: int) -> dict:
        if n_tokens < 1:
            raise ValueError(f"n_tokens must be >= 1, got {n_tokens}")
        prompt = jnp.asarray(np.asarray(prompt, np.int32))
        B, S = prompt.shape
        self.stats.n_samples += B
        logits0, caches, *_ = self.fns.prefill(prompt, S + n_tokens)
        c1, rows = self.fns.split(caches)
        merged = np.asarray(logits0)
        logits_out, toks_out = [], []
        C = self.sc.capacity
        for t in range(n_tokens):
            tok = np.argmax(merged, axis=-1).astype(np.int32)[:, None]
            toks_out.append(tok)
            logits_out.append(merged)
            if t == n_tokens - 1:
                break
            pos = S + t
            h_rows, c1, exit_logits, *_ = self.fns.s1(jnp.asarray(tok), c1,
                                                      pos)
            exit_mask, _, _ = ed.decision_and_argmax(exit_logits,
                                                     self.sc.c_thr)
            exit_mask = np.asarray(exit_mask)        # per-step host sync
            merged = np.array(np.asarray(exit_logits))
            hard = [i for i in range(B) if not exit_mask[i]]
            self.stats.record_decisions(B, len(hard))
            for lo in range(0, len(hard), C):
                chunk = hard[lo:lo + C]
                pad = C - len(chunk)
                take = chunk + [chunk[0]] * pad      # flush-padded bucket
                bucket_h = jnp.stack([h_rows[i] for i in take])
                bucket_cache = jax.tree.map(
                    lambda m: jnp.stack([m[i] for i in take]), rows)
                logits, new_rows, *_ = self.fns.s2(bucket_h, bucket_cache,
                                                   pos)
                lnp = np.asarray(logits)
                for j, sid in enumerate(chunk):
                    merged[sid] = lnp[j]
                    rows = jax.tree.map(
                        lambda m, r, j=j, sid=sid: m.at[sid].set(r[j]),
                        rows, new_rows)
                self.stats.n_stage2 += len(chunk)
                self.stats.record_bucket(len(chunk) / C)
        tokens = np.concatenate(toks_out, axis=1)
        return {"tokens": tokens, "logits": np.stack(logits_out, axis=1)}


# ---------------------------------------------------------------------------
# builders
# ---------------------------------------------------------------------------

def _stage_fns(params, cfg: ArchConfig, spec: ee.EarlyExitSpec,
               placement: Optional[StagePlacement] = None):
    """Per-stage jitted prefill callables. Disaggregated: each is bound to
    ITS stage's param slice placed on its executor (ee.split_params) —
    stage-1 layers + exit head resident on ex1, stage-2 layers + final
    head on ex2. Degenerate: both are bound to the caller's full tree
    (slicing would COPY the superblock leaves for no placement benefit);
    the sliced and full-tree programs are bitwise-identical, which the
    disaggregation tests enforce."""
    pl = placement or StagePlacement.single_device()
    presliced = pl.disaggregated
    if presliced:
        p1, p2 = ee.split_params(cfg, spec, params)
        p1 = pl.ex1.place(p1)
        p2 = pl.ex2.place(p2)
    else:
        p1 = p2 = params

    @jax.jit
    def s1(p, tokens):
        h, _, logits, _ = ee.stage1_prefill(p, cfg, spec, tokens)
        return h, logits

    @jax.jit
    def s2(p, slab):
        logits, _ = ee.stage2_prefill(p, cfg, spec, slab,
                                      presliced_params=presliced)
        return logits

    # params are program arguments, not closure constants (see
    # decode_stage_fns)
    return functools.partial(s1, p1), functools.partial(s2, p2)


def serve_dataset(server, tokens: np.ndarray, batch: int) -> dict:
    """Run a whole token set through the server in stage-1 batches.
    Returns {sample_id: logits} plus the stats object."""
    n = tokens.shape[0]
    results: dict = {}
    for lo in range(0, n, batch):
        hi = min(lo + batch, n)
        server.submit(tokens[lo:hi], np.arange(lo, hi), results)
    server.flush(results)
    return results
