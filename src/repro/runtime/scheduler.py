"""Slot-based decode scheduling: continuous batching for two-stage
early-exit serving.

ATHEENA provisions stage 2 for the *fraction* of hard samples (paper §IV),
but a step-synchronous decode server realizes that only within a step: every
easy token waits for the ring to drain before the batch may advance, so
stage 1 idles exactly when early exits should be paying off. This module
makes per-sample progression asynchronous (HAPI-style staged progressive
inference; cf. the Laskaridis et al. early-exit survey):

  * ``ContinuousScheduler`` owns a fixed pool of decode **slots**. Each slot
    holds one in-flight request with its own step counter (absolute cache
    position), so one pooled stage-1 dispatch advances samples sitting at
    *different* depths — the per-row ``step`` vector path in
    ``models.attention``/``models.mla``. Slots whose token exits early keep
    decoding through stage 1 on the next tick; slots whose token is hard are
    **parked** and their hidden row + stage-2 block-table row + position
    ride the pytree ring (payload lanes ``{"h", "cache", "step"}``) until a
    bucket fills, the bucketed stage-2 dispatch appends each row's key/value
    into its pages at the row's own cache offset, and the slots resume.
    Completed slots are immediately backfilled from an **admission queue**
    of open-loop (Poisson) arrivals.

  * The stage-2 KV cache is **paged**: one shared page pool plus a
    per-slot block table, pages allocated at admission and freed at finish
    (``PageAllocator``). This is the one serving store; the dense stage-2
    decode (``DecodeFns.s2``) survives only as the ``HostLoopDecoder``
    reference the token streams are checked against.

**Correctness contract.** Continuous mode deliberately trades batch-level
bitwise identity for utilization: merged logits are never materialized per
step across the batch, and samples interleave arbitrarily. What is preserved
— and enforced by ``tests/test_scheduler.py`` — is *per-sample token-stream
equivalence*: every sample id's greedy token stream is identical to the one
``HostLoopDecoder`` produces, in order, with no token dropped or duplicated.
Per-row computations (RMSNorm, attention over the row's own cache span,
row-wise matmuls) are batch-composition-independent, which is what makes the
streams match even though the batches they were computed in never do.

**Masked pooled stage 1.** The pool tick runs stage 1 on the full slot
batch with a per-slot ``active`` mask: free/parked rows compute garbage that
is discarded, and their caches are re-selected to the pre-tick state
(``_seg_select``) so recurrent state (mamba2/rglru) advances exactly once
per *consumed* token and attention rows re-write their slot when they
resume. This keeps every tick a fixed-shape jitted program — no recompiles
as slots churn.

The device-side pytree ring (``ring_init``/``ring_enqueue``/``ring_drain``)
and the chunked-enqueue/backpressure plumbing (``RingQueue``) live here and
are shared with the prefill server in ``runtime/serve_loop.py`` (which
re-exports them; the paper's Fig. 7 sizing/deadlock story is unchanged).
``ServeStats`` also lives here: it records per-request submit→finish
latency (``latency_p50/p90/p99``) and a per-dispatch ``realized_q`` series
— the drift signal threshold re-planning consumes.
"""
from __future__ import annotations

import functools
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Callable, Deque, Dict, List, Optional

import numpy as np

import jax
import jax.numpy as jnp

from repro.kernels import dispatch
from repro.models.moe import LOAD_FIELDS
from repro.runtime import faults, observe, telemetry
from repro.runtime.serve_api import RequestQueue
from repro.runtime.stage_executor import StagePlacement


@dataclass
class ServeConfig:
    capacity: int                   # stage-2 bucket size (ceil(p*B) rounded)
    queue_depth: int = 4            # buckets the buffer can hold
    c_thr: float = 0.9
    max_pending: int = 16           # pending device result groups (stage-1
                                    # batches + stage-2 buckets) before the
                                    # oldest are harvested to host, bounding
                                    # device memory on long-running streams
    harvest_timeout_s: Optional[float] = 60.0   # bound on any single wait
                                    # for a pending device result; a bucket
                                    # that never resolves raises
                                    # HarvestTimeout instead of wedging the
                                    # hot loop (None = wait forever)


class HarvestTimeout(TimeoutError):
    """A pending device result failed to become ready within the harvest
    timeout — surfaces a wedged transfer/dispatch as an error instead of an
    unbounded hot-loop hang."""


def bounded_wait(tree, timeout_s: Optional[float], what: str = "result"):
    """Wait for every jax.Array leaf of ``tree`` to be ready, raising
    ``HarvestTimeout`` past ``timeout_s`` (None = block natively). Polls
    ``is_ready()`` with a growing sleep so the fast path (already-ready
    results, the overwhelmingly common case) costs one no-op pass."""
    if timeout_s is None:
        return tree
    deadline = time.perf_counter() + timeout_s
    pause = 1e-4
    for leaf in jax.tree.leaves(tree):
        is_ready = getattr(leaf, "is_ready", None)
        if is_ready is None:                 # numpy/python leaf: ready
            continue
        while not is_ready():
            if time.perf_counter() >= deadline:
                raise HarvestTimeout(
                    f"{what} not ready after {timeout_s:.1f}s — a device "
                    f"dispatch or cross-stage transfer is stuck")
            time.sleep(pause)
            pause = min(pause * 2.0, 0.05)
    return tree


# bounded history so long-running streams keep O(1)-ish stats memory: the
# latency reservoir covers the percentile window, the q series the recent
# drift window (the re-planning signal cares about *persistent* drift)
_SERIES_CAP = 65536


@dataclass
class ServeStats:
    """Serving counters. ``n_samples`` counts distinct samples admitted;
    ``n_decisions`` counts exit decisions — equal for prefill (one decision
    per sample), ``n_samples * generated_tokens`` for decode. ``realized_q``
    is therefore per-decision, which is the quantity the stage-2 bucket is
    provisioned against in both regimes.

    Per-stage occupancy (the TAP apportionment feedback signal): a stage-1
    "cycle" is either a real dispatch (one batch/step/tick) or a forced-drain
    stall — a cycle spent waiting on stage 2 because the ring was full
    (every server counts ``n_stalls`` per forced drain, so one batch under
    heavy backpressure can stall several times). ``stage1_occupancy`` is
    the fraction of cycles doing stage-1 work; q > p pushes it below 1,
    the paper's Fig. 4 lower band. Stage 2's slots are its bucket lanes —
    ``stage2_occupancy`` is the fraction carrying real hard samples
    rather than flush padding (q < p pushes it below 1: bucket bubbles).
    ``stage1_chips``/``stage2_chips`` record the submesh sizes the serving
    placement apportioned (1/1 for single-device).

    Open-loop request tracking: ``record_submit``/``record_finish`` stamp
    per-request wall time; ``latency_p50/p90/p99`` summarize the (bounded)
    reservoir. ``realized_q_series`` keeps the per-dispatch hard fraction —
    the drift signal online threshold re-planning consumes (a persistent
    q > p trend means C_thr or the stage mesh needs re-planning).

    Windowed drift view: ``realized_q_ewma`` is the EWMA of the recent q
    series (``telemetry.ewma`` — the ONE definition the controller and the
    drift benchmarks share) and ``q_drift`` its excursion from the
    provisioned p (0.0 until a controller / caller sets
    ``provisioned_p``). Both ride in ``as_dict``.

    ``as_dict`` is a VERSIONED schema (``SCHEMA_VERSION``, emitted as the
    ``schema_version`` key): the dict is consumed outside this process —
    the serve CLI's JSON output, the benchmark payloads
    ``benchmarks/compare.py`` gates against ``baseline_cpu.json``, and the
    fleet ops surface (``FleetStats.as_dict`` embeds one per replica).
    Fields accreted ad hoc across PRs 2-6; from v2 on, adding/removing/
    renaming a key REQUIRES a version bump (and
    ``tests/test_serve_api.py`` freezes the key set). The schema is
    documented in README's "Serving stats schema" section.

    v3 adds the paged-KV-cache memory economics: ``cache_pages_total`` /
    ``cache_pages_in_use`` / ``cache_pages_free`` (the page allocator's
    free-list view; all 0 where nothing is paged — the prefill servers and
    the host-loop oracle), ``cache_hbm_bytes`` (bytes the page pools hold
    resident), ``page_fragmentation`` (1 − live_tokens /
    (pages_in_use × page_size): the fraction of allocated page capacity not
    holding a live token — tail-page waste), and ``ring_bytes_moved``
    (cumulative bytes enqueued onto the stage-boundary ring; the hop-size
    gauge the paged page-index payload is meant to shrink)."""
    SCHEMA_VERSION = 3
    n_samples: int = 0
    n_decisions: int = 0
    n_exited: int = 0
    n_stage2: int = 0
    n_stalls: int = 0
    n_stage1_batches: int = 0       # stage-1 dispatches (batches / ticks)
    n_buckets: int = 0              # running aggregate, O(1) memory
    provisioned_p: Optional[float] = None   # the rate the mesh was sized for
    bucket_fill_sum: float = 0.0
    stage1_chips: int = 1
    stage2_chips: int = 1
    # per-request latency + per-dispatch q (bounded deques, not lists: the
    # bucket-fill aggregate stays O(1); these keep a capped history window)
    submit_times: Dict[int, float] = field(default_factory=dict, repr=False)
    latencies: Deque[float] = field(
        default_factory=lambda: deque(maxlen=_SERIES_CAP), repr=False)
    realized_q_series: Deque[float] = field(
        default_factory=lambda: deque(maxlen=_SERIES_CAP), repr=False)
    # the drift filter's window, kept as its own bounded deque so the
    # per-tick EWMA folds O(window) recent entries instead of copying the
    # full (up to _SERIES_CAP) series on every controller visit
    _q_window: Deque[float] = field(
        default_factory=lambda: deque(maxlen=telemetry.DRIFT_WINDOW),
        repr=False)
    # live-migration accounting: completed migrations, rolled-back attempts,
    # and the measured serving pause (admission-closed to admission-reopened)
    # per completed migration — the zero-downtime budget the migration bench
    # gates on
    n_migrations: int = 0
    n_migration_rollbacks: int = 0
    migration_pauses_ms: Deque[float] = field(
        default_factory=lambda: deque(maxlen=1024), repr=False)
    # paged-cache memory economics (v3): the continuous scheduler keeps
    # these current; servers without a page pool leave them at 0
    cache_pages_total: int = 0
    cache_pages_in_use: int = 0
    cache_hbm_bytes: int = 0
    cache_page_size: int = 0        # not emitted; fragmentation denominator
    live_tokens: int = 0            # not emitted; fragmentation numerator
    ring_bytes_moved: int = 0

    def record_decisions(self, n: int, n_hard: int) -> None:
        self.n_stage1_batches += 1
        self.n_decisions += n
        self.n_exited += n - n_hard
        q = n_hard / n if n else 0.0
        self.realized_q_series.append(q)
        self._q_window.append(q)

    def record_bucket(self, fill: float) -> None:
        self.n_buckets += 1
        self.bucket_fill_sum += fill

    def record_placement(self, placement) -> None:
        self.stage1_chips = placement.ex1.n_devices
        self.stage2_chips = placement.ex2.n_devices

    def record_submit(self, sample_id: int, t: float) -> None:
        self.submit_times[sample_id] = t

    def record_migration(self, pause_ms: float) -> None:
        self.n_migrations += 1
        self.migration_pauses_ms.append(float(pause_ms))

    def record_migration_rollback(self) -> None:
        self.n_migration_rollbacks += 1

    def _pause_pct(self, pct: float) -> float:
        if not self.migration_pauses_ms:
            return 0.0
        return float(np.percentile(np.asarray(self.migration_pauses_ms), pct))

    @property
    def migration_pause_p50_ms(self) -> float:
        return self._pause_pct(50.0)

    @property
    def migration_pause_p99_ms(self) -> float:
        return self._pause_pct(99.0)

    def record_finish(self, sample_id: int, t: float) -> None:
        """Submit→finish wall latency; unmatched finishes are ignored so
        servers that never recorded submits (closed-loop tests) stay
        latency-free rather than wrong."""
        t0 = self.submit_times.pop(sample_id, None)
        if t0 is not None:
            self.latencies.append(t - t0)

    def _latency_pct(self, pct: float) -> float:
        if not self.latencies:
            return 0.0
        return float(np.percentile(np.asarray(self.latencies), pct))

    @property
    def latency_p50(self) -> float:
        return self._latency_pct(50.0)

    @property
    def latency_p90(self) -> float:
        return self._latency_pct(90.0)

    @property
    def latency_p99(self) -> float:
        return self._latency_pct(99.0)

    @property
    def n_finished(self) -> int:
        return len(self.latencies)

    @property
    def mean_bucket_fill(self) -> float:
        return self.bucket_fill_sum / self.n_buckets if self.n_buckets else 0.0

    @property
    def stage1_occupancy(self) -> float:
        total = self.n_stage1_batches + self.n_stalls
        return self.n_stage1_batches / total if total else 0.0

    @property
    def stage2_occupancy(self) -> float:
        # buckets share one capacity, so the mean fill IS the slot occupancy
        return self.mean_bucket_fill

    @property
    def realized_q(self) -> float:
        return self.n_stage2 / max(self.n_decisions, 1)

    @property
    def realized_q_ewma(self) -> float:
        """EWMA of the recent per-dispatch q (telemetry.ewma's window/alpha
        — the shared drift-filter definition; folded over the bounded
        window deque, so a hot-loop read costs O(window) not O(series))."""
        return telemetry.ewma(self._q_window)

    @property
    def q_drift(self) -> float:
        """Windowed drift of realized q from the provisioned p (0.0 when no
        p was declared — an unprovisioned server has nothing to drift
        from)."""
        if self.provisioned_p is None:
            return 0.0
        return self.realized_q_ewma - self.provisioned_p

    @property
    def decisions_per_sample(self) -> float:
        return self.n_decisions / max(self.n_samples, 1)

    @property
    def cache_pages_free(self) -> int:
        return max(self.cache_pages_total - self.cache_pages_in_use, 0)

    @property
    def page_fragmentation(self) -> float:
        """Fraction of allocated page capacity not holding a live token
        (tail-page internal fragmentation). 0.0 with no pages in use."""
        cap = self.cache_pages_in_use * self.cache_page_size
        if cap <= 0:
            return 0.0
        return float(min(max(1.0 - self.live_tokens / cap, 0.0), 1.0))

    def as_dict(self):
        return {"schema_version": self.SCHEMA_VERSION,
                "n_samples": self.n_samples, "n_decisions": self.n_decisions,
                "n_exited": self.n_exited, "n_stage2": self.n_stage2,
                "n_stalls": self.n_stalls, "realized_q": self.realized_q,
                "decisions_per_sample": self.decisions_per_sample,
                "mean_bucket_fill": self.mean_bucket_fill,
                "stage1_chips": self.stage1_chips,
                "stage2_chips": self.stage2_chips,
                "stage1_occupancy": self.stage1_occupancy,
                "stage2_occupancy": self.stage2_occupancy,
                "n_finished": self.n_finished,
                "latency_p50": self.latency_p50,
                "latency_p90": self.latency_p90,
                "latency_p99": self.latency_p99,
                "provisioned_p": self.provisioned_p,
                "realized_q_ewma": self.realized_q_ewma,
                "q_drift": self.q_drift,
                "n_migrations": self.n_migrations,
                "n_migration_rollbacks": self.n_migration_rollbacks,
                "migration_pause_p50_ms": self.migration_pause_p50_ms,
                "migration_pause_p99_ms": self.migration_pause_p99_ms,
                "cache_pages_total": self.cache_pages_total,
                "cache_pages_in_use": self.cache_pages_in_use,
                "cache_pages_free": self.cache_pages_free,
                "cache_hbm_bytes": self.cache_hbm_bytes,
                "page_fragmentation": self.page_fragmentation,
                "ring_bytes_moved": self.ring_bytes_moved,
                "realized_q_series": list(self.realized_q_series)}


# ---------------------------------------------------------------------------
# device-side ring buffer over a pytree payload: per-leaf (size, *row) slabs
# sharing one id lane + int32 cursors, updated in place (donated) by jitted
# steps. Decode payloads add per-row "step" lanes (the row's absolute cache
# position) so stage-2 results scatter back at the right offsets.
# ---------------------------------------------------------------------------

def ring_init(size: int, row, dtype=None) -> dict:
    """Allocate the ring. ``row`` is either a bare shape tuple with ``dtype``
    (single-slab convenience, payload = one array) or a pytree whose leaves
    carry ``.shape``/``.dtype`` per-row (arrays or ShapeDtypeStructs).
    Returns {'data' pytree of (size, *row_leaf), 'ids' (size,), 'head' (),
    'count' ()} — ids slots are -1 (the paper's unused Sample ID)."""
    if dtype is not None:
        row = jax.ShapeDtypeStruct(tuple(row), dtype)
    data = jax.tree.map(
        lambda r: jnp.zeros((size,) + tuple(r.shape), r.dtype), row)
    return {
        "data": data,
        "ids": jnp.full((size,), -1, jnp.int32),
        "head": jnp.zeros((), jnp.int32),
        "count": jnp.zeros((), jnp.int32),
    }


@functools.partial(jax.jit, donate_argnums=(0,))
def _ring_enqueue_range(buf: dict, slab, slab_ids, lo, hi) -> dict:
    """Append slab rows [lo, min(hi, n_valid)) at the ring's tail, where
    n_valid is the compacted slab's valid prefix (ids >= 0). ``slab`` is a
    pytree matching buf['data'] rows (every leaf (n, *row_leaf)). The donated
    buffer is updated in place; unselected rows scatter out of bounds and
    are dropped. The caller guarantees the selected range fits."""
    with jax.named_scope("ring_enqueue"):
        size = buf["ids"].shape[0]
        n = slab_ids.shape[0]
        n_valid = jnp.sum(slab_ids >= 0).astype(jnp.int32)
        upper = jnp.minimum(hi, n_valid)
        lanes = jnp.arange(n, dtype=jnp.int32)
        sel = (lanes >= lo) & (lanes < upper)
        idx = (buf["head"] + buf["count"] + lanes - lo) % size
        idx = jnp.where(sel, idx, size)              # OOB -> dropped
        return {
            "data": jax.tree.map(lambda d, s: d.at[idx].set(s, mode="drop"),
                                 buf["data"], slab),
            "ids": buf["ids"].at[idx].set(slab_ids, mode="drop"),
            "head": buf["head"],
            "count": buf["count"] + jnp.maximum(upper - lo, 0),
        }


def ring_enqueue(buf: dict, slab, slab_ids: jnp.ndarray) -> dict:
    """Append the whole valid prefix of a compacted slab pytree (ids >= 0)
    at the ring's tail; see ``_ring_enqueue_range``."""
    return _ring_enqueue_range(buf, slab, slab_ids, 0, slab_ids.shape[0])


@functools.partial(jax.jit, donate_argnums=(0,), static_argnames=("capacity",))
def ring_drain(buf: dict, capacity: int):
    """Pop up to ``capacity`` samples from the ring's head into a stage-2
    bucket. Returns (buf, bucket pytree of (capacity, *row_leaf),
    bucket_ids (capacity,)) — slots past the take carry id -1 (flush) and
    whatever stale rows the ring holds (stage 2 is row-independent, flush
    rows are discarded by the exit merge)."""
    with jax.named_scope("ring_drain"):
        size = buf["ids"].shape[0]
        take_n = jnp.minimum(buf["count"], capacity).astype(jnp.int32)
        lanes = jnp.arange(capacity, dtype=jnp.int32)
        idx = (buf["head"] + lanes) % size
        valid = lanes < take_n
        bucket = jax.tree.map(lambda d: jnp.take(d, idx, axis=0),
                              buf["data"])
        bucket_ids = jnp.where(valid, jnp.take(buf["ids"], idx), -1)
        new = {
            "data": buf["data"],
            "ids": buf["ids"].at[jnp.where(valid, idx, size)].set(
                -1, mode="drop"),
            "head": (buf["head"] + take_n) % size,
            "count": buf["count"] - take_n,
        }
        return new, bucket, bucket_ids


class RingQueue:
    """Chunked-enqueue/bucket-pop plumbing over the device ring: the one
    hard-token queue implementation the prefill server
    (``runtime/serve_loop.py``) and the continuous scheduler share.

    ``enqueue`` appends ``n_hard`` valid rows of a compacted slab pytree in
    chunks, calling ``drain_one`` (pop a bucket + dispatch stage 2) whenever
    the ring is out of space — so a batch hairier than the whole ring still
    serves, it just backpressures stage 1 harder (paper Fig. 7). Full
    buckets drain first by construction (count == size when stalled).

    The slab arrives from stage 1; placing it onto ``ex`` IS the stage
    boundary hop — under a disaggregated placement that is a device-to-
    device ``jax.device_put`` across submesh shardings, and the ring itself
    is resident on stage 2's submesh."""

    def __init__(self, sc: ServeConfig, ex, stats: ServeStats):
        self.sc = sc
        self.ex = ex                      # the ring + stage 2 live here
        self.stats = stats
        self.size = sc.queue_depth * sc.capacity
        self._buf: Optional[dict] = None
        self.count = 0                    # host mirror of buf['count']
        self._row_nbytes = 0              # per-row payload bytes (all leaves)

    def _note_row_bytes(self) -> None:
        """Cache the per-row payload size of the live buffer — the unit of
        ``stats.ring_bytes_moved`` (a paged payload ships page *indices*
        instead of cache rows, which is exactly what this gauge shows)."""
        self._row_nbytes = sum(
            int(np.prod(leaf.shape[1:])) * leaf.dtype.itemsize
            for leaf in jax.tree.leaves(self._buf["data"]))

    def ensure(self, row_spec) -> dict:
        """Allocate (or return) the device buffer for payload rows shaped
        ``row_spec`` — the fused pool tick needs the buffer BEFORE launch
        (it is donated through the tick), whereas ``enqueue`` can defer
        allocation to the first slab it sees."""
        if self._buf is None:
            self._buf = self.ex.place_io(ring_init(self.size, row_spec))
            self._note_row_bytes()
        return self._buf

    def put_buf(self, buf: dict) -> None:
        """Swap in the buffer a donated tick returned (the old one's
        storage was consumed by the donation)."""
        self._buf = buf

    def note_enqueued(self, k: int) -> None:
        """Advance the host count mirror for ``k`` rows a fused tick
        already wrote device-side."""
        self.count += k
        self.stats.ring_bytes_moved += k * self._row_nbytes

    def enqueue(self, slab_tree, slab_ids, n_hard: int,
                drain_one: Callable[[], None], off: int = 0,
                fire_fault: bool = True) -> None:
        """Append rows [off, n_hard) of the compacted slab. ``off > 0`` is
        the fused tick's overflow spill: the first ``off`` rows already
        sit in the ring (written in-kernel), and the tick fired the
        'enqueue' fault point itself, so the spill skips it
        (``fire_fault=False`` — one visit per logical enqueue either
        way)."""
        if fire_fault:
            faults.fault_point("enqueue")
        slab_tree = self.ex.place_io(slab_tree)
        slab_ids = self.ex.place_io(slab_ids)
        if self._buf is None:
            spec = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape[1:], x.dtype),
                slab_tree)
            self._buf = self.ex.place_io(ring_init(self.size, spec))
            self._note_row_bytes()
        while off < n_hard:
            free = self.size - self.count
            if free == 0:
                self.stats.n_stalls += 1
                before = self.count
                # a transiently-failed drain retries with backoff; a drain
                # that "succeeds" without freeing ring space would spin this
                # stall loop forever, so no-progress is an error, not a hang
                faults.retry(drain_one, what="backpressure-drain")
                if self.count >= before:
                    raise RuntimeError(
                        "ring backpressure drain made no progress "
                        f"(count {before} -> {self.count}) — stage-2 "
                        "dispatch is stuck")
                continue
            take = min(free, n_hard - off)
            self._buf = _ring_enqueue_range(self._buf, slab_tree, slab_ids,
                                            off, off + take)
            self.count += take
            self.stats.ring_bytes_moved += take * self._row_nbytes
            off += take

    def pop(self):
        """Pop up to ``capacity`` rows; returns (bucket pytree, ids,
        n_taken) or None when the ring is empty — n_taken is authoritative
        for callers mirroring the FIFO host-side. Updates occupancy
        stats."""
        take = min(self.count, self.sc.capacity)
        if take == 0:
            return None
        self._buf, bucket, bucket_ids = ring_drain(self._buf,
                                                   self.sc.capacity)
        self.count -= take
        self.stats.n_stage2 += take
        self.stats.record_bucket(take / self.sc.capacity)
        return bucket, bucket_ids, take


# ---------------------------------------------------------------------------
# sample-major row helpers (shared with serve_loop): gather rows into a
# compacted slab / scatter updated bucket rows back into the store
# ---------------------------------------------------------------------------

@jax.jit
def _gather_rows(rows, ids):
    """Gather sample-major rows by compacted slab ids (-1 flush slots read
    row 0; their content is never used — flush ids drop on enqueue)."""
    take = jnp.maximum(ids, 0)
    return jax.tree.map(lambda m: jnp.take(m, take, axis=0), rows)


@functools.partial(jax.jit, donate_argnums=(0,))
def _scatter_rows(rows, bucket_rows, ids):
    """Scatter updated bucket cache rows back into the sample-major store;
    flush ids (-1) scatter out of bounds and are dropped. Donated: the
    store is updated in place."""
    b = jax.tree.leaves(rows)[0].shape[0]
    safe = jnp.where(ids >= 0, ids, b)
    return jax.tree.map(lambda m, r: m.at[safe].set(r, mode="drop"),
                        rows, bucket_rows)


# ---------------------------------------------------------------------------
# open-loop request plumbing: arrivals, clocks
# ---------------------------------------------------------------------------

@dataclass
class Request:
    """One decode request in the admission queue. ``arrival_time`` is in the
    scheduler clock's time base (seconds); a request is admissible once the
    clock passes it — submit everything up front to replay a trace.

    ``tenant``/``slo_class`` identify who submitted and under which service
    class — the fleet router (``runtime/router.py``) keys priority
    admission, per-tenant budgets and difficulty estimates on them; a bare
    scheduler ignores both (single-tenant serving is the degenerate
    fleet)."""
    sample_id: int
    prompt: np.ndarray          # (S,) int32
    n_tokens: int               # total tokens to emit (incl. prefill token)
    arrival_time: float = 0.0
    tenant: str = "default"
    slo_class: str = "standard"


class Clock:
    """Wall clock with fast-forward: ``now`` is seconds since construction
    plus all skipped idle time, so an idle server jumps to the next arrival
    instead of sleeping, while *service* time stays real wall time. Both
    policies measure latency in this one time base."""

    def __init__(self):
        self._t0 = time.perf_counter()
        self._skip = 0.0

    def now(self) -> float:
        return time.perf_counter() - self._t0 + self._skip

    def advance_to(self, t: float) -> None:
        gap = t - self.now()
        if gap > 0:
            self._skip += gap


class LogicalClock:
    """Deterministic clock for property tests: only ``advance_to`` moves it."""

    def __init__(self, t: float = 0.0):
        self._t = t

    def now(self) -> float:
        return self._t

    def advance_to(self, t: float) -> None:
        self._t = max(self._t, t)


# ---------------------------------------------------------------------------
# pooled segment-cache helpers. A segment cache (ee.split_caches output /
# run_layers layout) is {'first': [per-layer pytrees, batch axis 0],
# 'blocks': leaves with batch axis 1 (leading superblock axis), 'rem':
# [batch axis 0]}. The slot pool holds one such tree of width n_slots and
# admits/ticks rows in place.
# ---------------------------------------------------------------------------

def _seg_map2(f_ax0, f_ax1, a, b):
    return {"first": jax.tree.map(f_ax0, a["first"], b["first"]),
            "blocks": jax.tree.map(f_ax1, a["blocks"], b["blocks"]),
            "rem": jax.tree.map(f_ax0, a["rem"], b["rem"])}


def seg_pool_like(seg, n_slots: int):
    """A zeroed slot-pool segment cache shaped like ``seg`` (batch 1) but
    ``n_slots`` wide."""
    def ax0(x):
        return jnp.zeros((n_slots,) + x.shape[1:], x.dtype)

    def ax1(x):
        return jnp.zeros(x.shape[:1] + (n_slots,) + x.shape[2:], x.dtype)

    return {"first": jax.tree.map(ax0, seg["first"]),
            "blocks": jax.tree.map(ax1, seg["blocks"]),
            "rem": jax.tree.map(ax0, seg["rem"])}


def _seg_select(active, new, old):
    """Per-slot cache select: keep ``new`` where the slot was active this
    tick, ``old`` otherwise — parked/free rows' garbage compute is discarded
    and recurrent state advances exactly once per consumed token."""
    def sel(axis):
        def f(n, o):
            shape = [1] * n.ndim
            shape[axis] = n.shape[axis]
            return jnp.where(active.reshape(shape), n, o)
        return f

    return _seg_map2(sel(0), sel(1), new, old)


# ---------------------------------------------------------------------------
# jitted pool-tick / lane-update steps (module level: the jit cache is keyed
# on the stage callables, so fresh scheduler instances over the same
# DecodeFns reuse compiled programs)
# ---------------------------------------------------------------------------

def _tick_fn(fn) -> jax.tree_util.Partial:
    """A stage body as a pytree ARGUMENT of the tick jit: the arrays a
    ``jax.tree_util.Partial`` binds (the stage params) become program
    arguments, while the function itself keys the compile cache. A static
    or closed-over function would embed its arrays in the program as
    constants — gigabytes of host memory per compile at published
    widths."""
    if isinstance(fn, jax.tree_util.Partial):
        return fn
    return jax.tree_util.Partial(fn)


@functools.partial(jax.jit, donate_argnums=(1,),
                   static_argnames=("backend", "mesh"))
def _pool_tick(tok, c1, pos, active, start, budget, c_thr, *, s1, backend,
               mesh):
    """One continuous tick over the whole slot pool: masked stage 1 at
    per-slot positions, fused exit decision + compaction, easy-token
    advance. The active mask is device-resident — easy rows stay active
    until their token budget is spent (``pos - start + 1`` counts emitted
    tokens), hard rows deactivate (parked) — so a tick needs no host
    uploads at all. Returns everything the host needs to park/emit/enqueue:
    (new_c1, hard slab, slab slot ids, slab steps, n_hard, easy mask,
    hard mask, emitted tokens, new tok lane, new pos lane, new active,
    per-slot exit confidences — the controller's reservoir feed, already
    computed by the fused decision kernel so exposing it is free), and
    last the MoE load counters stage 1 returns (an empty list for a model
    without MoE layers)."""
    with jax.named_scope("pool_tick"):
        h, nc1, exit_logits, *load = s1(tok, c1, pos)
        nc1 = _seg_select(active, nc1, c1)
        # the decision kernel's pred IS the greedy token — one logits pass
        # serves both the exit decision and the emitted token
        exit_mask, pred, conf = dispatch.exit_decision_op(
            exit_logits, c_thr, backend=backend, mesh=mesh)
        easy = active & exit_mask
        hard = active & ~exit_mask
        n = tok.shape[0]
        slab, src, n_hard = dispatch.gather_compact_op(
            h, hard, n, backend=backend, mesh=mesh)
        slab_slots = src                      # slot index IS the ring id
        slab_steps = jnp.where(src >= 0, jnp.take(pos, jnp.maximum(src, 0)),
                               0)
        new_tok = jnp.where(easy[:, None], pred[:, None], tok)
        new_pos = pos + easy.astype(jnp.int32)
        new_active = easy & (new_pos - start + 1 < budget)
        return (nc1, slab, slab_slots, slab_steps, n_hard, easy, hard, pred,
                new_tok, new_pos, new_active, conf, load)


@functools.partial(jax.jit, donate_argnums=(1, 6),
                   static_argnames=("backend",))
def _pool_tick_fused(tok, c1, pos, active, start, budget, ring, rows, c_thr,
                     *, s1, backend):
    """The persistent-tick variant: ONE compiled program per steady-state
    decode step. Stage 1, the cache select, the exit decision, compaction
    AND the ring enqueue all trace into this jit — the ring buffer is
    donated through the tick, the fused dispatch kernel writes compacted
    hard rows (hidden + stage-2 cache rows gathered from the sample-major
    store + step lanes) straight into the slabs at (head+count) offsets,
    and the kernel's pred doubles as the emitted token. ``rows`` (the
    stage-2 store) is read, never donated. Rows past the ring's free space
    are not written; the host spills them through the composed
    backpressure chain using the returned ``src``/``h``.

    Only valid on a non-disaggregated placement (one submesh cannot span
    two). Returns what ``_pool_tick`` does, the MoE load counters last."""
    with jax.named_scope("pool_tick_fused"):
        h, nc1, exit_logits, *load = s1(tok, c1, pos)
        nc1 = _seg_select(active, nc1, c1)
        n = tok.shape[0]
        lanes = jnp.arange(n, dtype=jnp.int32)  # slot index IS the ring id
        payload = {"h": h, "cache": rows, "step": pos}
        ring, exit_mask, pred, conf, src, n_hard = dispatch.fused_dispatch(
            exit_logits, active, lanes, payload, ring, c_thr, backend=backend)
        easy = active & exit_mask
        hard = active & ~exit_mask
        new_tok = jnp.where(easy[:, None], pred[:, None], tok)
        new_pos = pos + easy.astype(jnp.int32)
        new_active = easy & (new_pos - start + 1 < budget)
        return (nc1, ring, h, src, n_hard, easy, hard, pred, new_tok,
                new_pos, new_active, conf, load)


@functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3, 4, 5))
def _admit_stage1(c1_pool, tok, pos, active, start, budget, logits0, c1_rows,
                  slots, position, budgets):
    """One-dispatch stage-1 side of a chunked admission: greedy first tokens
    from the chunk's prefill logits (k, V), the chunk's stage-1 cache rows
    into their slots' pool rows, and the slots' lanes (next token, position,
    per-request token budget; active iff the budget leaves decode tokens).
    ``slots`` is the (k,) slot-index vector; ``position`` the shared prompt
    length. Donated pools; returns the first tokens (k,) on device (one
    host sync per chunk, not per request)."""
    tok0 = jnp.argmax(logits0, axis=-1).astype(jnp.int32)      # (k,)

    def ax0(p, s):
        return p.at[slots].set(s.astype(p.dtype))

    def ax1(p, s):
        return p.at[:, slots].set(s.astype(p.dtype))

    return (_seg_map2(ax0, ax1, c1_pool, c1_rows),
            tok.at[slots, 0].set(tok0), pos.at[slots].set(position),
            active.at[slots].set(budgets > 1),
            start.at[slots].set(position), budget.at[slots].set(budgets),
            tok0)


def _record_load(program: str, load) -> None:
    """The MoE load counters a program returned (``moe.LOAD_FIELDS``,
    summed over its MoE layers), as the args of a ``moe_load`` span on the
    open profile window's record. ``load`` is empty for a model without
    MoE layers: nothing is recorded."""
    if load:
        with observe.annotate("moe_load", program=program, **dict(
                zip(LOAD_FIELDS, (int(v) for v in load[0])))):
            pass


@functools.partial(jax.jit, donate_argnums=(0, 1, 2))
def _unpark_lanes(tok, pos, active, start, budget, ids, s2_tok):
    """Apply a stage-2 bucket to the lanes: each valid id's next token is
    the bucket's greedy token, its position advances past the consumed one,
    and it re-activates unless its token budget is now spent (flush ids -1
    drop)."""
    n = tok.shape[0]
    safe = jnp.where(ids >= 0, ids, n)
    tok = tok.at[safe].set(s2_tok[:, None], mode="drop")
    pos = pos.at[safe].add(1, mode="drop")
    live = jnp.take(pos, safe, mode="clip") - jnp.take(start, safe,
                                                       mode="clip") + 1 \
        < jnp.take(budget, safe, mode="clip")
    active = active.at[safe].set(live, mode="drop")
    return tok, pos, active


@jax.jit
def _greedy_row(logits):
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


# ---------------------------------------------------------------------------
# device-resident page allocator: one int32 free-list lane whose prefix
# [0, n_free) holds the free page ids (page 0 is the NULL page and is never
# allocated). alloc slices the tail of the free prefix into a null-padded
# block-table row WITHOUT touching the lane (the host n_free cursor is the
# only mutation, so a failed admission needs no device rollback); free
# compacts a row's live pages back onto the prefix end. Both are O(row)
# jitted programs — no host loop over pages.
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("max_pages",))
def _alloc_row(lane, n_free, count, *, max_pages: int):
    """Pop ``count`` pages off the free prefix's tail (lane[n_free-count :
    n_free]) into a (max_pages,) block-table row, null-padded past
    ``count``. Pure: the lane is read, never written."""
    j = jnp.arange(max_pages, dtype=jnp.int32)
    idx = jnp.clip(n_free - count + j, 0, lane.shape[0] - 1)
    return jnp.where(j < count, jnp.take(lane, idx), 0).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("max_pages",))
def _alloc_rows(lane, n_free, counts, *, max_pages: int):
    """Batched ``_alloc_row``: pop ``counts[i]`` pages per row off the free
    prefix's tail, LIFO in row order — row i reads the same lane slice the
    i-th sequential ``_alloc_row`` call would, so one dispatch admits a
    whole chunk. Pure like ``_alloc_row``."""
    starts = n_free - jnp.cumsum(counts)
    j = jnp.arange(max_pages, dtype=jnp.int32)[None, :]
    idx = jnp.clip(starts[:, None] + j, 0, lane.shape[0] - 1)
    return jnp.where(j < counts[:, None], jnp.take(lane, idx),
                     0).astype(jnp.int32)


@functools.partial(jax.jit, donate_argnums=(0,))
def _free_row(lane, n_free, bt_row):
    """Return a block-table row's live pages (entries > 0) to the free
    prefix: cumsum-compacted onto positions [n_free, n_free+count); null
    entries scatter out of bounds and drop. Donated — the lane is updated
    in place."""
    valid = bt_row > 0
    pos = jnp.cumsum(valid.astype(jnp.int32)) - 1
    dst = jnp.where(valid, n_free + pos, lane.shape[0])
    return lane.at[dst].set(bt_row, mode="drop")


@functools.partial(jax.jit, donate_argnums=(0, 2))
def _free_slot_row(lane, n_free, rows, slot):
    """Free-on-finish as ONE program: read slot ``slot``'s block-table row
    out of the (n_slots, max_pages) lane, compact its live pages onto the
    free prefix, and zero the row. Fusing the gather + free + clear keeps
    the per-finish cost at a single jitted dispatch (three eager ops here
    dominated the paged tick in profiles)."""
    bt_row = rows[slot]
    valid = bt_row > 0
    pos = jnp.cumsum(valid.astype(jnp.int32)) - 1
    dst = jnp.where(valid, n_free + pos, lane.shape[0])
    return (lane.at[dst].set(bt_row, mode="drop"),
            rows.at[slot].set(0))


class PageAllocator:
    """Free-list allocator over a shared KV page pool. ``n_pages`` counts
    ALLOCATABLE pages — ids 1..n_pages; the pool arrays hold one extra page
    at index 0, the all-zeros NULL page every padded block-table entry
    points at. The free set lives on device (the lane) with a host-side
    ``n_free`` cursor; allocation order is LIFO, which keeps recently-freed
    (cache-warm) pages hot.

    ``snapshot``/``restore`` give live migration an exact state capture:
    the snapshot DEFENSIVELY COPIES the lane (``_free_row`` donates it, so
    an aliased snapshot would be invalidated by the next free), and restore
    copies again so one snapshot survives multiple rollbacks."""

    def __init__(self, n_pages: int, page_size: int, ex=None):
        if n_pages < 1:
            raise ValueError(f"n_pages must be >= 1, got {n_pages}")
        if page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {page_size}")
        self.n_pages = n_pages
        self.page_size = page_size
        lane = jnp.arange(1, n_pages + 1, dtype=jnp.int32)
        self._lane = ex.place_io(lane) if ex is not None else lane
        self.n_free = n_pages

    @staticmethod
    def pages_for(span: int, page_size: int) -> int:
        """Pages needed to hold a ``span``-token cache row (>= 1: even an
        empty row owns its tail page)."""
        return max(1, -(-int(span) // int(page_size)))

    @property
    def n_in_use(self) -> int:
        return self.n_pages - self.n_free

    def alloc(self, count: int, *, max_pages: int) -> jnp.ndarray:
        """Allocate ``count`` pages as a null-padded (max_pages,) block-
        table row. The caller checks ``n_free`` first — admission
        backpressure is a policy decision, not an exception path."""
        if count > max_pages:
            raise ValueError(f"request needs {count} pages but a block "
                             f"table row holds {max_pages}")
        if count > self.n_free:
            raise RuntimeError(f"page pool exhausted: need {count}, "
                               f"free {self.n_free}/{self.n_pages}")
        row = _alloc_row(self._lane, self.n_free, count,
                         max_pages=max_pages)
        self.n_free -= count
        return row

    def alloc_many(self, counts: List[int], *, max_pages: int):
        """Allocate a chunk of block-table rows in ONE dispatch; row i gets
        ``counts[i]`` pages, identical page ids to ``counts[i]`` sequential
        ``alloc`` calls. Returns a (k, max_pages) i32 array."""
        if any(c > max_pages for c in counts):
            raise ValueError(f"request needs {max(counts)} pages but a "
                             f"block table row holds {max_pages}")
        total = sum(counts)
        if total > self.n_free:
            raise RuntimeError(f"page pool exhausted: need {total}, "
                               f"free {self.n_free}/{self.n_pages}")
        rows = _alloc_rows(self._lane, self.n_free,
                           jnp.asarray(counts, jnp.int32),
                           max_pages=max_pages)
        self.n_free -= total
        return rows

    def free(self, bt_row, count: int) -> None:
        """Return a block-table row's ``count`` live pages to the free
        list."""
        self._lane = _free_row(self._lane, self.n_free, bt_row)
        self.n_free += count

    def free_slot(self, rows, slot: int, count: int):
        """Free slot ``slot``'s pages straight out of the (n_slots, M)
        block-table lane and zero its row, one fused dispatch; returns the
        updated lane (``rows`` is donated)."""
        self._lane, rows = _free_slot_row(self._lane, self.n_free, rows,
                                          slot)
        self.n_free += count
        return rows

    def snapshot(self):
        return jnp.array(self._lane, copy=True), self.n_free

    def restore(self, snap) -> None:
        lane, n_free = snap
        self._lane = jnp.array(lane, copy=True)
        self.n_free = int(n_free)

    def relayout(self, place_fn) -> None:
        """Re-place the lane onto a new submesh (live migration's device
        re-split)."""
        self._lane = place_fn(self._lane)


# ---------------------------------------------------------------------------
# the continuous slot scheduler
# ---------------------------------------------------------------------------

_FREE, _ACTIVE, _PARKED = 0, 1, 2


# what stage fns must carry for the paged stage-2 store
_PAGED_SURFACE = ("page_size", "s2_paged", "pool_init", "admit_pages")


class ContinuousScheduler:
    """Continuous-batching two-stage EE decode over a fixed slot pool.

    ``fns`` is a ``runtime.serve_loop.DecodeFns`` with its paged surface
    (``page_size``/``s2_paged``/``pool_init``/``admit_pages``, as
    ``decode_stage_fns(page_size=...)`` builds it; duck-typed — property
    tests drive the policy with toy stage callables). All admitted requests
    must satisfy ``len(prompt) + n_tokens <= max_len`` (the pool's shared
    cache width), a multiple of ``page_size``. ``n_pages`` allocatable
    pages back the pool (default: ``n_slots * max_len / page_size``; pass
    less to serve more slots than a full-width store would hold at the same
    HBM budget).

    Under a disaggregated ``placement`` the slot lanes, pooled stage-1 cache
    and the pool tick live on ``ex1``; the page pool, the block-table lane,
    the ring and the bucketed vector-step ``stage2_decode`` dispatches on
    ``ex2``. The hard slab + step lane hop ex1 -> ex2 at enqueue and each
    bucket's greedy tokens hop ex2 -> ex1 at unpark — ``jax.device_put``
    transfers, never the host.

    ``results`` maps sample id -> list of emitted greedy tokens (stream
    order). Latency is recorded per request in ``stats``.

    **Control surface** (the drift controller's actuators —
    ``runtime/controller.py``): ``set_c_thr`` re-aims the exit threshold
    (traced arg, never recompiles), ``set_eager_drain_below`` adapts the
    partial-bucket drain policy, ``set_active_cap`` bounds live slot
    occupancy (admission-side, so shrink happens by attrition — no slot is
    ever preempted), and ``request_capacity`` schedules a bucket re-size
    that applies only at a DISCRETE re-plan point (empty ring), the one
    actuation allowed to recompile. With no controller attached every
    control field keeps its constructor value and the hot loop is
    byte-for-byte the uncontrolled one.
    """

    def __init__(self, fns, sc: ServeConfig, *, n_slots: int, max_len: int,
                 placement: Optional[StagePlacement] = None, clock=None,
                 eager_drain_below: Optional[int] = None,
                 fns_factory: Optional[Callable] = None,
                 n_pages: Optional[int] = None, events=None):
        if n_slots < 1:
            raise ValueError(f"n_slots must be >= 1, got {n_slots}")
        missing = [name for name in _PAGED_SURFACE
                   if getattr(fns, name, None) is None]
        if missing:
            raise ValueError(
                f"stage fns lack the paged decode surface {missing}: build "
                "them with decode_stage_fns(page_size=...)")
        self.fns = fns
        # optional request-lifecycle feed (telemetry.EventLog): when set,
        # the scheduler emits submit/admit/park/bucket/finish/tick events
        # the observability plane (runtime/observe.Tracer) assembles into
        # per-request span trees. None (the default) costs the hot path
        # one attribute check per emission site.
        self.events = events
        # fns_factory(placement) -> DecodeFns rebuilds the stage callables
        # against a NEW placement (re-slicing params per ee.split_params
        # onto its submeshes) — the hook live migration needs to perform a
        # full chip re-split rather than only a capacity change
        self.fns_factory = fns_factory
        self.sc = sc
        self.n_slots = n_slots
        self.max_len = max_len
        self.c_thr = float(sc.c_thr)
        self.controller = None               # attached via controller.attach
        self.active_cap = n_slots            # live-slot occupancy cap
        self._pending_capacity: Optional[int] = None
        self._pending_migration = None       # armed via request_migration
        self._admission_open = True          # closed during QUIESCE
        # starvation-aware dispatch: a pool tick costs the same whether 2 or
        # n_slots rows are active, so once the ACTIVE count dips below this
        # threshold a partial bucket is worth its flush padding — stage-2
        # bubbles are cheaper than stage-1 ticks over a starved pool. The
        # default (bucket capacity) keeps at least a bucket's worth of slots
        # decoding; 0 recovers pure full-bucket dispatch (maximum fill,
        # maximum parking latency).
        self.eager_drain_below = (sc.capacity if eager_drain_below is None
                                  else eager_drain_below)
        self.placement = placement or StagePlacement.single_device()
        self.ex1, self.ex2 = self.placement.ex1, self.placement.ex2
        self.clock = clock or Clock()
        self.stats = ServeStats()
        self.stats.record_placement(self.placement)
        self.ring = RingQueue(sc, self.ex2, self.stats)
        # the stage-2 store is a shared PAGE POOL + a per-slot block-table
        # lane (self._rows, reused verbatim as the ring payload's "cache"
        # lane: a hop ships page INDICES, never cache rows), and capacity
        # is measured in pages
        self.page_size = fns.page_size
        if max_len % self.page_size != 0:
            raise ValueError(
                f"max_len={max_len} must be a multiple of page_size="
                f"{self.page_size} (parity with the dense reference needs "
                f"the gathered span == max_len)")
        self.max_pages = max_len // self.page_size
        self.n_pages = (int(n_pages) if n_pages is not None
                        else n_slots * self.max_pages)
        self._pool = None                    # the stage-2 page pool
        self._alloc = PageAllocator(self.n_pages, self.page_size, ex=self.ex2)
        self.stats.cache_pages_total = self.n_pages
        self.stats.cache_page_size = self.page_size
        # the transport-agnostic admission queue (runtime/serve_api.py):
        # owns FIFO order, the queued-sid set, submit-side validation and
        # the revocation primitive fleet preemption uses
        self.queue: RequestQueue = RequestQueue(
            max_len=max_len, is_dup=lambda sid: sid in self.results)
        self.results: Dict[int, List[int]] = {}
        # host-side slot metadata
        self._sid = [-1] * n_slots
        self._emitted = [0] * n_slots
        self._budget = [0] * n_slots
        self._state = [_FREE] * n_slots
        # page bookkeeping: pages owned / prompt length per slot (live
        # cache tokens = prompt + emitted - 1 — the fragmentation gauge)
        self._slot_pages = [0] * n_slots
        self._slot_len = [0] * n_slots
        self._free: List[int] = list(range(n_slots - 1, -1, -1))
        self.peak_busy = 0
        # per-slot hardness tally (hard decisions / decisions of the
        # resident request) and the per-request finish feed: (sid, n_hard,
        # n_decisions) tuples appended at finish — the router's tenant-
        # difficulty signal. Bounded like every other stats series; a
        # standalone scheduler that never drains it just keeps the recent
        # window.
        self._slot_hard = [0] * n_slots
        self._slot_dec = [0] * n_slots
        self._finished: Deque = deque(maxlen=_SERIES_CAP)
        # parked slots in ring order (the compaction is contractually
        # stable, so ascending slot order per tick IS enqueue order) — lets
        # bucket results be harvested lazily: state transitions happen at
        # dispatch, token values land under a bounded pending window
        self._parked_fifo: Deque[int] = deque()
        self._pending: Deque = deque()
        # fused-tick ring row spec, derived abstractly at pool build; None
        # means the stage fns resisted eval_shape and ticks stay composed
        self._ring_row_spec = None
        # device-side pool state (lazy: shapes come from the first
        # admission); lanes: next token, position, active/start/budget
        self._c1 = None
        self._rows = None
        self._tok = None
        self._pos = None
        self._active_lane = None
        self._start_lane = None
        self._budget_lane = None

    # -- control surface (drift-controller actuators) ------------------------

    def set_c_thr(self, c_thr: float) -> None:
        """Re-aim the exit threshold from the next tick on. ``c_thr`` is a
        traced argument of the pool tick, so this never recompiles."""
        self.c_thr = float(c_thr)

    def set_eager_drain_below(self, k: int) -> None:
        """Adapt the starvation-aware partial-drain policy: dispatch a
        partial bucket once the live count dips below ``k`` (0 = pure
        full-bucket dispatch)."""
        self.eager_drain_below = max(0, int(k))

    def set_active_cap(self, cap: int) -> None:
        """Bound live slot occupancy. Admission-side: a shrink takes effect
        by attrition (busy slots finish and are not backfilled), never by
        preempting an in-flight request. Clamped to [1, n_slots] so the
        pool always makes progress."""
        self.active_cap = max(1, min(int(cap), self.n_slots))

    def request_capacity(self, capacity: int) -> None:
        """Schedule a stage-2 bucket-capacity re-size (the re-plan
        actuator's apply path). Deferred to the next DISCRETE re-plan
        point — an empty ring — where no in-flight row's home can change
        shape under it; the resized ``ring_drain`` is the one steady-state
        recompile the controller is allowed to cause."""
        self._pending_capacity = max(1, min(int(capacity), self.n_slots))

    def _maybe_apply_capacity(self) -> None:
        if self._pending_capacity is None or self.ring.count > 0:
            return
        cap, self._pending_capacity = self._pending_capacity, None
        if cap == self.sc.capacity:
            return
        # fresh config + ring at the new capacity (the caller's ServeConfig
        # is never mutated); the buffer re-allocates lazily on next enqueue
        self.sc = ServeConfig(capacity=cap, queue_depth=self.sc.queue_depth,
                              c_thr=self.sc.c_thr,
                              max_pending=self.sc.max_pending,
                              harvest_timeout_s=self.sc.harvest_timeout_s)
        self.ring = RingQueue(self.sc, self.ex2, self.stats)

    def request_migration(self, plan) -> None:
        """Arm a live migration (a ``runtime.migration.MigrationPlan``).
        Like ``request_capacity`` it defers to a discrete point — the top
        of the next loop iteration — where the migrator quiesces, snapshots,
        re-places and resumes the pool; on failure it rolls back and
        serving continues on the old placement. Arming again before the
        previous plan ran replaces it (last writer wins)."""
        self._pending_migration = plan

    def _maybe_migrate(self) -> None:
        if self._pending_migration is None:
            return
        plan, self._pending_migration = self._pending_migration, None
        # lazy import: migration.py drives this scheduler (not vice versa)
        from repro.runtime.migration import LiveMigrator, MigrationError
        try:
            LiveMigrator(self, plan).run()
        except MigrationError:
            # the migrator already rolled back to the pre-migration
            # placement and re-opened admission; serving continues there.
            # The attempt is visible in stats.n_migration_rollbacks and the
            # fault log — nothing to re-raise: a failed RE-PLAN must not
            # kill a healthy server.
            pass

    # -- admission -----------------------------------------------------------

    def submit(self, req: Request) -> None:
        """Queue one request (arrival order = queue order; arrival_time
        gates admissibility against the scheduler clock). Validation —
        the shared ``serve_api.validate_request`` surface — happens at
        the queue's push, so a malformed request is rejected before it
        can damage in-flight state mid-admission."""
        self.queue.append(req)
        if self.events is not None:
            self.events.emit("submit", sid=req.sample_id,
                             arrival=req.arrival_time,
                             n_tokens=req.n_tokens)

    def _ensure_pool(self, c1_row, rows_row) -> None:
        if self._c1 is not None:
            return
        self._c1 = seg_pool_like(c1_row, self.n_slots)
        # the slot-major store is the BLOCK-TABLE lane (zero rows = all-null
        # tables); the actual cache bytes live in one shared page pool (+1
        # page: the NULL page at index 0)
        self._rows = self.ex2.place_io(
            jnp.zeros((self.n_slots, self.max_pages), jnp.int32))
        self._pool = self.ex2.place_io(
            self.fns.pool_init(rows_row, self.n_pages + 1))
        self.stats.cache_hbm_bytes = sum(
            leaf.nbytes for leaf in jax.tree.leaves(self._pool))
        self._tok = self.ex1.place_io(jnp.zeros((self.n_slots, 1), jnp.int32))
        self._pos = self.ex1.place_io(jnp.zeros((self.n_slots,), jnp.int32))
        self._active_lane = self.ex1.place_io(jnp.zeros((self.n_slots,),
                                                        bool))
        self._start_lane = self.ex1.place_io(jnp.zeros((self.n_slots,),
                                                       jnp.int32))
        self._budget_lane = self.ex1.place_io(jnp.zeros((self.n_slots,),
                                                        jnp.int32))
        # derive the fused tick's ring row spec without executing stage 1:
        # the ring must exist BEFORE the first fused launch (it is donated
        # through the tick), and its 'h' leaf shape is stage 1's output.
        # Duck-typed host-side fns that pull a tracer to the host (JAX's
        # tracer-conversion errors) keep the composed three-program tick;
        # any other failure is a real stage-1 bug and raises here.
        try:
            h_av, *_ = jax.eval_shape(self.fns.s1_raw, self._tok,
                                      self._c1, self._pos)
        except jax.errors.JAXTypeError:
            self._ring_row_spec = None
            return
        self._ring_row_spec = {
            "h": jax.ShapeDtypeStruct(h_av.shape[1:], h_av.dtype),
            "cache": jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape[1:], x.dtype),
                self._rows),
            "step": jax.ShapeDtypeStruct((), jnp.int32),
        }

    def _admit_chunk(self, reqs: List[Request]) -> None:
        """Admit a chunk of requests sharing one prompt length with ONE
        batched prefill + one fused pool write — per-request admission cost
        is the classic continuous-batching tax, and chunking it is what
        keeps backfill from serializing the pipeline."""
        prompts = np.stack([np.asarray(r.prompt, np.int32) for r in reqs])
        S = prompts.shape[1]
        with observe.annotate("admit_chunk", n=len(reqs), prompt_len=S):
            for r in reqs:
                self.stats.n_samples += 1
                self.stats.record_submit(r.sample_id, r.arrival_time)
            with observe.annotate("prefill"):
                logits0, caches, *load = self.fns.prefill(
                    self.ex1.place_io(jnp.asarray(prompts)), self.max_len)
                c1_rows, rows_rows = self.fns.split(caches)
            self._ensure_pool(c1_rows, rows_rows)
            slots = [self._free.pop() for _ in reqs]
            slots_dev = jnp.asarray(slots, jnp.int32)
            budgets = jnp.asarray([r.n_tokens for r in reqs], jnp.int32)
            (self._c1, self._tok, self._pos, self._active_lane,
             self._start_lane, self._budget_lane, tok0) = _admit_stage1(
                self._c1, self._tok, self._pos, self._active_lane,
                self._start_lane, self._budget_lane, logits0, c1_rows,
                self.ex1.place_io(slots_dev), S, self.ex1.place_io(budgets))
            # alloc-on-admit: one block-table row per request (the page-
            # budget admission check in _try_admit guarantees the free list
            # covers the chunk), then ONE fused pool scatter moves the
            # chunk's prefill cache rows into their pages
            needs = []
            for r, slot in zip(reqs, slots):
                need = PageAllocator.pages_for(S + r.n_tokens - 1,
                                               self.page_size)
                needs.append(need)
                self._slot_pages[slot] = need
                self._slot_len[slot] = S
            bt_rows = self.ex2.place_io(
                self._alloc.alloc_many(needs, max_pages=self.max_pages))
            self._pool = self.fns.admit_pages(
                self._pool, self.ex2.place_io(rows_rows), bt_rows)
            self._rows = _scatter_rows(self._rows, bt_rows,
                                       self.ex2.place_io(slots_dev))
            with observe.annotate("admit_sync"):
                # one admission sync per chunk
                tok0_np, load_np = jax.device_get((tok0, load))
            _record_load("prefill", load_np)
            for j, (r, slot) in enumerate(zip(reqs, slots)):
                self.results[r.sample_id] = [int(tok0_np[j])]
                self._sid[slot] = r.sample_id
                self._emitted[slot] = 1
                self._budget[slot] = r.n_tokens
                self._state[slot] = _ACTIVE
                self._slot_hard[slot] = 0
                self._slot_dec[slot] = 0
                if self.events is not None:
                    self.events.emit("admit", sid=r.sample_id, slot=slot,
                                     prompt_len=S)
                if r.n_tokens == 1:          # prefill-only: free right away
                    self._finish_slot(slot)
            self.peak_busy = max(self.peak_busy,
                                 self.n_slots - len(self._free))

    def _try_admit(self) -> int:
        """Admit admissible requests in arrival order, chunked to power-of-2
        batch sizes (bounded set of prefill shapes -> bounded compiles). A
        chunk is a same-prompt-length prefix of the admissible run, bounded
        by free slots AND the controller's live-occupancy cap. Returns the
        number of requests admitted."""
        admitted = 0
        if not self._admission_open:          # QUIESCE: migration in flight
            return admitted
        while self._free and self.queue:
            busy = self.n_slots - len(self._free)
            headroom = min(len(self._free), self.active_cap - busy)
            if headroom <= 0:
                return admitted
            now = self.clock.now()
            n_adm = 0
            pages_acc = 0
            S0 = len(self.queue[0].prompt)
            for r in self.queue:
                if (r.arrival_time > now or len(r.prompt) != S0
                        or n_adm >= headroom):
                    break
                need = PageAllocator.pages_for(
                    len(r.prompt) + r.n_tokens - 1, self.page_size)
                if need > self.n_pages:
                    raise ValueError(
                        f"request {r.sample_id} needs {need} pages but the "
                        f"pool holds {self.n_pages} total — it can never "
                        "be admitted")
                if pages_acc + need > self._alloc.n_free:
                    # free-list empty(ish): admission BACKPRESSURE, not a
                    # drop — the head request waits for pages to be freed
                    # by finishing slots (attrition)
                    break
                pages_acc += need
                n_adm += 1
            if n_adm == 0:
                return admitted
            k = 1 << (n_adm.bit_length() - 1)     # largest power of 2 <= n
            self._admit_chunk([self.queue.popleft() for _ in range(k)])
            admitted += k
        return admitted

    # -- emission / completion ----------------------------------------------

    def _finish_slot(self, slot: int) -> None:
        """Free a slot whose request just emitted its last token, stamp
        the request's finish time, and append it to the finish feed (sid +
        its realized hardness tally — what ``drain_finished`` hands the
        router)."""
        sid = self._sid[slot]
        self._state[slot] = _FREE
        self._sid[slot] = -1
        self._free.append(slot)
        if self._slot_pages[slot] > 0:
            # free-on-finish: the slot's pages go back on the free list and
            # its device block-table row is zeroed — a later flush clone of
            # this row must never let stage 2 append into recycled pages
            self._rows = self._alloc.free_slot(self._rows, slot,
                                               self._slot_pages[slot])
            self._slot_pages[slot] = 0
            self._slot_len[slot] = 0
        self.stats.record_finish(sid, self.clock.now())
        self._finished.append((sid, self._slot_hard[slot],
                               self._slot_dec[slot]))
        if self.events is not None:
            self.events.emit("finish", sid=sid,
                             n_hard=self._slot_hard[slot],
                             n_decisions=self._slot_dec[slot])

    def _advance_slot(self, slot: int) -> None:
        """One token emitted for this slot: finish when the budget is
        spent, else back to ACTIVE — the one completion rule both the easy
        (tick) and hard (bucket) paths share."""
        self._emitted[slot] += 1
        if self._emitted[slot] >= self._budget[slot]:
            self._finish_slot(slot)
        else:
            self._state[slot] = _ACTIVE

    def _emit(self, slot: int, token: int) -> None:
        self.results[self._sid[slot]].append(token)
        self._advance_slot(slot)

    # -- stage 2 dispatch ----------------------------------------------------

    def _dispatch_bucket(self, reason: str) -> None:
        """Pop one bucket off the ring and run stage 2 on it. ``reason``
        names the drain rule that asked for it (``full``, ``eager``,
        ``forced`` or ``backpressure``) on the dispatch span."""
        # the injection boundary sits BEFORE the pop — a retried dispatch
        # re-runs from an unmutated ring, so transient faults are safe to
        # absorb with faults.retry at every call site
        faults.fault_point("dispatch")
        popped = self.ring.pop()
        if popped is None:
            return
        bucket, ids, take = popped
        with observe.annotate("stage2_bucket_dispatch", reason=reason,
                              take=take):
            # the bucket's "cache" lane carries block-table rows (page
            # indices — the whole ring hop is index-sized). Flush lanes (id
            # -1) cloned a live slot's bt row out of the ring slab; sanitize
            # them to the NULL table + sentinel step so the shared pool is
            # never appended through a discarded row. The pool is donated
            # through s2_paged and comes back updated — no scatter-back
            # (pages are shared state, not slot rows).
            from repro.runtime.serve_loop import _sanitize_paged_bucket
            bt_safe, step_safe = _sanitize_paged_bucket(
                bucket["cache"], ids, bucket["step"], sentinel=self.max_len)
            logits, self._pool, *load = self.fns.s2_paged(
                bucket["h"], bt_safe, step_safe, self._pool)
        toks = _greedy_row(logits)
        # ex2 -> ex1 hop: greedy tokens come home to the slot lanes
        self._tok, self._pos, self._active_lane = _unpark_lanes(
            self._tok, self._pos, self._active_lane, self._start_lane,
            self._budget_lane, self.ex1.place_io(ids),
            self.ex1.place_io(toks))
        # host state transitions AND finish stamps NOW (the popped slots
        # are the FIFO head — no device sync needed; the next tick's sync
        # forces this bucket's compute within one window, so dispatch-time
        # stamps match the easy path's tick-time stamps); token VALUES land
        # at harvest, bounded by max_pending like the sync servers'
        # backlogs
        entries = []
        popped_slots = []
        for _ in range(take):
            slot = self._parked_fifo.popleft()
            sid = self._sid[slot]
            entries.append((sid, len(self.results[sid])))
            self.results[sid].append(None)       # filled at harvest
            popped_slots.append(slot)
        if self.events is not None:
            # bucket BEFORE the advance: a request finishing off this
            # bucket must close its stage-2 park span first
            self.events.emit("bucket",
                             sids=tuple(self._sid[s] for s in popped_slots),
                             take=take, capacity=self.sc.capacity)
        for slot in popped_slots:
            self._advance_slot(slot)
        self._pending.append((entries, toks, *load))
        while len(self._pending) > self.sc.max_pending:
            self._harvest_one()

    def _harvest_one(self) -> None:
        item = self._pending.popleft()
        entries, toks, *load = item
        # bounded wait: a bucket whose device result never resolves raises
        # HarvestTimeout instead of blocking np.asarray forever — the
        # entries go back on the pending deque so a caller that survives
        # the error (or a later retry) still harvests every token
        with observe.annotate("harvest_sync"):
            try:
                bounded_wait(toks, self.sc.harvest_timeout_s,
                             what=f"stage-2 bucket ({len(entries)} tokens)")
            except HarvestTimeout:
                self._pending.appendleft(item)
                raise
            toks_np, load_np = jax.device_get((toks, load))
        _record_load("bucket", load_np)
        for j, (sid, idx) in enumerate(entries):
            self.results[sid][idx] = int(toks_np[j])

    # -- the tick ------------------------------------------------------------

    def _use_fused(self) -> bool:
        """The single-launch fused tick applies when stage 1, the ring and
        the stage-2 store share one submesh (degenerate placement) and the
        ring row spec could be derived abstractly. A migration onto a
        disaggregated placement flips this off mid-serve (and back)."""
        return (self._ring_row_spec is not None
                and not self.placement.disaggregated)

    def tick_hlo(self) -> str:
        """The compiled program text of the pool tick this scheduler runs
        (fused or composed) at the pool's current shapes — which kernels
        the tick really contains. Needs a pool: call after an admission."""
        lanes = (self._tok, self._c1, self._pos, self._active_lane,
                 self._start_lane, self._budget_lane)
        kw = dict(s1=_tick_fn(self.fns.s1_raw),
                  backend=dispatch.kernel_backend())
        if self._use_fused():
            lowered = _pool_tick_fused.lower(
                *lanes, self.ring.ensure(self._ring_row_spec), self._rows,
                self.c_thr, **kw)
        else:
            lowered = _pool_tick.lower(*lanes, self.c_thr,
                                       mesh=self.ex1.mesh, **kw)
        return lowered.compile().as_text()

    def _tick(self) -> None:
        if self._use_fused():
            self._tick_fused()
        else:
            self._tick_composed()

    def _finish_tick(self, n_hard_dev, easy, hard, pred, conf, load):
        """The one per-tick host sync: n_hard (control flow) + the easy/
        hard masks, emitted tokens and confidences (results + the
        controller's reservoir feed) and the MoE load counters, fetched
        together. Emits easy tokens and feeds the controller; returns the
        host-side pieces the hard path needs."""
        with observe.annotate("finish_tick_sync"):
            n_hard, easy_np, hard_np, emit_np, conf_np, load_np = \
                jax.device_get((n_hard_dev, easy, hard, pred, conf, load))
        _record_load("tick", load_np)
        n_hard = int(n_hard)
        n_dec = int(easy_np.sum()) + n_hard
        self.stats.record_decisions(n_dec, n_hard)
        if self.events is not None:
            self.events.emit("tick", n_decisions=n_dec, n_hard=n_hard)
        if self.controller is not None:
            # SENSE: only live rows' confidences are real (free/parked rows
            # compute garbage that the masks discard)
            self.controller.on_tick(self, n_dec, n_hard,
                                    conf_np[easy_np | hard_np])
        for i in np.nonzero(easy_np)[0]:
            self._slot_dec[int(i)] += 1
            self._emit(int(i), int(emit_np[i]))
        return n_hard, hard_np

    def _park_hard(self, hard_np) -> None:
        parked = []
        for i in np.nonzero(hard_np)[0]:         # ascending = slab order
            self._slot_dec[int(i)] += 1
            self._slot_hard[int(i)] += 1
            self._state[int(i)] = _PARKED
            self._parked_fifo.append(int(i))
            parked.append(int(i))
        if self.events is not None and parked:
            # one batched event per tick (like "bucket"): the park feed is
            # hot-path, and per-row emits would dominate the event volume
            self.events.emit("park",
                             sids=tuple(self._sid[s] for s in parked),
                             slots=tuple(parked))

    def _tick_composed(self) -> None:
        with observe.annotate("pool_tick"):
            (self._c1, slab, slots, steps, n_hard_dev, easy, hard, pred,
             self._tok, self._pos, self._active_lane, conf,
             load) = _pool_tick(
                self._tok, self._c1, self._pos, self._active_lane,
                self._start_lane, self._budget_lane, self.c_thr,
                s1=_tick_fn(self.fns.s1_raw),
                backend=dispatch.kernel_backend(), mesh=self.ex1.mesh)
        n_hard, hard_np = self._finish_tick(n_hard_dev, easy, hard, pred,
                                            conf, load)
        if n_hard > 0:
            self._park_hard(hard_np)
            # ex1 -> ex2 hop: the id lane crosses first (the cache gather
            # runs ON ex2 — the store never leaves stage 2's submesh); the
            # hidden slab + step lane cross inside the enqueue's place_io
            slots2 = self.ex2.place_io(slots)
            cache_slab = _gather_rows(self._rows, slots2)
            # retried: the enqueue fault boundary sits before any ring
            # mutation, so a transient failure re-runs the whole enqueue
            with observe.annotate("ring_enqueue"):
                faults.retry(self.ring.enqueue,
                             {"h": slab, "cache": cache_slab, "step": steps},
                             slots2, n_hard, functools.partial(
                                 self._dispatch_bucket, "backpressure"),
                             what="ring-enqueue")

    def _tick_fused(self) -> None:
        ring_buf = self.ring.ensure(self._ring_row_spec)
        with observe.annotate("pool_tick_fused"):
            (self._c1, ring_buf, h, src, n_hard_dev, easy, hard, pred,
             self._tok, self._pos, self._active_lane,
             conf, load) = _pool_tick_fused(
                self._tok, self._c1, self._pos, self._active_lane,
                self._start_lane, self._budget_lane, ring_buf, self._rows,
                self.c_thr, s1=_tick_fn(self.fns.s1_raw),
                backend=dispatch.kernel_backend())
        self.ring.put_buf(ring_buf)
        n_hard, hard_np = self._finish_tick(n_hard_dev, easy, hard, pred,
                                            conf, load)
        if n_hard > 0:
            # the enqueue happened IN the tick; its fault boundary fires
            # here (same visit cadence as the composed path — once per
            # hard tick). A transient fault is absorbed by the retry with
            # the device ring already consistent; only the host mirror
            # below was still pending.
            faults.retry(faults.fault_point, "enqueue", what="ring-enqueue")
            n_enq = min(n_hard, self.ring.size - self.ring.count)
            self.ring.note_enqueued(n_enq)
            self._park_hard(hard_np)
            if n_enq < n_hard:
                # overflow: the ring filled mid-batch. Re-materialize the
                # still-pending slab rows from src (hard rows' pos did not
                # advance, so the live lanes are still decision-time
                # steps) and push them through the composed backpressure
                # chain — stall/drain ordering and n_stalls match the
                # composed path exactly.
                slab = _gather_rows({"h": h, "cache": self._rows,
                                     "step": self._pos}, src)
                self.ring.enqueue(slab, src, n_hard, functools.partial(
                    self._dispatch_bucket, "backpressure"), off=n_enq,
                    fire_fault=False)

    # -- the loop ------------------------------------------------------------

    def _n_state(self, state: int) -> int:
        return sum(1 for s in self._state if s == state)

    def _refresh_page_stats(self) -> None:
        """Fold the allocator's view + the host token tallies into the v3
        stats fields (once per scheduler iteration — the gauges are cheap
        host arithmetic)."""
        self.stats.cache_pages_in_use = self._alloc.n_in_use
        self.stats.live_tokens = sum(
            self._slot_len[i] + self._emitted[i] - 1
            for i in range(self.n_slots) if self._state[i] != _FREE)

    # -- ReplicaHandle introspection (serve_api.py) --------------------------

    @property
    def n_busy(self) -> int:
        """Slots holding an in-flight request (ACTIVE or PARKED) — the
        live-occupancy half of the router's load signal."""
        return self.n_slots - len(self._free)

    @property
    def queue_len(self) -> int:
        """Unadmitted requests awaiting a slot — the queue-depth half."""
        return len(self.queue)

    def next_arrival(self) -> Optional[float]:
        return self.queue.next_arrival()

    def revoke_queued(self, sample_ids=None) -> List[Request]:
        """Remove and return UNADMITTED queued requests (None = all) —
        the fleet preemption / degrade-redistribution primitive. Admitted
        requests are untouched, so a revoked request has never emitted a
        token and re-queueing it elsewhere preserves its stream."""
        return self.queue.revoke(sample_ids)

    def drain_finished(self) -> List:
        """Pop the per-request finish feed accumulated since the last
        call: (sample_id, n_hard_decisions, n_decisions) per finished
        request — the realized per-request hardness the router folds into
        its tenant difficulty estimates."""
        out = list(self._finished)
        self._finished.clear()
        return out

    def step(self) -> str:
        """ONE scheduler iteration — the replica state machine the fleet
        router (and ``drain``) drives. Admits what is admissible, then
        either ticks the pool (easy slots advance, full buckets dispatch
        eagerly, partial buckets under the starvation policy) or forces a
        partial bucket when every busy slot is parked — the HAPI-style
        staged policy, one iteration at a time.

        Returns ``"busy"`` (progressed), ``"waiting"`` (queued work whose
        arrival_time is still in the future — the caller owns the clock
        and should advance it toward ``next_arrival()``), or ``"idle"``
        (queue and pool fully drained; deferred token values may still be
        pending — ``drain`` harvests them)."""
        with observe.annotate("sched_step"):
            self._maybe_migrate()            # discrete re-plan points only
            self._maybe_apply_capacity()
            admitted = self._try_admit()
            self._refresh_page_stats()
            if self._n_state(_ACTIVE) > 0:
                self._tick()
                while self.ring.count >= self.sc.capacity:
                    faults.retry(self._dispatch_bucket, "full",
                                 what="full-drain")
                # starved pool: partial buckets beat idle stage-1 width
                while (self.ring.count > 0
                       and self._n_state(_ACTIVE) < self.eager_drain_below):
                    faults.retry(self._dispatch_bucket, "eager",
                                 what="eager-drain")
                return "busy"
            if self.ring.count > 0:
                # forced partial: all parked
                faults.retry(self._dispatch_bucket, "forced",
                             what="forced-drain")
                return "busy"
            if admitted:                     # prefill-only requests finish
                return "busy"                # at admission: still progress
            if self.queue:
                if not self._free:           # full pool, all parked, empty
                    raise AssertionError("scheduler wedged: parked slots "
                                         "with an empty ring")
                return "waiting"
            return "idle"

    def drain(self) -> Dict[int, List[int]]:
        """Drive ``step`` until the queue and every slot drain (advancing
        the clock over idle gaps), then harvest every deferred token
        value. Idempotent: a drained scheduler returns its results."""
        while True:
            state = self.step()
            if state == "waiting":
                self.clock.advance_to(self.queue.next_arrival())
            elif state == "idle":
                break
        while self._pending:                 # final harvest: fill the
            self._harvest_one()              # deferred token values
        assert self._n_state(_FREE) == self.n_slots, \
            "scheduler drained with busy slots"
        return self.results

    def run(self) -> Dict[int, List[int]]:
        """Drive the pool until the queue and every slot drain — the
        standalone entry point (``drain`` under its original name)."""
        return self.drain()


def poisson_arrivals(n: int, rate: float, seed: int = 0) -> np.ndarray:
    """Cumulative Poisson-process arrival times for ``n`` requests at
    ``rate`` (requests/second); ``rate`` <= 0 or inf means all at t=0."""
    if not np.isfinite(rate) or rate <= 0:
        return np.zeros(n)
    gaps = np.random.default_rng(seed).exponential(1.0 / rate, size=n)
    return np.cumsum(gaps)
