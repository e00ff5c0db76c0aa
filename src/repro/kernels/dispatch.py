"""Kernel dispatch: one policy deciding which implementation serves a call.

The serving runtime (runtime/serve_loop.py) and the one-shot pipeline
(core/early_exit.serve_batch) route every exit-decision and conditional-
buffer call through this module instead of picking an implementation at the
call site. Three backends exist per kernel:

  pallas     — the compiled Pallas TPU kernel (kernel.py). Only exists on
               a TPU backend; requesting it elsewhere raises, so a run that
               asked for the chip's kernels never silently measures the
               interpreter instead.
  interpret  — the same Pallas kernel body run under the Pallas interpreter.
               Validates the kernel on CPU but is orders of magnitude slower
               than XLA; used by the parity tests, never by the hot path.
  ref        — the pure-jnp oracle (ref.py). Fast under XLA on CPU/GPU and
               the semantics contract the kernels are tested against.

Resolution order: explicit ``backend=`` argument > ``set_backend()`` >
``REPRO_KERNEL_BACKEND`` env var > ``auto``. ``auto`` picks ``pallas`` on
TPU and ``ref`` everywhere else — i.e. the hot path always runs compiled
code, and the interpreter is something you must ask for by name.
"""
from __future__ import annotations

import functools
import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import compat
from repro.kernels.exit_decision.kernel import exit_decision_pallas
from repro.kernels.exit_decision.ref import exit_decision_ref
from repro.kernels.fused_dispatch.kernel import fused_dispatch_pallas
from repro.kernels.fused_dispatch.ref import fused_dispatch_ref
from repro.kernels.gather_compact.kernel import gather_compact_pallas
from repro.kernels.gather_compact.ref import gather_compact_ref
from repro.kernels.paged_attention.kernel import paged_gather_append_pallas
from repro.kernels.paged_attention.ref import paged_gather_append_ref

BACKENDS = ("auto", "pallas", "interpret", "ref")
_ENV_VAR = "REPRO_KERNEL_BACKEND"
_override: Optional[str] = None
_resolve_cache: dict = {}
_n_resolutions = 0


def n_backend_resolutions() -> int:
    """Lifetime count of ``kernel_backend`` memo MISSES (fresh platform
    probes + validations). A steadily climbing value under a steady-state
    server means something is thrashing the memo (e.g. a test sweeping
    ``REPRO_FAULT_LOG``-style env state or ``set_backend`` churn) — the
    observability plane exports it as
    ``repro_backend_resolutions_total``."""
    return _n_resolutions


@functools.lru_cache(maxsize=1)
def _on_tpu() -> bool:
    # jax.default_backend() initializes the platform — not free, and the
    # answer cannot change within a process, so ask exactly once.
    return jax.default_backend() == "tpu"


def set_backend(name: Optional[str]) -> None:
    """Process-wide backend override (None restores auto/env resolution)."""
    global _override
    if name is not None and name not in BACKENDS:
        raise ValueError(f"unknown kernel backend {name!r}; "
                         f"expected one of {BACKENDS}")
    _override = name
    _resolve_cache.clear()


def kernel_backend(backend: Optional[str] = None) -> str:
    """Resolve to a concrete backend: 'pallas' | 'interpret' | 'ref'.

    Memoized on (explicit arg, override, env var): the env var stays a live
    input — tests monkeypatch it — but the platform probe and validation run
    once per distinct key instead of on every hot-loop op call."""
    env = os.environ.get(_ENV_VAR)
    key = (backend, _override, env)
    hit = _resolve_cache.get(key)
    if hit is not None:
        return hit
    global _n_resolutions
    _n_resolutions += 1
    req = backend or _override or env or "auto"
    if req not in BACKENDS:
        raise ValueError(f"unknown kernel backend {req!r}; "
                         f"expected one of {BACKENDS}")
    if req == "auto":
        res = "pallas" if _on_tpu() else "ref"
    elif req == "pallas" and not _on_tpu():
        raise RuntimeError(
            f"kernel backend 'pallas' needs a TPU, but JAX's default backend "
            f"is {jax.default_backend()!r}; ask for 'interpret' to run the "
            f"kernel bodies under the Pallas interpreter")
    else:
        res = req
    _resolve_cache[key] = res
    return res


def kernel_body_backend() -> str:
    """The backend that runs the Pallas kernel BODIES here: compiled on
    TPU, interpreted elsewhere — what the per-kernel ``ops.py`` parity
    wrappers ask for with ``use_pallas=True``."""
    return "pallas" if _on_tpu() else "interpret"


# ---------------------------------------------------------------------------
# dispatched ops (the serving hot path calls these)
# ---------------------------------------------------------------------------

def _on_mesh(kernel, mesh, *args):
    """Call a Pallas kernel inside a program partitioned over ``mesh`` (a
    multi-device stage submesh). The TPU compiler cannot partition a Mosaic
    kernel, so every device runs the whole call on replicated operands."""
    if mesh is None or mesh.size == 1:
        return kernel(*args)
    return compat.shard_map(kernel, mesh=mesh, in_specs=P(),
                            out_specs=P())(*args)


@functools.partial(jax.jit, static_argnames=("backend", "mesh"))
def _exit_decision(logits, c_thr, backend: str, mesh=None):
    if backend == "ref":
        return exit_decision_ref(logits, c_thr)
    return _on_mesh(functools.partial(exit_decision_pallas,
                                      interpret=(backend == "interpret")),
                    mesh, logits, c_thr)


def exit_decision_op(logits: jnp.ndarray, c_thr, *,
                     backend: Optional[str] = None, mesh=None
                     ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Fused exit decision (Eq. 4). logits: (..., V) -> (exit bool, pred
    i32, conf f32), each shaped (...,). One streamed read of the logits;
    no materialized softmax on any backend. ``mesh``: the stage submesh
    the enclosing program spans, if any."""
    lead = logits.shape[:-1]
    x = logits.reshape((-1, logits.shape[-1]))
    e, p, c = _exit_decision(x, c_thr, kernel_backend(backend), mesh)
    return e.reshape(lead), p.reshape(lead), c.reshape(lead)


@functools.partial(jax.jit, static_argnames=("capacity", "backend", "mesh"))
def _gather_compact(x, hard_mask, capacity: int, backend: str, mesh=None):
    if backend == "ref":
        return gather_compact_ref(x, hard_mask, capacity)
    return _on_mesh(functools.partial(gather_compact_pallas,
                                      capacity=capacity,
                                      interpret=(backend == "interpret")),
                    mesh, x, hard_mask)


def gather_compact_op(x: jnp.ndarray, hard_mask: jnp.ndarray, capacity: int,
                      *, backend: Optional[str] = None, mesh=None
                      ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Conditional-buffer compaction. x: (B, ...); hard_mask: (B,) bool.
    Returns (slab (capacity, ...), slab_ids (capacity,) int32 with -1 flush
    slots, n_hard ()). ``mesh`` as for ``exit_decision_op``."""
    B = x.shape[0]
    feat = x.shape[1:]
    xf = x.reshape(B, -1)
    slab, ids, nh = _gather_compact(xf, hard_mask, capacity,
                                    kernel_backend(backend), mesh)
    return slab.reshape((capacity,) + feat), ids, nh


def paged_gather_append(a_pool, b_pool, a_new, b_new, block_tables, pos, *,
                        backend: str):
    """Traceable paged-cache gather+append body for use INSIDE an enclosing
    jit (the paged decode step calls this per attention layer). ``backend``
    must already be resolved (call ``kernel_backend`` outside the trace).

    a_pool/b_pool: (P, page, *F) page pools (page 0 = null, all-zeros);
    a_new/b_new: (B, *F) new-token rows; block_tables: (B, M) i32; pos:
    (B,) i32 linear write positions (>= M*page skips the append). Returns
    (gathered_a (B, M, page, *Fa), gathered_b, a_pool', b_pool') — the
    gathered slabs reshaped to (B, M*page, *F) are exactly the dense cache
    rows, appended token included. Feature dims are flattened for the
    kernel and restored here, so every backend is bitwise-identical."""
    fa, fb = a_pool.shape[2:], b_pool.shape[2:]
    n_pages, page = a_pool.shape[:2]
    B, M = block_tables.shape
    with jax.named_scope("paged_gather_append"):
        if backend == "ref":
            ga, gb, ap, bp = paged_gather_append_ref(
                a_pool, b_pool, a_new, b_new, block_tables, pos)
            return ga, gb, ap, bp
        ga, gb, ap, bp = paged_gather_append_pallas(
            a_pool.reshape(n_pages, page, -1),
            b_pool.reshape(n_pages, page, -1),
            a_new.reshape(B, -1), b_new.reshape(B, -1), block_tables, pos,
            interpret=(backend == "interpret"))
        return (ga.reshape((B, M, page) + fa), gb.reshape((B, M, page) + fb),
                ap.reshape(a_pool.shape), bp.reshape(b_pool.shape))


@functools.partial(jax.jit, static_argnames=("backend",),
                   donate_argnums=(0, 1))
def _paged_gather_append_donated(a_pool, b_pool, a_new, b_new, block_tables,
                                 pos, backend: str):
    return paged_gather_append(a_pool, b_pool, a_new, b_new, block_tables,
                               pos, backend=backend)


@functools.partial(jax.jit, static_argnames=("backend",))
def _paged_gather_append_copy(a_pool, b_pool, a_new, b_new, block_tables,
                              pos, backend: str):
    return paged_gather_append(a_pool, b_pool, a_new, b_new, block_tables,
                               pos, backend=backend)


def paged_gather_append_op(a_pool, b_pool, a_new, b_new, block_tables, pos,
                           *, backend: Optional[str] = None,
                           donate: bool = True):
    """Standalone jitted paged gather+append. By default the pools are
    DONATED (the appended pools reuse their buffers); ``donate=False``
    keeps the inputs alive for paged-vs-dense comparisons."""
    fn = _paged_gather_append_donated if donate else _paged_gather_append_copy
    return fn(a_pool, b_pool, a_new, b_new, block_tables, pos,
              backend=kernel_backend(backend))


def fused_dispatch(logits, active, sample_ids, payload, ring, c_thr, *,
                   backend: str):
    """Traceable fused dispatch body (decision + compaction + ring enqueue
    in one pass) for use INSIDE an enclosing jit — the pool tick calls this
    so the whole decode step stays one program. ``backend`` must already be
    resolved (call ``kernel_backend`` outside the trace).

    logits (B, V); active (B,) bool or None; sample_ids (B,) i32; payload
    pytree of (B, *row) leaves matching ring['data']. Returns
    (ring', exit_mask, pred, conf, src, n_hard); rows past the ring's free
    space are NOT written (caller handles overflow via src)."""
    with jax.named_scope("fused_dispatch"):
        if backend == "ref":
            return fused_dispatch_ref(logits, active, sample_ids, payload,
                                      ring, c_thr)
        return fused_dispatch_pallas(logits, active, sample_ids, payload,
                                     ring, c_thr,
                                     interpret=(backend == "interpret"))


@functools.partial(jax.jit, static_argnames=("backend",),
                   donate_argnums=(4,))
def _fused_dispatch_donated(logits, active, sample_ids, payload, ring,
                            c_thr, backend: str):
    return fused_dispatch(logits, active, sample_ids, payload, ring, c_thr,
                          backend=backend)


@functools.partial(jax.jit, static_argnames=("backend",))
def _fused_dispatch_copy(logits, active, sample_ids, payload, ring, c_thr,
                         backend: str):
    return fused_dispatch(logits, active, sample_ids, payload, ring, c_thr,
                          backend=backend)


def fused_dispatch_op(logits: jnp.ndarray, active: Optional[jnp.ndarray],
                      sample_ids: jnp.ndarray, payload, ring: dict, c_thr,
                      *, backend: Optional[str] = None, donate: bool = True):
    """Standalone jitted fused dispatch. By default the ring argument is
    DONATED (its buffers are reused for the output ring — pass a ring you
    no longer read); ``donate=False`` keeps the input ring alive for
    composed-vs-fused comparisons."""
    fn = _fused_dispatch_donated if donate else _fused_dispatch_copy
    return fn(logits, active, sample_ids, payload, ring, c_thr,
              backend=kernel_backend(backend))
