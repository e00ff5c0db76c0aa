"""Pallas TPU kernel: fused dispatch — Exit Decision + Conditional Buffer
+ ring enqueue in one HBM pass over each operand.

Composition of two streamed kernels plus O(B + size) integer cursor math:

  1. ``exit_decision_pallas`` reads the stage-1 logits ONCE and emits
     (exit_mask, pred, conf) — the Eq. (4) online reduction.
  2. The compaction permutation and the ring write-cursor map are a few
     prefix sums over (B,)/(size,) int vectors — lowered inline by XLA,
     never worth a kernel of their own.
  3. ``_scatter_merge_kernel`` per payload leaf: streams the leaf's ring
     slab feature-tile by feature-tile, overwriting exactly the slots the
     cursor map claims with rows gathered from the payload. The ring slab
     is aliased input→output (``input_output_aliases``), so the slab is
     read+written in place in one pass and the easy rows are never copied —
     the Conditional Buffer's address-invalidation trick (§III-C.2), with
     the buffer being the inter-stage ring itself rather than a slab that
     XLA would scatter into the ring afterwards.

The slot→source map ``src_map`` (size,) is computed by XLA and handed to
the merge kernel as a scalar-prefetch operand: ring slot ``r`` takes
payload row ``src[(r - head - count) % size]`` iff that lane is below
``n_enq = min(n_hard, free)``, else keeps its current bytes. Each slot is
claimed at most once because ``n_enq <= size``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.exit_decision.kernel import exit_decision_pallas
from repro.kernels.fused_dispatch.ref import compact_src


def _scatter_merge_kernel(srcmap_ref, x_ref, ring_ref, out_ref):
    r = pl.program_id(0)
    claimed = srcmap_ref[r] >= 0                          # scalar SMEM read
    out_ref[...] = jnp.where(claimed, x_ref[...].astype(out_ref.dtype),
                             ring_ref[...])


@functools.partial(jax.jit, static_argnames=("block_f", "interpret"))
def _scatter_merge(src_map, x, ring_leaf, *, block_f: int = 2048,
                   interpret: bool = False):
    """x: (B, F) payload leaf; ring_leaf: (size, F). Writes row
    ``x[src_map[r]]`` into slot r where ``src_map[r] >= 0``; other slots
    keep their bytes. Ring slab aliased in place.

    ``src_map`` is a SCALAR-PREFETCH operand: grid cell (r, j) DMAs payload
    row ``src_map[r]`` (row 0 for unclaimed slots — consecutive cells
    re-use the fetched block) next to ring slot r's tile j, and the body
    reads the one scalar ``src_map[r]``. Rows move as ``(1, 1, bf)`` blocks
    over a unit middle axis, which the TPU's (8, 128) tiling rule accepts
    for any ring size or batch."""
    size, F = ring_leaf.shape
    b = x.shape[0]
    bf = min(block_f, F)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,                      # src_map -> SMEM
        grid=(size, pl.cdiv(F, bf)),
        in_specs=[
            pl.BlockSpec((1, 1, bf),
                         lambda r, j, sm: (jnp.maximum(sm[r], 0), 0, j)),
            pl.BlockSpec((1, 1, bf), lambda r, j, sm: (r, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, 1, bf), lambda r, j, sm: (r, 0, j)),
    )
    out = pl.pallas_call(
        _scatter_merge_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((size, 1, F), ring_leaf.dtype),
        # flat inputs = (src_map, x, ring): the ring slab aliases the output
        input_output_aliases={2: 0},
        interpret=interpret,
    )(src_map, x.reshape(b, 1, F), ring_leaf.reshape(size, 1, F))
    return out.reshape(size, F)


def fused_dispatch_pallas(logits, active, sample_ids, payload, ring, c_thr,
                          *, interpret: bool = False):
    """Same contract as ``fused_dispatch_ref`` (see ref.py module doc);
    kernel-body backend. Traceable — jit at the dispatch layer."""
    exit_mask, pred, conf = exit_decision_pallas(logits, c_thr,
                                                 interpret=interpret)
    hard = ~exit_mask if active is None else active & ~exit_mask
    src, n_hard = compact_src(hard)

    b = src.shape[0]
    size = ring["ids"].shape[0]
    head, count = ring["head"], ring["count"]
    free = jnp.int32(size) - count
    n_enq = jnp.minimum(n_hard, free).astype(jnp.int32)
    # slot -> payload row map: invert lane = (r - head - count) % size
    slots = jnp.arange(size, dtype=jnp.int32)
    lane = (slots - head - count) % size
    src_map = jnp.where(
        lane < n_enq,
        jnp.take(src, jnp.minimum(lane, b - 1)), -1).astype(jnp.int32)

    def merge(d, p):
        feat = d.shape[1:]
        F = math.prod(feat)
        if F == 0:                       # degenerate leaf: nothing to move
            return d
        out = _scatter_merge(src_map, p.reshape(b, F), d.reshape(size, F),
                             interpret=interpret)
        return out.reshape((size,) + feat)

    with jax.named_scope("fused_dispatch_scatter_merge"):
        data = jax.tree.map(merge, ring["data"], payload)
        ids = merge(ring["ids"][:, None], sample_ids[:, None])[:, 0]
    new_ring = {"data": data, "ids": ids, "head": head, "count": count + n_enq}
    return new_ring, exit_mask, pred, conf, src, n_hard
