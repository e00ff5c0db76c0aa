"""Pallas TPU kernel: stream compaction — the Conditional Buffer (§III-C.2).

The FPGA conditional buffer drops an exiting sample's feature map by
invalidating its addresses in one cycle. The TPU analogue: a stable
prefix-sum partition over the (B,) mask — O(B) integer math that XLA lowers
inline (``compact_src``, shared bitwise with the fused dispatch kernel) —
yields the take vector, which rides into the kernel as a SCALAR-PREFETCH
operand (``pltpu.PrefetchScalarGridSpec``). The x BlockSpec's index map
looks up ``take[c]``, so grid cell (c, j) DMAs exactly source row
``take[c]``'s feature tile j into slab row c: x is read only where a row
survives and only the compacted slab is written back, so the stage-2 input
slab never round-trips through host memory (the paper keeps the decision
on-chip for exactly this reason).

SMEM is only ever read as scalars (the index maps), and rows move as
``(1, 1, bf)`` blocks over a unit middle axis, so every block obeys the
TPU's (8, 128) tiling rule whatever the batch size.

Grid: (C, F / bf).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.fused_dispatch.ref import compact_src


def _copy_row_kernel(take_ref, x_ref, slab_ref):
    slab_ref[...] = x_ref[...]


def gather_rows_pallas(x: jnp.ndarray, take: jnp.ndarray, *,
                       block_f: int = 2048, interpret: bool = False):
    """x: (B, F); take: (C,) int32 row indices in [0, B). Returns
    ``x[take]`` (C, F), one scalar-prefetched row DMA per (row, tile)."""
    B, F = x.shape
    C = take.shape[0]
    bf = min(block_f, F)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,                      # take -> SMEM
        grid=(C, pl.cdiv(F, bf)),
        in_specs=[pl.BlockSpec((1, 1, bf), lambda c, j, t: (t[c], 0, j))],
        out_specs=pl.BlockSpec((1, 1, bf), lambda c, j, t: (c, 0, j)),
    )
    slab = pl.pallas_call(
        _copy_row_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((C, 1, F), x.dtype),
        interpret=interpret,
    )(take, x.reshape(B, 1, F))
    return slab.reshape(C, F)


@functools.partial(jax.jit, static_argnames=("capacity", "block_f",
                                             "interpret"))
def gather_compact_pallas(x: jnp.ndarray, hard_mask: jnp.ndarray,
                          capacity: int, *, block_f: int = 2048,
                          interpret: bool = False):
    """x: (B, F); hard_mask: (B,) bool. Returns (slab (C, F), slab_ids (C,),
    n_hard ())."""
    B = x.shape[0]
    src, n_hard = compact_src(hard_mask)             # (B,), -1 past n_hard
    if capacity <= B:
        ids = src[:capacity]
    else:
        ids = jnp.pad(src, (0, capacity - B), constant_values=-1)
    take = jnp.maximum(ids, 0)                       # flush slots -> row 0
    slab = gather_rows_pallas(x, take, block_f=block_f, interpret=interpret)
    return slab, ids, n_hard
