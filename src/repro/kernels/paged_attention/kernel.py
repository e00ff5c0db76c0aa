"""Pallas TPU kernel: paged KV-cache gather + tail-page append, one launch.

The block table and the per-row write positions ride as SCALAR-PREFETCH
operands (``pltpu.PrefetchScalarGridSpec``): they land in SMEM before the
body runs, so the pool BlockSpec's index map can look up ``bt[b, p]`` and
DMA exactly the pages each grid cell touches — the canonical Pallas
block-table paged-attention mechanism. Grid is ``(B, max_pages)``: cell
(b, p) streams pool page ``bt[b, p]`` through VMEM once, merges the row's
new-token features in-register when (b, p) is the row's tail cell, and
writes the merged page to BOTH the gathered output (``(B, max_pages, page,
F)`` — reshaped, the dense cache row) and back to the pool in place
(``input_output_aliases``: the pool never copies).

Null-page discipline: page 0 is shared by every unused block-table entry.
Its cells never satisfy the append predicate (``bt[b,p] > 0`` fails), so
each visit rewrites the identical all-zero bytes — the non-injective output
index map is deterministic by construction. Pool pages referenced by no
table entry are never visited and keep their bytes through the alias.

Both pools (K+V, or MLA latent+rope) move in the same launch; feature dims
are pre-flattened by the dispatch layer to ``(P, page, F)``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _paged_gather_append_kernel(bt_ref, pos_ref, ap_ref, bp_ref, an_ref,
                                bn_ref, ga_ref, gb_ref, apo_ref, bpo_ref, *,
                                page: int, max_pages: int):
    b = pl.program_id(0)
    p = pl.program_id(1)
    pos = pos_ref[b]
    # tail cell: this grid cell's page holds the row's write position, the
    # position is in range (not the parked/flush sentinel), and the page is
    # a real allocation (never append into the shared null page 0)
    tail = ((pos // page == p) & (pos < max_pages * page)
            & (bt_ref[b, p] > 0))
    rows = jax.lax.broadcasted_iota(jnp.int32, (page, 1), 0)
    write = tail & (rows == pos % page)                  # (page, 1)
    a_merged = jnp.where(write, an_ref[0], ap_ref[0])    # an_ref[0]: (1, F)
    b_merged = jnp.where(write, bn_ref[0], bp_ref[0])
    ga_ref[0, 0] = a_merged
    gb_ref[0, 0] = b_merged
    apo_ref[0] = a_merged
    bpo_ref[0] = b_merged


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_gather_append_pallas(a_pool: jnp.ndarray, b_pool: jnp.ndarray,
                               a_new: jnp.ndarray, b_new: jnp.ndarray,
                               block_tables: jnp.ndarray, pos: jnp.ndarray,
                               *, interpret: bool = False):
    """a_pool: (P, page, Fa); b_pool: (P, page, Fb); a_new: (B, Fa);
    b_new: (B, Fb); block_tables: (B, M) i32; pos: (B,) i32. Returns
    (gathered_a (B, M, page, Fa), gathered_b, a_pool', b_pool')."""
    n_pages, page, fa = a_pool.shape
    fb = b_pool.shape[-1]
    B, M = block_tables.shape

    kernel = functools.partial(_paged_gather_append_kernel, page=page,
                               max_pages=M)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                 # block_tables, pos -> SMEM
        grid=(B, M),
        in_specs=[
            pl.BlockSpec((1, page, fa), lambda b, p, bt, pos: (bt[b, p], 0, 0)),
            pl.BlockSpec((1, page, fb), lambda b, p, bt, pos: (bt[b, p], 0, 0)),
            # new-token rows ride a unit middle axis: a (1, 1, F) block
            # is (8, 128)-legal for any batch size
            pl.BlockSpec((1, 1, fa), lambda b, p, bt, pos: (b, 0, 0)),
            pl.BlockSpec((1, 1, fb), lambda b, p, bt, pos: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, page, fa),
                         lambda b, p, bt, pos: (b, p, 0, 0)),
            pl.BlockSpec((1, 1, page, fb),
                         lambda b, p, bt, pos: (b, p, 0, 0)),
            pl.BlockSpec((1, page, fa), lambda b, p, bt, pos: (bt[b, p], 0, 0)),
            pl.BlockSpec((1, page, fb), lambda b, p, bt, pos: (bt[b, p], 0, 0)),
        ],
    )
    with jax.named_scope("paged_gather_append_kernel"):
        return pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=[
                jax.ShapeDtypeStruct((B, M, page, fa), a_pool.dtype),
                jax.ShapeDtypeStruct((B, M, page, fb), b_pool.dtype),
                jax.ShapeDtypeStruct(a_pool.shape, a_pool.dtype),
                jax.ShapeDtypeStruct(b_pool.shape, b_pool.dtype),
            ],
            # flat pallas_call inputs = (bt, pos, a_pool, b_pool, a_new,
            # b_new); the pools alias the in-place pool outputs (2 and 3)
            input_output_aliases={2: 2, 3: 3},
            interpret=interpret,
        )(block_tables, pos, a_pool, b_pool, a_new.reshape(B, 1, fa),
          b_new.reshape(B, 1, fb))
