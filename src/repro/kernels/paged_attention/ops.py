"""Back-compat wrapper for the paged-cache gather+append primitive.

Delegates to the dispatch layer (kernels/dispatch.py). ``use_pallas=True``
exercises the Pallas kernel body (interpreted on CPU, compiled on TPU);
``use_pallas=False`` runs the pure-jnp oracle. The serving hot path should
call ``dispatch.paged_gather_append_op`` (or the traceable
``dispatch.paged_gather_append`` inside an enclosing jit) instead.
"""
from __future__ import annotations

from repro.kernels import dispatch


def paged_gather_append_op(a_pool, b_pool, a_new, b_new, block_tables, pos,
                           *, use_pallas: bool = True, donate: bool = True):
    """a_pool/b_pool: (P, page, *F); a_new/b_new: (B, *F); block_tables:
    (B, M) i32; pos: (B,) i32. Returns (gathered_a (B, M, page, *Fa),
    gathered_b, a_pool', b_pool')."""
    backend = dispatch.kernel_body_backend() if use_pallas else "ref"
    return dispatch.paged_gather_append_op(a_pool, b_pool, a_new, b_new,
                                           block_tables, pos,
                                           backend=backend, donate=donate)
