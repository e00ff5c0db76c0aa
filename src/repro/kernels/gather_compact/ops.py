"""Back-compat wrapper for stream compaction (Conditional Buffer).

Delegates to the dispatch layer (kernels/dispatch.py). ``use_pallas=True``
exercises the Pallas kernel body (interpreted on CPU, compiled on TPU);
``use_pallas=False`` runs the pure-jnp oracle. The serving hot path should
call ``dispatch.gather_compact_op`` instead.
"""
from __future__ import annotations

from typing import Tuple

import jax.numpy as jnp

from repro.kernels import dispatch


def gather_compact_op(x: jnp.ndarray, hard_mask: jnp.ndarray, capacity: int,
                      *, use_pallas: bool = True
                      ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """x: (B, ...); hard_mask: (B,). Returns (slab (C, ...), slab_ids (C,),
    n_hard ())."""
    backend = dispatch.kernel_body_backend() if use_pallas else "ref"
    return dispatch.gather_compact_op(x, hard_mask, capacity,
                                      backend=backend)
