"""Back-compat wrapper for the fused dispatch kernel.

Delegates to the dispatch layer (kernels/dispatch.py). ``use_pallas=True``
exercises the Pallas kernel body (interpreted on CPU, compiled on TPU);
``use_pallas=False`` runs the pure-jnp oracle. The serving hot path should
call ``dispatch.fused_dispatch_op`` instead.
"""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from repro.kernels import dispatch


def fused_dispatch_op(logits: jnp.ndarray, active: Optional[jnp.ndarray],
                      sample_ids: jnp.ndarray, payload, ring: dict, c_thr,
                      *, use_pallas: bool = True):
    """See ``fused_dispatch_ref`` for the contract. Returns
    (ring', exit_mask, pred, conf, src, n_hard)."""
    backend = dispatch.kernel_body_backend() if use_pallas else "ref"
    return dispatch.fused_dispatch_op(logits, active, sample_ids, payload,
                                      ring, c_thr, backend=backend)
