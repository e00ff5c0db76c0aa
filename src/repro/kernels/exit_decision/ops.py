"""Back-compat wrapper for the Exit Decision kernel.

Delegates to the dispatch layer (kernels/dispatch.py). ``use_pallas=True``
exercises the Pallas kernel body (interpreted on CPU, compiled on TPU) —
this is what the parity sweeps in tests/ rely on; ``use_pallas=False`` runs
the pure-jnp oracle. The serving hot path should call
``dispatch.exit_decision_op`` instead, whose ``auto`` policy never pays the
interpreter tax off-TPU.
"""
from __future__ import annotations

from typing import Tuple

import jax.numpy as jnp

from repro.kernels import dispatch


def exit_decision_op(logits: jnp.ndarray, c_thr, *, use_pallas: bool = True
                     ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Fused exit decision. logits: (..., V) -> (exit bool, pred i32,
    conf f32), each shaped (...,)."""
    backend = dispatch.kernel_body_backend() if use_pallas else "ref"
    return dispatch.exit_decision_op(logits, c_thr, backend=backend)
