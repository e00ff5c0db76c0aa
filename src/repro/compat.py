"""Thin wrappers over jax APIs whose spelling the codebase pins in one
place: ``shard_map`` (replication check off by default) and the compiled
program's cost analysis as one flat dict."""
from __future__ import annotations

from jax import shard_map as _shard_map


def shard_map(f, *, mesh, in_specs, out_specs, check_vma: bool = False):
    """``jax.shard_map`` with the replication check off unless asked for."""
    return _shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                      check_vma=check_vma)


def xla_cost_analysis(compiled) -> dict:
    """``compiled.cost_analysis()`` as one dict."""
    return compiled.cost_analysis() or {}
