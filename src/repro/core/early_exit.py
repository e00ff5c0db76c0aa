"""EarlyExitModel: stage partitioning + exit heads for LM backbones.

Wraps any registry backbone (models/transformer.py) with depth early exits
(ATHEENA's CDFG form, Fig. 3): stage 1 = embed + layers [0, k) + exit head,
stage 2 = layers [k, N) + final head. The exit head is RMSNorm + tied
unembedding (the LM analogue of BranchyNet's lightweight exit classifier).

The staged entry points mirror the hardware: `stage1_*` produce intermediate
hidden states + exit logits; the exit decision + conditional buffer
(core/conditional.py) filter samples; `stage2_*` finish the hard ones.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import conditional as cond
from repro.kernels import dispatch
from repro.models import transformer as T
from repro.models.config import ArchConfig
from repro.models.layers import init_rmsnorm, rmsnorm, unembed


@dataclass(frozen=True)
class EarlyExitSpec:
    exit_layer: int            # stage boundary k (superblock-aligned)
    c_thr: float = 0.9         # Eq. (2) confidence threshold
    loss_weights: Tuple[float, float] = (0.3, 1.0)   # (exit, final) — BranchyNet


def default_spec(cfg: ArchConfig, c_thr: float = 0.9) -> EarlyExitSpec:
    return EarlyExitSpec(exit_layer=cfg.default_exit_layers()[0], c_thr=c_thr)


def validate_boundary(cfg: ArchConfig, k: int) -> None:
    base = cfg.first_k_dense
    if not (base <= k <= cfg.n_layers):
        raise ValueError(f"exit layer {k} outside [{base}, {cfg.n_layers}]")
    if (k - base) % cfg.pattern_len != 0:
        raise ValueError(
            f"exit layer {k} must be superblock-aligned (pattern len "
            f"{cfg.pattern_len}, leading dense {base})")


def init_ee_params(key, cfg: ArchConfig, spec: EarlyExitSpec) -> dict:
    validate_boundary(cfg, spec.exit_layer)
    k1, k2 = jax.random.split(key)
    return {
        "backbone": T.init_params(k1, cfg),
        "exit_head": {"norm": init_rmsnorm(cfg.d_model, cfg.p_dtype())},
    }


def ee_param_shapes(cfg: ArchConfig, spec: EarlyExitSpec):
    return jax.eval_shape(lambda k: init_ee_params(k, cfg, spec),
                          jax.ShapeDtypeStruct((2,), jnp.uint32))


def exit_head(params, cfg: ArchConfig, h):
    """Exit classifier: norm + tied unembed -> fp32 logits."""
    hn = rmsnorm(params["exit_head"]["norm"], h, cfg.norm_eps)
    bb = params["backbone"]
    if cfg.tie_embeddings or "head" not in bb:
        return unembed(bb["embed"], hn)
    return jnp.einsum("...d,dv->...v", hn.astype(jnp.float32),
                      bb["head"].astype(jnp.float32))


# ---------------------------------------------------------------------------
# training: all exits computed for every sample (joint loss)
# ---------------------------------------------------------------------------

def forward_train(params, cfg: ArchConfig, spec: EarlyExitSpec, tokens, *,
                  frontend_embeds=None):
    """Returns (exit_hidden, final_hidden, aux): hidden states before each
    head so the loss can chunk the unembedding over sequence."""
    bb = params["backbone"]
    memory = None
    if cfg.encdec:
        memory = T.encode(bb, cfg, frontend_embeds)
        frontend_embeds = None
    h = T.embed_tokens(bb, cfg, tokens, frontend_embeds)
    h, _, aux1 = T.run_layers(bb, cfg, h, 0, spec.exit_layer, mode="train",
                              memory=memory)
    exit_hidden = rmsnorm(params["exit_head"]["norm"], h, cfg.norm_eps)
    h, _, aux2 = T.run_layers(bb, cfg, h, spec.exit_layer, cfg.n_layers,
                              mode="train", memory=memory)
    final_hidden = rmsnorm(bb["final_norm"], h, cfg.norm_eps)
    return exit_hidden, final_hidden, aux1 + aux2


# ---------------------------------------------------------------------------
# serving: staged execution (the hardware mapping)
# ---------------------------------------------------------------------------

def stage1_prefill(params, cfg: ArchConfig, spec: EarlyExitSpec, tokens, *,
                   frontend_embeds=None):
    """Stage 1: embed + layers [0,k) + exit head on the last position.
    Returns (hidden (B,S,d), caches_seg1, exit_logits (B,V), memory)."""
    bb = params["backbone"]
    memory = None
    if cfg.encdec:
        memory = T.encode(bb, cfg, frontend_embeds)
        frontend_embeds = None
    h = T.embed_tokens(bb, cfg, tokens, frontend_embeds)
    h, caches, _ = T.run_layers(bb, cfg, h, 0, spec.exit_layer, mode="prefill",
                                memory=memory)
    logits = exit_head(params, cfg, h[:, -1])
    return h, caches, logits, memory


def _stage2_base_sb(cfg: ArchConfig, spec: EarlyExitSpec) -> int:
    return (spec.exit_layer - cfg.first_k_dense) // cfg.pattern_len


def stage2_prefill(params, cfg: ArchConfig, spec: EarlyExitSpec, h, *,
                   memory=None, presliced_params: bool = False):
    """Stage 2: layers [k,N) + final head on hard samples only.
    h: (C, S, d) compacted slab. Returns (logits (C,V), caches_seg2).
    ``presliced_params``: params is a stage-2 slice (ee.split_params), whose
    'blocks' leaves start at the exit boundary."""
    bb = params["backbone"]
    base = _stage2_base_sb(cfg, spec) if presliced_params else 0
    h, caches, _ = T.run_layers(bb, cfg, h, spec.exit_layer, cfg.n_layers,
                                mode="prefill", memory=memory,
                                param_base_sb=base)
    return T.head(bb, cfg, h[:, -1]), caches


def stage1_decode(params, cfg: ArchConfig, spec: EarlyExitSpec, token, caches,
                  step):
    """One-token stage 1. Returns (hidden (B,1,d), new_caches, exit_logits)."""
    bb = params["backbone"]
    h = T.embed_tokens(bb, cfg, token)
    h, ncaches, _ = T.run_layers(bb, cfg, h, 0, spec.exit_layer, mode="decode",
                                 caches=caches, step=step)
    return h, ncaches, exit_head(params, cfg, h[:, 0])


def stage2_decode(params, cfg: ArchConfig, spec: EarlyExitSpec, h, caches,
                  step, *, presliced: bool = True,
                  presliced_params: bool = False):
    """One-token stage 2 on the compacted hard slab. ``caches`` is the
    stage-2 SEGMENT cache (ee.split_caches) by default — its bucket batch
    size differs from stage 1's, so the pytrees cannot be shared.
    ``presliced_params`` marks a stage-2 param slice (ee.split_params)."""
    bb = params["backbone"]
    base = ((spec.exit_layer - cfg.first_k_dense) // cfg.pattern_len
            if presliced else 0)
    pbase = _stage2_base_sb(cfg, spec) if presliced_params else 0
    h, ncaches, _ = T.run_layers(bb, cfg, h, spec.exit_layer, cfg.n_layers,
                                 mode="decode", caches=caches, step=step,
                                 cache_base_sb=base, param_base_sb=pbase)
    return T.head(bb, cfg, h[:, 0]), ncaches


def _slice0(x, lo: int, hi: Optional[int]):
    """Slice axis 0 of an array OR a ShapeDtypeStruct (dry-run shapes)."""
    if isinstance(x, jax.ShapeDtypeStruct):
        n = x.shape[0]
        stop = n if hi is None else hi
        return jax.ShapeDtypeStruct((max(stop - lo, 0),) + x.shape[1:],
                                    x.dtype)
    return x[lo:] if hi is None else x[lo:hi]


def split_caches(cfg: ArchConfig, spec: EarlyExitSpec, caches):
    """Slice a full-depth cache pytree into (stage1, stage2) segments,
    mirroring run_layers' superblock slicing. Works on arrays and on
    ShapeDtypeStruct stand-ins (the dry-run path)."""
    pl = cfg.pattern_len
    k_super = (spec.exit_layer - cfg.first_k_dense) // pl
    s1 = {
        "first": caches["first"],
        "blocks": jax.tree.map(lambda x: _slice0(x, 0, k_super),
                               caches["blocks"]),
        "rem": [],
    }
    s2 = {
        "first": [],
        "blocks": jax.tree.map(lambda x: _slice0(x, k_super, None),
                               caches["blocks"]),
        "rem": caches["rem"],
    }
    return s1, s2


def split_params(cfg: ArchConfig, spec: EarlyExitSpec, params):
    """Slice the EE param tree into (stage1, stage2) resident sets — the
    multi-accelerator analogue of ATHEENA's per-stage floorplan regions,
    consumed by the StageExecutors (runtime/stage_executor.py) so each
    stage's submesh holds only its own layers.

    stage 1: embed + leading dense + superblocks [0, k_super) + exit head
             (+ the unembedding the exit head reads — the tied table or the
             untied 'head' matrix);
    stage 2: superblocks [k_super, N) + remainder + final norm + its
             unembedding. The unembedding both heads read is the one
             tensor resident on BOTH submeshes (the tied table, or the
             untied 'head' matrix — in which case the embed table stays on
             stage 1 only); everything else lives on exactly one.

    Slicing the stacked superblock leaves COPIES them (jnp slices are new
    buffers), so only split when there are disjoint submeshes to place the
    slices on — the degenerate single-device builders pass the full tree,
    and run_layers indexes each stage's layers in the stack in place in the
    inference modes (only ``mode="train"`` slices the stack to the stage's
    range). Stage-2 'blocks' leaves start at the exit boundary —
    pass ``presliced_params=True`` to the stage-2 entry points (they
    forward ``param_base_sb`` to run_layers)."""
    bb = params["backbone"]
    k_super = _stage2_base_sb(cfg, spec)
    # the unembedding: T.head and exit_head read the tied table, or the
    # separate 'head' matrix when untied (same fallback condition as both)
    shared = {}
    if cfg.tie_embeddings or "head" not in bb:
        shared["embed"] = bb["embed"]
    else:
        shared["head"] = bb["head"]
    bb1 = dict(shared)
    bb1["embed"] = bb["embed"]               # embed_tokens is stage 1's
    bb1["first"] = bb["first"]
    bb1["blocks"] = jax.tree.map(lambda x: _slice0(x, 0, k_super),
                                 bb["blocks"])
    bb1["rem"] = []
    if "encoder" in bb:                      # enc-dec: memory is stage 1's
        bb1["encoder"] = bb["encoder"]
    bb2 = dict(shared)
    bb2["first"] = []
    bb2["blocks"] = jax.tree.map(lambda x: _slice0(x, k_super, None),
                                 bb["blocks"])
    bb2["rem"] = bb["rem"]
    bb2["final_norm"] = bb["final_norm"]
    return ({"backbone": bb1, "exit_head": params["exit_head"]},
            {"backbone": bb2})


# ---------------------------------------------------------------------------
# one-shot batched EE inference (classification-style; used by the profiler
# and the CPU-measurable throughput benchmark)
# ---------------------------------------------------------------------------

def serve_batch(params, cfg: ArchConfig, spec: EarlyExitSpec, tokens, *,
                capacity: Optional[int] = None, frontend_embeds=None):
    """Full EE pipeline on one batch (prefill-style): stage 1 for all, exit
    decision, conditional buffer compaction, stage 2 for the hard slab, exit
    merge by sample id. Returns dict with merged last-token logits, the exit
    mask, and occupancy stats.

    The decision + compaction route through the kernel dispatch layer
    (``kernels.dispatch``): the fused Pallas kernels on TPU, their jnp
    oracles under XLA on CPU — never a per-sample host loop and never a
    materialized (B, V) softmax."""
    B = tokens.shape[0]
    sample_ids = jnp.arange(B, dtype=jnp.int32)
    h, _, exit_logits, memory = stage1_prefill(params, cfg, spec, tokens,
                                               frontend_embeds=frontend_embeds)
    exit_mask, pred, conf = dispatch.exit_decision_op(exit_logits, spec.c_thr)
    hard_mask = ~exit_mask
    cap = capacity if capacity is not None else B
    slab, slab_ids, n_hard = dispatch.gather_compact_op(h, hard_mask, cap)
    overflow = jnp.maximum(n_hard - cap, 0)
    mem_slab = None
    if memory is not None:
        # reuse the hidden slab's permutation: sample_ids is arange(B), so
        # slab_ids ARE the surviving row indices (flush slots -1 -> row 0,
        # matching the conditional-buffer padding contract)
        take = jnp.maximum(slab_ids, 0)
        mem_slab = jax.tree.map(lambda x: jnp.take(x, take, axis=0), memory)
    final_logits, _ = stage2_prefill(params, cfg, spec, slab, memory=mem_slab)
    easy_ids = jnp.where(exit_mask, sample_ids, -1)
    merged = cond.exit_merge(B, easy_ids, exit_logits, slab_ids, final_logits)
    return {
        "logits": merged,
        "exit_mask": exit_mask,
        "exit_logits": exit_logits,
        "confidence": conf,
        "n_hard": n_hard,
        "overflow": overflow,
    }
