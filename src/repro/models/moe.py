"""Mixture-of-Experts: a dropless layer over the experts it holds.

The router scores every one of ``n_experts`` experts and keeps the top k
per token. The layer holds the weights of a contiguous range of
``n_held`` of them (all by default; one chip's share under expert
parallelism, told where its range starts). Each token's k assignments are
sorted by expert; those to held experts form one group per expert and run
as grouped matrix multiplications over the held stacks
(``jax.lax.ragged_dot``), and those to absent experts fall past every
group and add nothing: that is the part the other holders would add. No
expert has a capacity, so no assignment is dropped and a token's output
does not depend on its batch; the shapes are static (T*k rows).
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.models.config import ArchConfig
from repro.models.layers import dense_init, init_mlp, mlp

# the layer's load counters, summed over MoE layers by ``run_layers``:
# routed assignments, assignments on held experts, held experts with at
# least one row, the largest held group, and 1 (the number of layers)
LOAD_FIELDS = ("routed", "held_rows", "touched", "max_group", "layers")


def init_moe(key, cfg: ArchConfig) -> dict:
    m = cfg.moe
    d = cfg.d_model
    dt = cfg.p_dtype()
    ks = jax.random.split(key, 4)
    p = {
        "router": dense_init(ks[0], (d, m.n_experts), jnp.float32),
        # stacked weights of the held experts (n_held, ...), SwiGLU experts
        "e_gate": dense_init(ks[1], (m.held, d, m.d_ff_expert), dt),
        "e_up": dense_init(ks[2], (m.held, d, m.d_ff_expert), dt),
        "e_down": dense_init(ks[3], (m.held, m.d_ff_expert, d), dt),
    }
    if m.n_shared:
        p["shared"] = init_mlp(jax.random.fold_in(key, 7), d,
                               m.d_ff_expert * m.n_shared, "swiglu", dt)
    return p


def route(params, cfg: ArchConfig, x_flat) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """x_flat: (T, d) -> (topk_idx (T,k), topk_w (T,k), aux_loss ())."""
    m = cfg.moe
    logits = jnp.einsum("td,de->te", x_flat.astype(jnp.float32), params["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    topk_w, topk_idx = jax.lax.top_k(probs, m.top_k)
    if m.norm_topk_prob:
        topk_w = topk_w / jnp.maximum(jnp.sum(topk_w, axis=-1, keepdims=True),
                                      1e-9)
    # load-balance aux loss (Switch): E * sum_e f_e * P_e
    T = x_flat.shape[0]
    f = jnp.zeros((m.n_experts,), jnp.float32).at[topk_idx.reshape(-1)].add(
        1.0 / (T * m.top_k))
    P = jnp.mean(probs, axis=0)
    aux = m.n_experts * jnp.sum(f * P)
    return topk_idx, topk_w, aux


def moe_fwd(params, cfg: ArchConfig, x, *, first: int = 0
            ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """x: (B, S, d) -> (out (B, S, d), aux_loss (), load (5,) int32).
    The layer holds experts [first, first + n_held); ``load`` counts as
    ``LOAD_FIELDS`` says."""
    m = cfg.moe
    B, S, d = x.shape
    T, k, G = B * S, m.top_k, m.held
    xf = x.reshape(T, d)
    topk_idx, topk_w, aux = route(params, cfg, xf)

    # ---- dispatch: held assignments grouped by expert, absent ones last ---
    local = topk_idx.reshape(-1) - first                 # (T*k,)
    held = (local >= 0) & (local < G)
    group = jnp.where(held, local, G)
    order = jnp.argsort(group, stable=True)
    sizes = jnp.bincount(group, length=G + 1)[:G].astype(jnp.int32)
    rows = xf[order // k]                                # (T*k, d)

    # ---- the held experts' grouped SwiGLU (rows past the groups: unset) ---
    g = jax.lax.ragged_dot(rows, params["e_gate"], sizes)
    u = jax.lax.ragged_dot(rows, params["e_up"], sizes)
    h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    y = jax.lax.ragged_dot(h, params["e_down"], sizes)  # (T*k, d)

    # ---- combine: back to (token, slot) order, absent rows zeroed (what a
    # grouped matmul leaves past its groups is not specified), weighted sum
    inv = jnp.zeros_like(order).at[order].set(jnp.arange(T * k))
    y = jnp.where(held[:, None], y[inv], 0).reshape(T, k, d)
    out = jnp.einsum("tk,tkd->td", topk_w.astype(x.dtype), y)

    if m.n_shared:
        out = out + mlp(params["shared"], xf, "swiglu")
    load = jnp.stack([jnp.int32(T * k), jnp.sum(sizes),
                      jnp.sum(sizes > 0).astype(jnp.int32), jnp.max(sizes),
                      jnp.int32(1)])
    return out.reshape(B, S, d), aux, load


def moe_fwd_dense(params, cfg: ArchConfig, x, *, first: int = 0
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Reference dense formulation: every held expert sees every token
    (oracle for tests; never used in the hot path)."""
    m = cfg.moe
    B, S, d = x.shape
    xf = x.reshape(-1, d)
    topk_idx, topk_w, aux = route(params, cfg, xf)
    g = jnp.einsum("td,edf->tef", xf, params["e_gate"])
    u = jnp.einsum("td,edf->tef", xf, params["e_up"])
    h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    y = jnp.einsum("tef,efd->ted", h, params["e_down"])       # (T, G, d)
    w_full = jnp.zeros((xf.shape[0], m.n_experts), jnp.float32).at[
        jnp.arange(xf.shape[0])[:, None], topk_idx].set(topk_w)
    w_held = w_full[:, first:first + m.held]
    out = jnp.einsum("te,ted->td", w_held.astype(x.dtype), y)
    if m.n_shared:
        out = out + mlp(params["shared"], xf, "swiglu")
    return out.reshape(B, S, d), aux
