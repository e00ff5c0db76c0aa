"""Plain reference for a decoder with latent attention (MLA) and sparse
experts after leading dense layers, with one early exit and an untied
output head: the layout of DeepSeek-V2-Lite at smoke widths, for the
harness's CPU tests of a configuration that is not a dense Qwen decoder.

Straight ``jax.numpy`` in float32, every product at ``Precision.HIGHEST``,
nothing imported from the program under test. Per layer:

* MLA: q = x Wq split into a no-rope and a rope part; the joint projection
  x W_dkv gives the KV latent (RMSNorm'd) and one rope key shared by the
  heads; per-head keys and values are the latent times W_uk and W_uv;
  score = (q_nope . k_nope + q_rope . k_rope) / sqrt(nope + rope).
* the first ``first_k_dense`` layers end in a SwiGLU MLP of ``dense_ff``;
  the others in a router (softmax over the experts, top-k, weights
  renormalised over the k), SwiGLU experts of ``d_ff_expert`` and shared
  experts that every token runs.
* exit-gap semantics as ``references/qwen_dense.py``: a position without
  stage-2 keys holds a zero latent and a zero rope key in layers
  ``[exit, N)``.

Every expert is computed for every token and weighted by the router, which
is the same sum as a dispatch with no token dropped; at these sizes no
token is.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def f32_matmul(spec: str, x, w):
    return jnp.einsum(spec, x.astype(jnp.float32), w.astype(jnp.float32),
                      precision=HIGHEST)


def dims(cfg) -> dict:
    m, e = cfg.mla, cfg.moe
    return {"n_layers": cfg.n_layers, "d_model": cfg.d_model,
            "n_heads": cfg.n_heads, "vocab": cfg.vocab, "eps": cfg.norm_eps,
            "rope_theta": cfg.rope_theta, "exit": cfg.exit_layers[0],
            "first_k_dense": cfg.first_k_dense, "dense_ff": cfg.dense_ff,
            "kv_lora_rank": m.kv_lora_rank, "nope": m.qk_nope_head_dim,
            "rope": m.qk_rope_head_dim, "v_head_dim": m.v_head_dim,
            "n_experts": e.n_experts, "top_k": e.top_k,
            "d_ff_expert": e.d_ff_expert, "n_shared": e.n_shared}


def _layer_weights(blk: dict) -> dict:
    at = blk["attn"]
    lw = {"norm1": blk["norm1"]["scale"], "wq": at["wq"],
          "w_dkv": at["w_dkv"], "kv_norm": at["kv_norm"]["scale"],
          "w_uk": at["w_uk"], "w_uv": at["w_uv"], "wo": at["wo"],
          "norm2": blk["norm2"]["scale"]}
    if "mlp" in blk:
        lw.update(w_gate=blk["mlp"]["wi_gate"], w_up=blk["mlp"]["wi_up"],
                  w_down=blk["mlp"]["wo"])
    else:
        mo = blk["moe"]
        lw.update(router=mo["router"], e_gate=mo["e_gate"], e_up=mo["e_up"],
                  e_down=mo["e_down"], s_gate=mo["shared"]["wi_gate"],
                  s_up=mo["shared"]["wi_up"], s_down=mo["shared"]["wo"])
    return lw


def weights(params) -> dict:
    """The served arrays under this reference's names (no copies): the
    leading dense layers one by one, the expert layers stacked."""
    bb = params["backbone"]
    return {"embed": bb["embed"]["table"], "head": bb["head"],
            "dense": [_layer_weights(b) for b in bb["first"]],
            "layers": _layer_weights(bb["blocks"][0]),
            "exit_norm": params["exit_head"]["norm"]["scale"],
            "final_norm": bb["final_norm"]["scale"]}


def rmsnorm(x, scale, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(jnp.float32)


def rope(x, theta: float):
    """x: (T, H, D); rotate-half RoPE at positions 0..T-1."""
    T, _, D = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def swiglu(x, gate, up, down, matmul):
    g = matmul("td,df->tf", x, gate)
    return matmul("tf,fd->td", jax.nn.silu(g) * matmul("td,df->tf", x, up),
                  down)


def moe(a: dict, lw: dict, x, matmul):
    probs = jax.nn.softmax(matmul("td,de->te", x, lw["router"]), axis=-1)
    top_w, top_i = jax.lax.top_k(probs, a["top_k"])
    top_w = top_w / jnp.sum(top_w, -1, keepdims=True)
    w = jnp.zeros_like(probs).at[
        jnp.arange(x.shape[0])[:, None], top_i].set(top_w)
    g = matmul("td,edf->tef", x, lw["e_gate"])
    u = matmul("td,edf->tef", x, lw["e_up"])
    y = matmul("tef,efd->ted", jax.nn.silu(g) * u, lw["e_down"])
    return jnp.einsum("te,ted->td", w, y, precision=HIGHEST) \
        + swiglu(x, lw["s_gate"], lw["s_up"], lw["s_down"], matmul)


def layer(a: dict, lw: dict, h, keep, matmul):
    """One layer over the whole sequence h (T, d), float32. ``keep`` (T,)
    bool: positions whose latent and rope key this layer holds."""
    T = h.shape[0]
    H, r, nope, rp = a["n_heads"], a["kv_lora_rank"], a["nope"], a["rope"]
    x = rmsnorm(h, lw["norm1"], a["eps"])
    q = matmul("td,de->te", x, lw["wq"]).reshape(T, H, nope + rp)
    q_nope, q_rope = q[..., :nope], rope(q[..., nope:], a["rope_theta"])
    dkv = matmul("td,de->te", x, lw["w_dkv"])
    latent = rmsnorm(dkv[:, :r], lw["kv_norm"], a["eps"])
    k_rope = rope(dkv[:, None, r:], a["rope_theta"])[:, 0]
    latent = jnp.where(keep[:, None], latent, 0.0)
    k_rope = jnp.where(keep[:, None], k_rope, 0.0)
    k_nope = matmul("tr,re->te", latent, lw["w_uk"]).reshape(T, H, nope)
    v = matmul("tr,re->te", latent, lw["w_uv"]).reshape(T, H, -1)
    s = (matmul("thd,uhd->htu", q_nope, k_nope)
         + matmul("thd,ud->htu", q_rope, k_rope)) / math.sqrt(nope + rp)
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = matmul("htu,uhd->thd", p, v).reshape(T, -1)
    h = h + matmul("te,ed->td", o, lw["wo"])
    x = rmsnorm(h, lw["norm2"], a["eps"])
    if "router" in lw:
        return h + moe(a, lw, x, matmul)
    return h + swiglu(x, lw["w_gate"], lw["w_up"], lw["w_down"], matmul)


def layers(a: dict, w: dict, h, keep, lo: int, hi: int, matmul):
    """Layers [lo, hi), one at a time."""
    k = a["first_k_dense"]
    for i in range(lo, hi):
        lw = w["dense"][i] if i < k else \
            jax.tree.map(lambda x: x[i - k], w["layers"])
        h = layer(a, lw, h, keep, matmul)
    return h


def head(a: dict, w: dict, norm: str, h_rows, matmul):
    """RMSNorm + the untied head (d, V) -> float32 logits (rows, V)."""
    return matmul("td,dv->tv", rmsnorm(h_rows, w[norm], a["eps"]), w["head"])


def stage1(a: dict, w: dict, tokens, rows, matmul=f32_matmul):
    """Embed + layers [0, exit) over every position, and the exit head at
    ``rows``. Returns (h (T, d), exit logits (len(rows), V))."""
    h = jnp.take(w["embed"], tokens, axis=0).astype(jnp.float32)
    h = layers(a, w, h, jnp.ones(tokens.shape, bool), 0, a["exit"], matmul)
    return h, head(a, w, "exit_norm", h[rows], matmul)


def stage2(a: dict, w: dict, h, keep, rows, matmul=f32_matmul):
    """Layers [exit, N) with latents only at ``keep`` positions, and the
    final head at ``rows``."""
    h = layers(a, w, h, keep, a["exit"], a["n_layers"], matmul)
    return head(a, w, "final_norm", h[rows], matmul)


def full_forward(a: dict, w: dict, tokens, matmul=f32_matmul):
    """Every layer, every position kept: final logits at every position."""
    h = jnp.take(w["embed"], tokens, axis=0).astype(jnp.float32)
    h = layers(a, w, h, jnp.ones(tokens.shape, bool), 0, a["n_layers"],
               matmul)
    return head(a, w, "final_norm", h, matmul)
