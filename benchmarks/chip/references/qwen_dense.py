"""Plain reference for the Qwen2/Qwen3 dense decoder with one early exit.

Straight ``jax.numpy`` in float32, every matrix product at
``Precision.HIGHEST``; no kernels, caches, ring or batching, and nothing
imported from the program under test. It follows the published layer
equations (RMSNorm, grouped-query attention with rotate-half RoPE, an
optional bias on q/k/v (Qwen2) or RMSNorm on each q/k head (Qwen3), SwiGLU
MLP, tied unembedding) and the early-exit serving semantics the program
documents:

* the exit head is RMSNorm + the tied unembedding after layer ``exit``;
* a decode token exits when its max softmax probability exceeds ``c_thr``;
* a token that exits never runs layers ``[exit, N)``, so those layers hold
  zero keys and values at its position, and later tokens attend over the
  zeros (they still count in the softmax): the "exit-gap" semantics.

Weights arrive in the layout ``layout()`` names, stored as served (bf16);
they are upcast layer by layer. ``matmul`` is the one product every
equation uses, so the fp8 control (``fp8_matmul``) swaps precision in one
place.

The harness asks this module how it reads the model: ``dims(cfg)`` gives
the sizes it needs from the program's configuration, and
``weights(params)`` names the served arrays in its layout (no copies).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def f32_matmul(spec: str, x, w):
    return jnp.einsum(spec, x.astype(jnp.float32), w.astype(jnp.float32),
                      precision=HIGHEST)


def _fp8(x):
    """Symmetric float8_e4m3fn with one scale for the whole operand."""
    x = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn), scale


def fp8_matmul(spec: str, x, w):
    """The control: both operands in float8 e4m3, each with one scale,
    products accumulated in float32."""
    xq, xs = _fp8(x)
    wq, ws = _fp8(w)
    return jnp.einsum(spec, xq, wq, preferred_element_type=jnp.float32) \
        * (xs * ws)


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


def layer(a: dict, lw: dict, h, keep, matmul):
    """One decoder layer over the whole sequence h (T, d), float32.
    ``keep`` (T,) bool: positions whose keys and values this layer holds."""
    T = h.shape[0]
    H, KH, hd = a["n_heads"], a["n_kv_heads"], a["head_dim"]
    x = rmsnorm(h, lw["norm1"], a["eps"])
    q = matmul("td,de->te", x, lw["wq"])
    k = matmul("td,de->te", x, lw["wk"])
    v = matmul("td,de->te", x, lw["wv"])
    if a["qkv_bias"]:
        q = q + lw["bq"].astype(jnp.float32)
        k = k + lw["bk"].astype(jnp.float32)
        v = v + lw["bv"].astype(jnp.float32)
    q, k, v = q.reshape(T, H, hd), k.reshape(T, KH, hd), v.reshape(T, KH, hd)
    if a["qk_norm"]:
        q = rmsnorm(q, lw["q_norm"], a["eps"])
        k = rmsnorm(k, lw["k_norm"], a["eps"])
    q, k = rope(q, a["rope_theta"]), rope(k, a["rope_theta"])
    k = jnp.where(keep[:, None, None], k, 0.0)
    v = jnp.where(keep[:, None, None], v, 0.0)
    g = H // KH
    qg = q.reshape(T, KH, g, hd)
    s = matmul("tkgd,ukd->kgtu", qg, k) / math.sqrt(hd)
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    s = jnp.where(causal, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = matmul("kgtu,ukd->tkgd", p, v).reshape(T, H * hd)
    h = h + matmul("te,ed->td", o, lw["wo"])
    x = rmsnorm(h, lw["norm2"], a["eps"])
    gate = matmul("td,df->tf", x, lw["w_gate"])
    up = matmul("td,df->tf", x, lw["w_up"])
    return h + matmul("tf,fd->td", jax.nn.silu(gate) * up, lw["w_down"])


def layers(a: dict, w: dict, h, keep, lo: int, hi: int, matmul):
    """Layers [lo, hi) one at a time (scan over the stacked weights)."""
    def body(hh, i):
        lw = jax.tree.map(lambda x: x[i], w["layers"])
        return layer(a, lw, hh, keep, matmul), None
    h, _ = jax.lax.scan(body, h, jnp.arange(lo, hi))
    return h


def head(a: dict, w: dict, norm: str, h_rows, matmul):
    """RMSNorm + tied unembedding -> float32 logits (rows, V)."""
    return matmul("td,vd->tv", rmsnorm(h_rows, w[norm], a["eps"]),
                  w["embed"])


def layout() -> dict:
    """The weight names this reference reads (per-layer leaves stacked on
    a leading layer axis; matrices are (in, out))."""
    return {"embed": "(V, d)", "exit_norm": "(d,)", "final_norm": "(d,)",
            "layers": ["norm1", "wq", "wk", "wv", "bq", "bk", "bv",
                       "q_norm", "k_norm", "wo", "norm2", "w_gate", "w_up",
                       "w_down"]}


def dims(cfg) -> dict:
    """What the reference and the FLOP count need to know of the model."""
    return {"n_layers": cfg.n_layers, "d_model": cfg.d_model,
            "n_heads": cfg.n_heads, "n_kv_heads": cfg.n_kv_heads,
            "head_dim": cfg.resolved_head_dim, "d_ff": cfg.d_ff,
            "vocab": cfg.vocab, "eps": cfg.norm_eps,
            "rope_theta": cfg.rope_theta, "qkv_bias": cfg.qkv_bias,
            "qk_norm": cfg.qk_norm, "exit": cfg.exit_layers[0]}


def weights(params) -> dict:
    """The same arrays under the reference's names (no copies)."""
    bb = params["backbone"]
    blk = bb["blocks"][0]
    at, mlp = blk["attn"], blk["mlp"]
    lw = {"norm1": blk["norm1"]["scale"], "wq": at["wq"], "wk": at["wk"],
          "wv": at["wv"], "wo": at["wo"], "norm2": blk["norm2"]["scale"],
          "w_gate": mlp["wi_gate"], "w_up": mlp["wi_up"],
          "w_down": mlp["wo"]}
    for k in ("bq", "bk", "bv"):
        if k in at:
            lw[k] = at[k]
    for k in ("q_norm", "k_norm"):
        if k in at:
            lw[k] = at[k]["scale"]
    return {"embed": bb["embed"]["table"], "layers": lw,
            "exit_norm": params["exit_head"]["norm"]["scale"],
            "final_norm": bb["final_norm"]["scale"]}


def stage1(a: dict, w: dict, tokens, rows, matmul=f32_matmul):
    """Embed + layers [0, exit) over every position (stage 1 runs for every
    token), and the exit head at ``rows``. Returns (h (T, d), exit logits
    (len(rows), V))."""
    h = jnp.take(w["embed"], tokens, axis=0).astype(jnp.float32)
    keep = jnp.ones(tokens.shape, bool)
    h = layers(a, w, h, keep, 0, a["exit"], matmul)
    return h, head(a, w, "exit_norm", h[rows], matmul)


def stage2(a: dict, w: dict, h, keep, rows, matmul=f32_matmul):
    """Layers [exit, N) with keys and values only at ``keep`` positions,
    and the final head at ``rows``."""
    h = layers(a, w, h, keep, a["exit"], a["n_layers"], matmul)
    return head(a, w, "final_norm", h[rows], matmul)


def full_forward(a: dict, w: dict, tokens, matmul=f32_matmul):
    """Every layer, every position kept: final logits at every position."""
    h = jnp.take(w["embed"], tokens, axis=0).astype(jnp.float32)
    keep = jnp.ones(tokens.shape, bool)
    h = layers(a, w, h, keep, 0, a["n_layers"], matmul)
    return head(a, w, "final_norm", h, matmul)
