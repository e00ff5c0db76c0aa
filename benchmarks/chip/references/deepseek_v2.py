"""Plain reference for DeepSeek-V2-Lite with one early exit, as one chip's
share of expert parallelism serves it.

Straight ``jax.numpy`` in float32, every matrix product at
``Precision.HIGHEST``; no kernels, caches, ring, batching or grouped
dispatch, and nothing imported from the program under test. Per layer
(DeepSeek-V2, arXiv:2405.04434, and the published config.json):

* MLA without q LoRA: q = x Wq, split per head into a no-rope part (128)
  and a rope part (64); x W_dkv gives the KV latent (512, RMSNorm'd) and
  one rope key (64) shared by the heads. Per-head keys and values are
  decompressed from the latent (k_nope = latent W_uk, v = latent W_uv),
  not absorbed into the query as the program's decode is, so this is an
  independent check of that algebra. score = (q_nope . k_nope + q_rope .
  k_rope) * softmax_scale.
* YaRN rope, written out from the published formula: inverse frequencies
  theta^(-2i/D) for i below the ``beta_fast`` correction dimension, those
  / ``factor`` above the ``beta_slow`` one, a linear ramp between;
  softmax_scale = mscale(factor, mscale_all_dim)^2 / sqrt(192), with
  mscale(s, m) = 0.1 m ln s + 1; cos and sin times mscale(factor, mscale)
  / mscale(factor, mscale_all_dim).
* layer 0: a SwiGLU MLP of ``dense_ff``. The others: a router (softmax
  over all ``n_experts``, top-k, the weights renormalised only where the
  configuration says ``norm_topk_prob``), SwiGLU experts of
  ``d_ff_expert``, and ``n_shared`` shared experts every token runs.
* exit-gap semantics as ``qwen_dense.py``: a position without stage-2
  keys holds a zero latent and a zero rope key in layers ``[exit, N)``.

Departures from the published model, each a property of the served
configuration rather than of this code:

* one chip's share of EP4: only the held experts ``[0, n_held)`` have
  weights; each is computed for every token and weighted by its router
  weight, and what the absent experts would add is left out, as on the
  chip (that partial sum is what the next layer sees);
* rotate-half rope on the first and second halves of the rope dimensions,
  where HF DeepSeek rotates interleaved pairs: a fixed permutation of the
  rope columns of random weights, which changes nothing measured;
* ``routed_scaling_factor`` (1) and the auxiliary losses are not applied;
* random weights (``chipbench/system.py``), an untied head drawn like the
  embedding.

Weights arrive as served (bf16) and are upcast product by product.
``matmul`` is the one product every equation uses, so the float8 control
(``fp8_matmul``) swaps precision in one place.
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


def dims(cfg) -> dict:
    """What this reference and the FLOP count need to know of the model."""
    m, e, rs = cfg.mla, cfg.moe, cfg.rope_scaling
    a = {"n_layers": cfg.n_layers, "d_model": cfg.d_model,
         "n_heads": cfg.n_heads, "vocab": cfg.vocab, "eps": cfg.norm_eps,
         "rope_theta": cfg.rope_theta, "exit": cfg.exit_layers[0],
         "first_k_dense": cfg.first_k_dense, "dense_ff": cfg.dense_ff,
         "kv_lora_rank": m.kv_lora_rank, "nope": m.qk_nope_head_dim,
         "rope": m.qk_rope_head_dim, "v_head_dim": m.v_head_dim,
         "n_experts": e.n_experts, "n_held": e.held, "top_k": e.top_k,
         "d_ff_expert": e.d_ff_expert, "n_shared": e.n_shared,
         "norm_topk_prob": e.norm_topk_prob, "yarn": None}
    if rs is not None:
        a["yarn"] = {k: getattr(rs, k) for k in (
            "factor", "original_max_position_embeddings", "beta_fast",
            "beta_slow", "mscale", "mscale_all_dim")}
    return a


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


def _mscale(s: float, m: float) -> float:
    return 1.0 if s <= 1 else 0.1 * m * math.log(s) + 1.0


def rope_inv_freq(a: dict):
    """(rope/2,) inverse frequencies: YaRN's where the model states it."""
    D, base, y = a["rope"], a["rope_theta"], a["yarn"]
    expo = jnp.arange(0, D, 2, dtype=jnp.float32) / D
    extra = 1.0 / (base ** expo)
    if y is None:
        return extra
    inter = 1.0 / (y["factor"] * base ** expo)

    def corr(n_rot):
        return (D * math.log(y["original_max_position_embeddings"]
                             / (n_rot * 2 * math.pi))) / (2 * math.log(base))
    low = max(math.floor(corr(y["beta_fast"])), 0)
    high = min(math.ceil(corr(y["beta_slow"])), D - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(D // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return inter * ramp + extra * (1.0 - ramp)


def softmax_scale(a: dict) -> float:
    s = 1.0 / math.sqrt(a["nope"] + a["rope"])
    y = a["yarn"]
    if y is not None and y["mscale_all_dim"]:
        s *= _mscale(y["factor"], y["mscale_all_dim"]) ** 2
    return s


def rope(a: dict, x):
    """x: (T, H, D); rotate-half rope at positions 0..T-1."""
    T = x.shape[0]
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * rope_inv_freq(a)
    f = 1.0
    if a["yarn"] is not None:
        y = a["yarn"]
        f = _mscale(y["factor"], y["mscale"]) / _mscale(y["factor"],
                                                        y["mscale_all_dim"])
    cos, sin = (f * jnp.cos(ang))[:, None, :], (f * jnp.sin(ang))[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def swiglu(x, gate, up, down, matmul):
    g = matmul("td,df->tf", x, gate)
    return matmul("tf,fd->td", jax.nn.silu(g) * matmul("td,df->tf", x, up),
                  down)


def moe(a: dict, lw: dict, x, matmul):
    """Softmax routing over every expert, top-k; the held experts
    [0, n_held), each over every token, weighted by the router; plus the
    shared experts."""
    T = x.shape[0]
    probs = jax.nn.softmax(matmul("td,de->te", x, lw["router"]), axis=-1)
    top_w, top_i = jax.lax.top_k(probs, a["top_k"])
    if a["norm_topk_prob"]:
        top_w = top_w / jnp.sum(top_w, -1, keepdims=True)
    w = jnp.zeros_like(probs).at[jnp.arange(T)[:, None], top_i].set(top_w)
    w = w[:, :a["n_held"]]
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
    q_nope, q_rope = q[..., :nope], rope(a, q[..., nope:])
    dkv = matmul("td,de->te", x, lw["w_dkv"])
    latent = rmsnorm(dkv[:, :r], lw["kv_norm"], a["eps"])
    k_rope = rope(a, dkv[:, None, r:])[:, 0]
    latent = jnp.where(keep[:, None], latent, 0.0)
    k_rope = jnp.where(keep[:, None], k_rope, 0.0)
    k_nope = matmul("tr,re->te", latent, lw["w_uk"]).reshape(T, H, nope)
    v = matmul("tr,re->te", latent, lw["w_uv"]).reshape(T, H, -1)
    s = (matmul("thd,uhd->htu", q_nope, k_nope)
         + matmul("thd,ud->htu", q_rope, k_rope)) * softmax_scale(a)
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = matmul("htu,uhd->thd", p, v).reshape(T, -1)
    h = h + matmul("te,ed->td", o, lw["wo"])
    x = rmsnorm(h, lw["norm2"], a["eps"])
    if "router" in lw:
        return h + moe(a, lw, x, matmul)
    return h + swiglu(x, lw["w_gate"], lw["w_up"], lw["w_down"], matmul)


def layers(a: dict, w: dict, h, keep, lo: int, hi: int, matmul):
    """Layers [lo, hi): the leading dense ones one by one, then a scan
    over the stacked expert layers."""
    k = a["first_k_dense"]
    for i in range(lo, min(hi, k)):
        h = layer(a, w["dense"][i], h, keep, matmul)

    def body(hh, i):
        lw = jax.tree.map(lambda x: x[i], w["layers"])
        return layer(a, lw, hh, keep, matmul), None
    if hi > max(lo, k):
        h, _ = jax.lax.scan(body, h, jnp.arange(max(lo, k) - k, hi - k))
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
