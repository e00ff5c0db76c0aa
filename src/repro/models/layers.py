"""Common neural primitives, pure JAX (no flax).

Param convention: every module is a pair of functions
  init_<mod>(key, cfg, ...) -> params (pytree of jnp arrays)
  <mod>(params, x, ...)     -> y
Params are plain dicts so they stack cleanly along a leading layer axis for
``lax.scan`` over layers.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp


# ----------------------------------------------------------------------------
# initializers
# ----------------------------------------------------------------------------

def dense_init(key, shape, dtype, scale: Optional[float] = None):
    """Truncated-normal fan-in init (the MaxText/T5 default)."""
    fan_in = shape[0] if len(shape) >= 2 else shape[-1]
    std = scale if scale is not None else 1.0 / math.sqrt(fan_in)
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32) * std).astype(dtype)


def embed_init(key, shape, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * 0.02).astype(dtype)


# ----------------------------------------------------------------------------
# norms
# ----------------------------------------------------------------------------

def init_rmsnorm(d: int, dtype) -> dict:
    return {"scale": jnp.ones((d,), dtype)}


def rmsnorm(params, x, eps: float = 1e-6):
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * params["scale"].astype(jnp.float32)).astype(dt)


def init_layernorm(d: int, dtype) -> dict:
    return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}


def layernorm(params, x, eps: float = 1e-6):
    dt = x.dtype
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * params["scale"].astype(jnp.float32) + params["bias"].astype(jnp.float32)).astype(dt)


# ----------------------------------------------------------------------------
# RoPE
# ----------------------------------------------------------------------------

def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention magnitude correction, 0.1 m ln(s) + 1 (1 for s <= 1)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def _yarn_correction_dim(n_rot: float, head_dim: int, theta: float,
                         orig_len: int) -> float:
    """The rotary dimension whose wavelength fits ``n_rot`` turns in the
    original context."""
    return (head_dim * math.log(orig_len / (n_rot * 2 * math.pi))
            / (2 * math.log(theta)))


def rope_freqs(head_dim: int, theta: float, scaling=None):
    """(D/2,) inverse frequencies; with YaRN ``scaling`` (a
    ``config.RopeScaling``), those past the ``beta_fast`` correction
    dimension ramp linearly to the original / ``factor``, reached at the
    ``beta_slow`` one."""
    inv = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    if scaling is None:
        return inv
    orig = scaling.original_max_position_embeddings
    lo = max(math.floor(_yarn_correction_dim(scaling.beta_fast, head_dim,
                                             theta, orig)), 0)
    hi = min(math.ceil(_yarn_correction_dim(scaling.beta_slow, head_dim,
                                            theta, orig)), head_dim - 1)
    hi = hi + 0.001 if hi == lo else hi
    ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - lo)
                    / (hi - lo), 0.0, 1.0)
    return inv / scaling.factor * ramp + inv * (1.0 - ramp)


def apply_rope(x, positions, theta: float, scaling=None):
    """x: (..., S, H, D) or (..., S, D); positions: (..., S). ``scaling``:
    YaRN (``rope_freqs``), which also scales cos and sin by mscale(factor,
    mscale) / mscale(factor, mscale_all_dim)."""
    d = x.shape[-1]
    inv = rope_freqs(d, theta, scaling)              # (D/2,)
    ang = positions.astype(jnp.float32)[..., None] * inv   # (..., S, D/2)
    if x.ndim == ang.ndim + 1:                        # (..., S, H, D): broadcast heads
        ang = ang[..., None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if scaling is not None:
        f = (yarn_mscale(scaling.factor, scaling.mscale)
             / yarn_mscale(scaling.factor, scaling.mscale_all_dim))
        cos, sin = cos * f, sin * f
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ----------------------------------------------------------------------------
# MLP (SwiGLU / GELU)
# ----------------------------------------------------------------------------

def init_mlp(key, d_model: int, d_ff: int, act: str, dtype) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    p = {"wo": dense_init(k2, (d_ff, d_model), dtype)}
    if act in ("swiglu", "geglu"):
        p["wi_gate"] = dense_init(k1, (d_model, d_ff), dtype)
        p["wi_up"] = dense_init(k3, (d_model, d_ff), dtype)
    else:
        p["wi"] = dense_init(k1, (d_model, d_ff), dtype)
    return p


def mlp(params, x, act: str):
    if act in ("swiglu", "geglu"):
        g = jnp.einsum("...d,df->...f", x, params["wi_gate"])
        u = jnp.einsum("...d,df->...f", x, params["wi_up"])
        nl = jax.nn.silu if act == "swiglu" else jax.nn.gelu
        h = nl(g.astype(jnp.float32)).astype(x.dtype) * u
    else:
        h = jnp.einsum("...d,df->...f", x, params["wi"])
        h = jax.nn.gelu(h.astype(jnp.float32)).astype(x.dtype)
    return jnp.einsum("...f,fd->...d", h, params["wo"])


def mlp_flops(d_model: int, d_ff: int, act: str) -> int:
    n_mats = 3 if act in ("swiglu", "geglu") else 2
    return 2 * n_mats * d_model * d_ff


# ----------------------------------------------------------------------------
# Embedding / unembedding
# ----------------------------------------------------------------------------

def init_embedding(key, vocab: int, d_model: int, dtype) -> dict:
    return {"table": embed_init(key, (vocab, d_model), dtype)}


def embed(params, tokens):
    return jnp.take(params["table"], tokens, axis=0)


def unembed(params, h):
    """Tied unembedding: h @ table.T -> logits (fp32)."""
    return jnp.einsum("...d,vd->...v", h.astype(jnp.float32),
                      params["table"].astype(jnp.float32))


# ----------------------------------------------------------------------------
# Chunked causal attention core (pure JAX flash-style; the Pallas kernel in
# kernels/flash_attention mirrors this block structure for TPU).
# ----------------------------------------------------------------------------

def blocked_attention(q, k, v, *, causal: bool = True, window: Optional[int] = None,
                      q_block: int = 256, kv_block: int = 512,
                      softcap: Optional[float] = None,
                      q_offset=0, scale: Optional[float] = None):
    """Memory-bounded attention (the jnp mirror of the Pallas flash kernel).

    q: (B, Sq, H, D); k, v: (B, Sk, KH, D) with H % KH == 0.
    Three-level scan — kv-head groups, then query blocks, then kv blocks with
    an online softmax — so every loop-body tensor is a VMEM-sized tile (this
    is what the Pallas kernel enforces with BlockSpecs on TPU; the scan
    structure makes the lowered HLO's working set match it). ``q_offset`` is
    the absolute position of q[0] (sequence-parallel shards / decode
    continuation), int or traced scalar. ``scale`` multiplies the scores
    (1/sqrt(D) when None).
    """
    B, Sq, H, D = q.shape
    _, Sk, KH, _ = k.shape
    G = H // KH
    scale = 1.0 / math.sqrt(D) if scale is None else scale
    kb = min(kv_block, Sk)
    # adaptive q tile: biggest block keeping the (B, G, qb, kb) f32 score
    # tile within a VMEM budget — fewer K/V re-reads for small-G (MHA) archs
    budget = 4 * 1024 * 1024
    qb_fit = max(budget // (B * G * kb * 4), 1)
    qb_fit = 1 << (qb_fit.bit_length() - 1)            # floor pow2
    qb = min(max(q_block, qb_fit), 1024, Sq)
    # pad to multiples
    pad_q = (-Sq) % qb
    pad_k = (-Sk) % kb
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    nq, nk = q.shape[1] // qb, k.shape[1] // kb

    # head-group-major layouts: one kv head's tiles per outer step
    qr = q.reshape(B, nq, qb, KH, G, D).transpose(3, 1, 0, 2, 4, 5)
    #    (KH, nq, B, qb, G, D)
    kr = k.reshape(B, nk, kb, KH, D).transpose(3, 0, 1, 2, 4)   # (KH,B,nk,kb,D)
    vr = v.reshape(B, nk, kb, KH, D).transpose(3, 0, 1, 2, 4)
    q_pos = q_offset + jnp.arange(nq * qb).reshape(nq, qb)
    k_pos = jnp.arange(nk * kb).reshape(nk, kb)
    k_valid = (jnp.arange(nk * kb) < Sk).reshape(nk, kb)

    def h_step(_, hi):
        qh, kh, vh = hi                    # (nq,B,qb,G,D), (B,nk,kb,D)
        kh_t = kh.transpose(1, 0, 2, 3)    # (nk, B, kb, D)
        vh_t = vh.transpose(1, 0, 2, 3)

        def q_step(_, qi):
            qblk, qp = qi                  # (B, qb, G, D), (qb,)

            def kv_step(carry, ki):
                m, l, acc = carry
                kblk, vblk, kp, kval = ki  # (B, kb, D), (kb,)
                # inputs stay in their storage dtype (bf16 streams on TPU);
                # the MXU accumulates in f32 (preferred_element_type)
                s = jnp.einsum("bqgd,bkd->bgqk", qblk, kblk,
                               preferred_element_type=jnp.float32) * scale
                if softcap is not None:
                    s = softcap * jnp.tanh(s / softcap)
                mask = kval[None, :]
                if causal:
                    mask = mask & (qp[:, None] >= kp[None, :])
                if window is not None:
                    mask = mask & (qp[:, None] - kp[None, :] < window)
                s = jnp.where(mask[None, None], s, -jnp.inf)
                m_new = jnp.maximum(m, jnp.max(s, axis=-1))
                m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
                p = jnp.exp(s - m_safe[..., None])
                p = jnp.where(mask[None, None], p, 0.0)
                corr = jnp.where(jnp.isneginf(m), 0.0, jnp.exp(m - m_safe))
                l_new = l * corr + jnp.sum(p, axis=-1)
                acc_new = acc * corr[..., None] + jnp.einsum(
                    "bgqk,bkd->bgqd", p.astype(vblk.dtype), vblk,
                    preferred_element_type=jnp.float32)
                return (m_new, l_new, acc_new), None

            m0 = jnp.full((B, G, qb), -jnp.inf, jnp.float32)
            l0 = jnp.zeros((B, G, qb), jnp.float32)
            a0 = jnp.zeros((B, G, qb, D), jnp.float32)
            (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0),
                                          (kh_t, vh_t, k_pos, k_valid))
            out = acc / jnp.maximum(l, 1e-30)[..., None]   # (B, G, qb, D)
            return None, out.transpose(0, 2, 1, 3)          # (B, qb, G, D)

        _, blocks = jax.lax.scan(q_step, None, (qh, q_pos))
        return None, blocks                                 # (nq, B, qb, G, D)

    _, hb = jax.lax.scan(h_step, None, (qr, kr, vr))        # (KH,nq,B,qb,G,D)
    out = hb.transpose(2, 1, 3, 0, 4, 5).reshape(B, nq * qb, H, D)
    return out[:, :Sq].astype(q.dtype)


# ----------------------------------------------------------------------------
# Differentiable flash attention (custom VJP): the backward recomputes the
# probability blocks from (q, k, v, L) instead of letting AD stack every
# (nq, nk, B, G, qb, kb) p-block as a residual — THE dominant HBM term of
# naive-AD attention training (403 MB/layer for qwen2-1.5b train_4k).
# ----------------------------------------------------------------------------

def _flash_fwd_stats(q, k, v, causal, window, q_offset, qb, kb):
    """blocked_attention forward that also returns the per-row logsumexp
    L = m + log(l), shaped (B, Sq, H). Same 3-level scan structure."""
    B, Sq, H, D = q.shape
    _, Sk, KH, _ = k.shape
    G = H // KH
    scale = 1.0 / math.sqrt(D)
    nq, nk = Sq // qb, -(-Sk // kb)
    pad_k = nk * kb - Sk
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    qr = q.reshape(B, nq, qb, KH, G, D).transpose(3, 1, 0, 2, 4, 5)
    kr = k.reshape(B, nk, kb, KH, D).transpose(3, 0, 1, 2, 4)
    vr = v.reshape(B, nk, kb, KH, D).transpose(3, 0, 1, 2, 4)
    q_pos = q_offset + jnp.arange(nq * qb).reshape(nq, qb)
    k_pos = jnp.arange(nk * kb).reshape(nk, kb)
    k_valid = (jnp.arange(nk * kb) < Sk).reshape(nk, kb)

    def h_step(_, hi):
        qh, kh, vh = hi
        kh_t = kh.transpose(1, 0, 2, 3)
        vh_t = vh.transpose(1, 0, 2, 3)

        def q_step(_, qi):
            qblk, qp = qi

            def kv_step(carry, ki):
                m, l, acc = carry
                kblk, vblk, kp, kval = ki
                s = jnp.einsum("bqgd,bkd->bgqk", qblk, kblk,
                               preferred_element_type=jnp.float32) * scale
                mask = kval[None, :]
                if causal:
                    mask = mask & (qp[:, None] >= kp[None, :])
                if window is not None:
                    mask = mask & (qp[:, None] - kp[None, :] < window)
                s = jnp.where(mask[None, None], s, -jnp.inf)
                m_new = jnp.maximum(m, jnp.max(s, axis=-1))
                m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
                p = jnp.exp(s - m_safe[..., None])
                p = jnp.where(mask[None, None], p, 0.0)
                corr = jnp.where(jnp.isneginf(m), 0.0, jnp.exp(m - m_safe))
                l_new = l * corr + jnp.sum(p, axis=-1)
                acc_new = acc * corr[..., None] + jnp.einsum(
                    "bgqk,bkd->bgqd", p.astype(vblk.dtype), vblk,
                    preferred_element_type=jnp.float32)
                return (m_new, l_new, acc_new), None

            m0 = jnp.full((B, G, qb), -jnp.inf, jnp.float32)
            l0 = jnp.zeros((B, G, qb), jnp.float32)
            a0 = jnp.zeros((B, G, qb, D), jnp.float32)
            (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0),
                                          (kh_t, vh_t, k_pos, k_valid))
            out = acc / jnp.maximum(l, 1e-30)[..., None]
            m_s = jnp.where(jnp.isneginf(m), 0.0, m)
            L = m_s + jnp.log(jnp.maximum(l, 1e-30))     # (B, G, qb)
            return None, (out.transpose(0, 2, 1, 3), L.transpose(0, 2, 1))

        _, (blocks, Ls) = jax.lax.scan(q_step, None, (qh, q_pos))
        return None, (blocks, Ls)

    _, (hb, hL) = jax.lax.scan(h_step, None, (qr, kr, vr))
    out = hb.transpose(2, 1, 3, 0, 4, 5).reshape(B, nq * qb, H, D)
    L = hL.transpose(2, 1, 3, 0, 4).reshape(B, nq * qb, H)
    return out.astype(q.dtype), L


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def flash_attention_diff(q, k, v, q_offset, causal: bool = True,
                         window: Optional[int] = None, q_block: int = 256,
                         kv_block: int = 512):
    """Differentiable flash attention. Same semantics as blocked_attention
    (softcap unsupported — callers keep the plain path for softcap archs)."""
    return blocked_attention(q, k, v, causal=causal, window=window,
                             q_block=q_block, kv_block=kv_block,
                             q_offset=q_offset)


def _fad_fwd(q, k, v, q_offset, causal, window, q_block, kv_block):
    B, Sq, H, D = q.shape
    G = H // k.shape[2]
    kb = min(kv_block, k.shape[1])
    budget = 4 * 1024 * 1024
    qb_fit = max(budget // (max(B, 1) * max(G, 1) * kb * 4), 1)
    qb_fit = 1 << (qb_fit.bit_length() - 1)
    qb = min(max(q_block, qb_fit), 1024, Sq)
    pad_q = (-Sq) % qb
    qp = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0))) if pad_q else q
    out, L = _flash_fwd_stats(qp, k, v, causal, window, q_offset, qb, kb)
    out = out[:, :Sq]
    L = L[:, :Sq]
    return out, (q, k, v, out, L, q_offset)


def _fad_bwd(causal, window, q_block, kv_block, res, dout):
    q, k, v, out, L, q_offset = res
    B, Sq, H, D = q.shape
    _, Sk, KH, _ = k.shape
    G = H // KH
    scale = 1.0 / math.sqrt(D)
    # row-block backward over the full Sk: size qb so the (B, G, qb, Sk)
    # s/p/ds tiles stay VMEM-resident
    budget = 4 * 1024 * 1024
    qb_fit = max(budget // (max(B, 1) * max(G, 1) * Sk * 4), 1)
    qb = min(max(1 << (qb_fit.bit_length() - 1), 16), 128, Sq)
    pad_q = (-Sq) % qb
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
        out = jnp.pad(out, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
        dout = jnp.pad(dout, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
        L = jnp.pad(L, ((0, 0), (0, pad_q), (0, 0)))
    nq = q.shape[1] // qb
    # D_i = rowsum(dO * O) (the softmax-jacobian diagonal term)
    Drow = jnp.sum(dout.astype(jnp.float32) * out.astype(jnp.float32), -1)

    qr = q.reshape(B, nq, qb, KH, G, D).transpose(3, 1, 0, 2, 4, 5)
    dor = dout.reshape(B, nq, qb, KH, G, D).transpose(3, 1, 0, 2, 4, 5)
    Lr = L.reshape(B, nq, qb, KH, G).transpose(3, 1, 0, 2, 4)
    Dr = Drow.reshape(B, nq, qb, KH, G).transpose(3, 1, 0, 2, 4)
    kr = k.transpose(2, 0, 1, 3)                      # (KH, B, Sk, D)
    vr = v.transpose(2, 0, 1, 3)
    q_pos = q_offset + jnp.arange(nq * qb).reshape(nq, qb)
    k_pos = jnp.arange(Sk)

    def h_step(_, hi):
        qh, doh, Lh, Dh, kh, vh = hi      # per kv-head

        def q_step(carry, qi):
            dk_acc, dv_acc = carry         # (B, Sk, D) f32
            qblk, doblk, Lblk, Dblk, qp = qi
            s = jnp.einsum("bqgd,bkd->bgqk", qblk, kh,
                           preferred_element_type=jnp.float32) * scale
            mask = jnp.ones((qb, Sk), bool)
            if causal:
                mask = mask & (qp[:, None] >= k_pos[None, :])
            if window is not None:
                mask = mask & (qp[:, None] - k_pos[None, :] < window)
            Lg = Lblk.transpose(0, 2, 1)[..., None]     # (B, G, qb, 1)
            p = jnp.where(mask[None, None], jnp.exp(s - Lg), 0.0)
            dv_acc = dv_acc + jnp.einsum(
                "bgqk,bqgd->bkd", p.astype(doblk.dtype), doblk,
                preferred_element_type=jnp.float32)
            dp = jnp.einsum("bqgd,bkd->bgqk", doblk, vh,
                            preferred_element_type=jnp.float32)
            Dg = Dblk.transpose(0, 2, 1)[..., None]
            ds = p * (dp - Dg) * scale
            dk_acc = dk_acc + jnp.einsum(
                "bgqk,bqgd->bkd", ds.astype(qblk.dtype), qblk,
                preferred_element_type=jnp.float32)
            dq_blk = jnp.einsum("bgqk,bkd->bqgd", ds.astype(kh.dtype), kh,
                                preferred_element_type=jnp.float32)
            return (dk_acc, dv_acc), dq_blk

        z = jnp.zeros((B, Sk, D), jnp.float32)
        (dk_h, dv_h), dq_blocks = jax.lax.scan(
            q_step, (z, z), (qh, doh, Lh, Dh, q_pos))
        return None, (dq_blocks, dk_h, dv_h)

    _, (dqb, dkh, dvh) = jax.lax.scan(
        h_step, None, (qr, dor, Lr, Dr, kr, vr))
    dq = dqb.transpose(2, 1, 3, 0, 4, 5).reshape(B, nq * qb, H, D)[:, :Sq]
    dk = dkh.transpose(1, 2, 0, 3)                    # (B, Sk, KH, D)
    dv = dvh.transpose(1, 2, 0, 3)
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
            None)


flash_attention_diff.defvjp(_fad_fwd, _fad_bwd)


def masked_decode_attention(q, k_cache, v_cache, valid, *,
                            softcap: Optional[float] = None):
    """The ONE masked single-step attention core every decode path shares.

    q: (B, H, D); caches: (B, Smax, KH, D); valid: (B, Smax) bool — which
    cache positions participate. Callers build ``valid`` from their own
    bookkeeping (prefix length, sliding window over a ring buffer, paged
    block tables); the attention math itself is identical, which is what
    makes dense/windowed/paged parity *bitwise* rather than approximate.
    """
    B, Smax, KH, D = k_cache.shape
    H = q.shape[1]
    G = H // KH
    scale = 1.0 / math.sqrt(D)
    qf = q.reshape(B, KH, G, D).astype(jnp.float32)
    s = jnp.einsum("bhgd,bkhd->bhgk", qf, k_cache.astype(jnp.float32)) * scale
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    s = jnp.where(valid[:, None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgk,bkhd->bhgd", p, v_cache.astype(jnp.float32))
    return out.reshape(B, H, D).astype(q.dtype)


def decode_attention(q, k_cache, v_cache, cache_len, *, window: Optional[int] = None,
                     softcap: Optional[float] = None):
    """Single-step attention against a cache.

    q: (B, H, D); caches: (B, Smax, KH, D); cache_len: (B,) valid lengths
    (the new token's k/v must already be written at cache_len-1).
    """
    Smax = k_cache.shape[1]
    pos = jnp.arange(Smax)[None, :]                        # (1, Smax)
    valid = pos < cache_len[:, None]
    if window is not None:
        valid = valid & (pos >= cache_len[:, None] - window)
    return masked_decode_attention(q, k_cache, v_cache, valid,
                                   softcap=softcap)
