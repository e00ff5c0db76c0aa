"""Multi-head Latent Attention (DeepSeek-V2), with compressed-KV cache.

MLA projects hidden states into a low-rank KV latent (kv_lora_rank) plus a
shared rope key; per-head K/V are decompressed from the latent. The decode
cache stores only (latent, k_rope) — the paper-relevant 8-9x KV compression.
With YaRN (``cfg.rope_scaling``) the rope parts take the scaled frequencies
and the softmax scale gains mscale(factor, mscale_all_dim)^2, as DeepSeek-V2
publishes it.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.models.config import ArchConfig
from repro.models.layers import (apply_rope, blocked_attention, dense_init,
                                 init_rmsnorm, rmsnorm, yarn_mscale)


def init_mla(key, cfg: ArchConfig) -> dict:
    m = cfg.mla
    d, H = cfg.d_model, cfg.n_heads
    dt = cfg.p_dtype()
    qk_head = m.qk_nope_head_dim + m.qk_rope_head_dim
    ks = jax.random.split(key, 6)
    p = {
        # queries: full rank for V2-Lite (q_lora_rank == 0)
        "wq": dense_init(ks[0], (d, H * qk_head), dt),
        # joint latent projection: [kv latent | shared rope key]
        "w_dkv": dense_init(ks[1], (d, m.kv_lora_rank + m.qk_rope_head_dim), dt),
        "kv_norm": init_rmsnorm(m.kv_lora_rank, dt),
        # decompression
        "w_uk": dense_init(ks[2], (m.kv_lora_rank, H * m.qk_nope_head_dim), dt),
        "w_uv": dense_init(ks[3], (m.kv_lora_rank, H * m.v_head_dim), dt),
        "wo": dense_init(ks[4], (H * m.v_head_dim, d), dt),
    }
    return p


def _mla_qkv(params, cfg: ArchConfig, x, positions):
    m = cfg.mla
    B, S, _ = x.shape
    H = cfg.n_heads
    q = jnp.einsum("bsd,de->bse", x, params["wq"]).reshape(
        B, S, H, m.qk_nope_head_dim + m.qk_rope_head_dim)
    q_nope, q_rope = jnp.split(q, [m.qk_nope_head_dim], axis=-1)
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta, cfg.rope_scaling)

    dkv = jnp.einsum("bsd,de->bse", x, params["w_dkv"])
    latent, k_rope = jnp.split(dkv, [m.kv_lora_rank], axis=-1)
    latent = rmsnorm(params["kv_norm"], latent, cfg.norm_eps)
    k_rope = apply_rope(k_rope[:, :, None, :], positions, cfg.rope_theta,
                        cfg.rope_scaling)                        # (B,S,1,r)
    return q_nope, q_rope, latent, k_rope


def _yarn_softmax_factor(cfg: ArchConfig) -> float:
    """What YaRN multiplies the 1/sqrt(head dim) softmax scale by."""
    rs = cfg.rope_scaling
    if rs is None or not rs.mscale_all_dim:
        return 1.0
    return yarn_mscale(rs.factor, rs.mscale_all_dim) ** 2


def _decompress(params, cfg: ArchConfig, latent):
    m = cfg.mla
    B, S, _ = latent.shape
    H = cfg.n_heads
    k_nope = jnp.einsum("bsr,re->bse", latent, params["w_uk"]).reshape(
        B, S, H, m.qk_nope_head_dim)
    v = jnp.einsum("bsr,re->bse", latent, params["w_uv"]).reshape(
        B, S, H, m.v_head_dim)
    return k_nope, v


def mla_fwd(params, cfg: ArchConfig, x, positions=None):
    """Full-sequence MLA (training / prefill). Returns (out, cache_entries)."""
    m = cfg.mla
    B, S, _ = x.shape
    H = cfg.n_heads
    if positions is None:
        positions = jnp.arange(S)[None, :].astype(jnp.int32)
    q_nope, q_rope, latent, k_rope = _mla_qkv(params, cfg, x, positions)
    k_nope, v = _decompress(params, cfg, latent)
    # assemble per-head q/k with shared rope part broadcast over heads
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(
        k_rope, (B, S, H, m.qk_rope_head_dim))], axis=-1)
    # pad v to qk head dim so the blocked kernel is reusable, then slice back
    pad = q.shape[-1] - m.v_head_dim
    v_p = jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, pad)))
    scale = _yarn_softmax_factor(cfg) / math.sqrt(q.shape[-1])
    out = blocked_attention(q, k, v_p, causal=True,
                            scale=scale)[..., :m.v_head_dim]
    out = jnp.einsum("bse,ed->bsd", out.reshape(B, S, -1), params["wo"])
    return out, (latent, k_rope[:, :, 0, :])


def init_mla_cache(cfg: ArchConfig, batch: int, max_len: int):
    m = cfg.mla
    dt = cfg.act_dtype()
    return {
        "latent": jnp.zeros((batch, max_len, m.kv_lora_rank), dt),
        "k_rope": jnp.zeros((batch, max_len, m.qk_rope_head_dim), dt),
    }


def init_paged_mla_cache(cfg: ArchConfig, batch: int, max_len: int,
                         page_size: int, n_pages: int):
    """Paged compressed cache for ONE MLA layer: shared page pools for the
    latent and the rope key (page 0 = NULL, all-zeros) plus the per-row
    ``bt`` block table — same discipline as
    ``attention.init_paged_kv_cache``."""
    if max_len % page_size != 0:
        raise ValueError(f"max_len={max_len} must be a multiple of "
                         f"page_size={page_size} (bitwise paged/dense "
                         f"parity needs the gathered span == max_len)")
    m = cfg.mla
    dt = cfg.act_dtype()
    return {
        "latent": jnp.zeros((n_pages, page_size, m.kv_lora_rank), dt),
        "k_rope": jnp.zeros((n_pages, page_size, m.qk_rope_head_dim), dt),
        "bt": jnp.zeros((batch, max_len // page_size), jnp.int32),
    }


def _mla_attend(params, cfg: ArchConfig, q_nope, q_rope, lat_cache,
                kr_cache, valid, dtype):
    """The post-write absorbed-decode math, shared by the dense and paged
    paths: identical cache bytes -> bitwise-identical output."""
    m = cfg.mla
    B = q_nope.shape[0]
    H = cfg.n_heads
    # score = q_nope·(W_uk latent) + q_rope·k_rope
    # absorb W_uk into q (the standard MLA decode trick): q_abs (B,H,r)
    w_uk = params["w_uk"].reshape(m.kv_lora_rank, H, m.qk_nope_head_dim)
    q_abs = jnp.einsum("bhd,rhd->bhr", q_nope[:, 0].astype(jnp.float32),
                       w_uk.astype(jnp.float32))
    s = jnp.einsum("bhr,bsr->bhs", q_abs, lat_cache.astype(jnp.float32))
    s = s + jnp.einsum("bhr,bsr->bhs", q_rope[:, 0].astype(jnp.float32),
                       kr_cache.astype(jnp.float32))
    s = s / math.sqrt(m.qk_nope_head_dim + m.qk_rope_head_dim)
    if cfg.rope_scaling is not None:
        s = s * _yarn_softmax_factor(cfg)
    s = jnp.where(valid[:, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    # out = p · V = p · (W_uv latent); absorb W_uv on the way out
    ctx = jnp.einsum("bhs,bsr->bhr", p, lat_cache.astype(jnp.float32))
    w_uv = params["w_uv"].reshape(m.kv_lora_rank, H, m.v_head_dim)
    out = jnp.einsum("bhr,rhd->bhd", ctx, w_uv.astype(jnp.float32))
    out = out.reshape(B, -1).astype(dtype)
    return jnp.einsum("be,ed->bd", out, params["wo"])


def mla_decode(params, cfg: ArchConfig, x, cache, step):
    """One-token MLA decode against the compressed cache (dense
    {latent, k_rope}, or paged {latent pool, k_rope pool, bt} — detected by
    the ``bt`` key). ``step`` is the scalar absolute position, or a (B,)
    int32 vector of per-row positions (continuous-batching decode); the
    scalar path is untouched for bitwise parity with the step-synchronous
    servers."""
    B = x.shape[0]
    per_row = jnp.ndim(step) == 1
    pos = step[:, None] if per_row else jnp.full((B, 1), step, jnp.int32)
    q_nope, q_rope, latent, k_rope = _mla_qkv(params, cfg, x, pos)
    if "bt" in cache:
        from repro.kernels import dispatch
        bt = cache["bt"]
        M, page = bt.shape[1], cache["latent"].shape[1]
        pos_vec = step if per_row else jnp.full((B,), step, jnp.int32)
        glat, gkr, lat_pool, kr_pool = dispatch.paged_gather_append(
            cache["latent"], cache["k_rope"], latent[:, 0], k_rope[:, 0, 0, :],
            bt, pos_vec, backend=dispatch.kernel_backend())
        L = M * page
        lat_cache = glat.reshape(B, L, -1)
        kr_cache = gkr.reshape(B, L, -1)
        # sentinel rows (pos >= L) attend over all-zero pages with an
        # all-true mask: finite garbage on a discarded row, never NaN
        valid = (jnp.arange(L)[None, :] <= pos_vec[:, None]) | (
            pos_vec[:, None] >= L)
        out = _mla_attend(params, cfg, q_nope, q_rope, lat_cache, kr_cache,
                          valid, x.dtype)
        return out[:, None, :], {"latent": lat_pool, "k_rope": kr_pool,
                                 "bt": bt}
    if per_row:
        rows = jnp.arange(B, dtype=jnp.int32)
        lat_cache = cache["latent"].at[rows, step].set(latent[:, 0])
        kr_cache = cache["k_rope"].at[rows, step].set(k_rope[:, 0, 0, :])
    else:
        lat_cache = jax.lax.dynamic_update_slice(cache["latent"], latent,
                                                 (0, step, 0))
        kr_cache = jax.lax.dynamic_update_slice(cache["k_rope"],
                                                k_rope[:, :, 0, :],
                                                (0, step, 0))
    Smax = lat_cache.shape[1]
    valid = (jnp.arange(Smax)[None, :] <= step[:, None] if per_row
             else jnp.broadcast_to(jnp.arange(Smax) <= step, (B, Smax)))
    out = _mla_attend(params, cfg, q_nope, q_rope, lat_cache, kr_cache,
                      valid, x.dtype)
    return out[:, None, :], {"latent": lat_cache, "k_rope": kr_cache}
