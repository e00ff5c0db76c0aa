"""Backbone assembly: pattern-scanned layer stacks with staged execution.

The layer stack is organised as
    [first_k_dense unrolled layers] ++ [n_superblocks x pattern (lax.scan)]
    ++ [remainder unrolled layers]
so that 64-layer models lower as a single scanned superblock body, and the
early-exit stage boundary can slice the scanned stack at superblock
granularity (ATHEENA stage partitioning).

Three execution modes share the block code:
    mode="train"   full sequence, no cache returned
    mode="prefill" full sequence, caches returned
    mode="decode"  one token against caches (step = absolute position)
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models import hints
from repro.models import mamba2 as m2
from repro.models import mla as mla_mod
from repro.models import moe as moe_mod
from repro.models import rglru as rg
from repro.models.config import ArchConfig
from repro.models.layers import (embed, init_embedding, init_mlp, init_rmsnorm,
                                 mlp, rmsnorm, unembed)


# ----------------------------------------------------------------------------
# per-block init / apply
# ----------------------------------------------------------------------------

def _init_block(key, cfg: ArchConfig, kind: str, *, dense_mlp: bool = False,
                cross: bool = False) -> dict:
    """One backbone block of the given kind."""
    dt = cfg.p_dtype()
    d = cfg.d_model
    ks = jax.random.split(key, 8)
    p: Dict[str, Any] = {"norm1": init_rmsnorm(d, dt)}
    if kind in ("attn", "lattn"):
        if cfg.mla is not None and kind == "attn":
            p["attn"] = mla_mod.init_mla(ks[0], cfg)
        else:
            p["attn"] = attn.init_attention(ks[0], cfg)
    elif kind == "mamba2":
        p["mixer"] = m2.init_mamba2(ks[0], cfg)
    elif kind == "rglru":
        p["mixer"] = rg.init_rglru(ks[0], cfg)
    else:
        raise ValueError(kind)
    if cross:
        p["norm_x"] = init_rmsnorm(d, dt)
        p["cross"] = attn.init_attention(ks[3], cfg)
    if cfg.d_ff > 0 or (cfg.moe and not dense_mlp):
        p["norm2"] = init_rmsnorm(d, dt)
        if cfg.moe is not None and not dense_mlp:
            p["moe"] = moe_mod.init_moe(ks[1], cfg)
        else:
            ff = cfg.dense_ff if (dense_mlp and cfg.dense_ff) else cfg.d_ff
            p["mlp"] = init_mlp(ks[1], d, ff, cfg.mlp_act, dt)
    return p


def _init_block_cache(cfg: ArchConfig, kind: str, batch: int, max_len: int,
                      cross_len: int = 0) -> dict:
    if kind == "attn":
        if cfg.mla is not None:
            c = mla_mod.init_mla_cache(cfg, batch, max_len)
        else:
            c = attn.init_kv_cache(cfg, batch, max_len)
    elif kind == "lattn":
        c = attn.init_kv_cache(cfg, batch, max_len, window=cfg.window)
    elif kind == "mamba2":
        c = m2.init_mamba2_state(cfg, batch)
    elif kind == "rglru":
        c = rg.init_rglru_state(cfg, batch)
    else:
        raise ValueError(kind)
    if cross_len:
        hd = cfg.resolved_head_dim
        c = dict(c)
        c["xk"] = jnp.zeros((batch, cross_len, cfg.n_kv_heads, hd), cfg.act_dtype())
        c["xv"] = jnp.zeros((batch, cross_len, cfg.n_kv_heads, hd), cfg.act_dtype())
    return c


def _apply_block(params, cfg: ArchConfig, kind: str, h, *, mode: str,
                 cache=None, step=None, causal: bool = True,
                 memory=None, dense_mlp: bool = False):
    """Returns (h, new_cache, aux): the load-balance loss in ``train``,
    else an MoE block's load counters (zero for other blocks)."""
    aux = jnp.zeros((), jnp.float32)
    x = rmsnorm(params["norm1"], h, cfg.norm_eps)
    new_cache = cache
    if kind in ("attn", "lattn"):
        window = cfg.window if kind == "lattn" else None
        if cfg.mla is not None and kind == "attn":
            if mode == "decode":
                y, new_cache = mla_mod.mla_decode(params["attn"], cfg, x,
                                                  cache, step)
            else:
                y, (latent, k_rope) = mla_mod.mla_fwd(params["attn"], cfg, x)
                if mode == "prefill":
                    new_cache = {"latent": latent, "k_rope": k_rope}
        elif mode == "decode":
            y, kv = attn.attention_decode(params["attn"], cfg, x, cache, step,
                                          window=window if kind == "lattn" else None)
            new_cache = dict(cache)
            new_cache.update(kv)
        else:
            y, (k, v) = attn.attention_fwd(
                params["attn"], cfg, x, window=window, causal=causal,
                # the Pallas kernel is the TPU hot path; on the CPU host
                # (tests + dry-run) the lowered path is the jnp block scan —
                # interpret-mode pallas lowers refs as full-array copies,
                # which misrepresents the kernel's VMEM behaviour.
                use_kernel=(mode == "prefill" and
                            jax.default_backend() != "cpu"))
            if mode == "prefill":
                new_cache = {"k": k, "v": v}
                if kind == "lattn" and cfg.window and k.shape[1] > cfg.window:
                    # ring-buffer layout: decode expects position P at slot
                    # P % window; the last-window slice holds positions
                    # [S-w, S) contiguously, so rotate right by S % w.
                    r = k.shape[1] % cfg.window
                    new_cache = {
                        "k": jnp.roll(k[:, -cfg.window:], r, axis=1),
                        "v": jnp.roll(v[:, -cfg.window:], r, axis=1),
                    }
    elif kind == "mamba2":
        if mode == "decode":
            y, new_cache = m2.mamba2_decode(params["mixer"], cfg, x, cache)
        else:
            y, st = m2.mamba2_fwd(params["mixer"], cfg, x)
            new_cache = st if mode == "prefill" else None
    elif kind == "rglru":
        if mode == "decode":
            y, new_cache = rg.rglru_decode(params["mixer"], cfg, x, cache)
        else:
            y, st = rg.rglru_fwd(params["mixer"], cfg, x)
            new_cache = st if mode == "prefill" else None
    h = h + y
    if "cross" in params and (memory is not None or mode == "decode"):
        x = rmsnorm(params["norm_x"], h, cfg.norm_eps)
        if mode == "decode":
            xk, xv = cache["xk"], cache["xv"]
            hd = cfg.resolved_head_dim
            B = x.shape[0]
            q = jnp.einsum("bsd,de->bse", x, params["cross"]["wq"])
            if cfg.qkv_bias:
                q = q + params["cross"]["bq"]
            q = q.reshape(B, cfg.n_heads, hd)
            from repro.models.layers import decode_attention
            clen = jnp.full((B,), xk.shape[1], jnp.int32)
            y = decode_attention(q, xk, xv, clen)
            y = jnp.einsum("be,ed->bd", y.reshape(B, -1),
                           params["cross"]["wo"])[:, None]
        else:
            kv = attn.cross_kv(params["cross"], cfg, memory)
            y, _ = attn.attention_fwd(params["cross"], cfg, x, kv=kv)
            if mode == "prefill":
                new_cache = dict(new_cache or {})
                new_cache["xk"], new_cache["xv"] = kv
        h = h + y
    if "moe" in params:
        x = rmsnorm(params["norm2"], h, cfg.norm_eps)
        y, loss, load = moe_mod.moe_fwd(params["moe"], cfg, x)
        # training sums the load-balance loss; serving sums the load
        # counters (moe.LOAD_FIELDS), which no dense block adds to
        aux = loss if mode == "train" else load
        h = h + y
    elif "mlp" in params:
        x = rmsnorm(params["norm2"], h, cfg.norm_eps)
        ff = cfg.dense_ff if (dense_mlp and cfg.dense_ff) else cfg.d_ff
        h = h + mlp(params["mlp"], x, cfg.mlp_act)
    if mode != "decode" and "moe" not in params:
        # Megatron-SP residual layout. MoE blocks are exempt: the routed
        # all-to-all wants token-sharded layouts and the seq constraint
        # forces extra gathers around the dispatch (measured regression:
        # grok train t_coll 228 -> 359 s with the constraint applied).
        h = hints.constrain_seq(h)
    return h, new_cache, aux


# ----------------------------------------------------------------------------
# stack init
# ----------------------------------------------------------------------------

def init_params(key, cfg: ArchConfig, *, decoder_cross: bool = False) -> dict:
    """Full parameter pytree for the decoder-only (or decoder-side) backbone.
    For encdec archs this also builds the encoder stack."""
    ks = jax.random.split(key, 16)
    p: Dict[str, Any] = {"embed": init_embedding(ks[0], cfg.vocab, cfg.d_model,
                                                 cfg.p_dtype())}
    cross = cfg.encdec or decoder_cross

    # leading dense layers (unrolled)
    p["first"] = [
        _init_block(jax.random.fold_in(ks[1], i), cfg, cfg.layer_kind(i),
                    dense_mlp=True, cross=cross)
        for i in range(cfg.first_k_dense)
    ]

    # scanned superblocks: one stacked param set per pattern position
    def stack_init(pos: int):
        kind = cfg.pattern[pos]
        def one(i):
            return _init_block(jax.random.fold_in(ks[2], pos * 10_000 + i),
                               cfg, kind, cross=cross)
        return jax.tree.map(lambda *xs: jnp.stack(xs),
                            *[one(i) for i in range(cfg.n_superblocks)]) \
            if cfg.n_superblocks else None

    p["blocks"] = tuple(stack_init(pos) for pos in range(cfg.pattern_len))

    # remainder (unrolled)
    p["rem"] = [
        _init_block(jax.random.fold_in(ks[3], i), cfg, cfg.pattern[i], cross=cross)
        for i in range(cfg.n_remainder)
    ]

    p["final_norm"] = init_rmsnorm(cfg.d_model, cfg.p_dtype())
    if not cfg.tie_embeddings:
        from repro.models.layers import dense_init
        p["head"] = dense_init(ks[4], (cfg.d_model, cfg.vocab), cfg.p_dtype())

    if cfg.encdec:
        enc_cfg = cfg.replace(encdec=False, pattern=("attn",), first_k_dense=0,
                              n_layers=cfg.n_enc_layers)
        enc_stack = jax.tree.map(
            lambda *xs: jnp.stack(xs),
            *[_init_block(jax.random.fold_in(ks[5], i), enc_cfg, "attn")
              for i in range(cfg.n_enc_layers)])
        enc = {"blocks": (enc_stack,),
               "final_norm": init_rmsnorm(cfg.d_model, cfg.p_dtype())}
        p["encoder"] = enc
    return p


def param_shapes(cfg: ArchConfig):
    """ShapeDtypeStruct pytree — no allocation (dry-run path)."""
    return jax.eval_shape(lambda k: init_params(k, cfg),
                          jax.ShapeDtypeStruct((2,), jnp.uint32))


# ----------------------------------------------------------------------------
# cache init
# ----------------------------------------------------------------------------

def init_cache(cfg: ArchConfig, batch: int, max_len: int,
               cross_len: int = 0) -> dict:
    """Cache pytree matching the param layout."""
    c: Dict[str, Any] = {}
    c["first"] = [_init_block_cache(cfg, cfg.layer_kind(i), batch, max_len,
                                    cross_len)
                  for i in range(cfg.first_k_dense)]

    def stack_cache(pos: int):
        kind = cfg.pattern[pos]
        if cfg.n_superblocks == 0:
            return None
        one = _init_block_cache(cfg, kind, batch, max_len, cross_len)
        return jax.tree.map(
            lambda x: jnp.broadcast_to(x, (cfg.n_superblocks,) + x.shape), one)

    c["blocks"] = tuple(stack_cache(pos) for pos in range(cfg.pattern_len))
    c["rem"] = [_init_block_cache(cfg, cfg.pattern[i], batch, max_len, cross_len)
                for i in range(cfg.n_remainder)]
    return c


def cache_shapes(cfg: ArchConfig, batch: int, max_len: int, cross_len: int = 0):
    return jax.eval_shape(lambda: init_cache(cfg, batch, max_len, cross_len))


def pad_caches(cfg: ArchConfig, caches, max_len: int):
    """Grow prefill caches along their time axis to ``max_len`` so decode
    steps have slots to write into. Windowed (lattn) caches stay at window
    size (ring buffer; prefill rotates them onto the P % window slot
    layout); recurrent states (mamba2/rglru) have no time axis.
    """
    def pad_axis(x, axis, target):
        cur = x.shape[axis]
        if cur >= target:
            return x
        pads = [(0, 0)] * x.ndim
        pads[axis] = (0, target - cur)
        return jnp.pad(x, pads)

    def pad_block(c, kind):
        if c is None:
            return None
        c = dict(c)
        if kind in ("attn", "lattn"):
            if "latent" in c:                       # MLA compressed cache
                c["latent"] = pad_axis(c["latent"], -2, max_len)
                c["k_rope"] = pad_axis(c["k_rope"], -2, max_len)
            else:
                tgt = min(max_len, cfg.window) if (
                    kind == "lattn" and cfg.window) else max_len
                c["k"] = pad_axis(c["k"], -3, tgt)
                c["v"] = pad_axis(c["v"], -3, tgt)
        return c

    out = {"first": [pad_block(c, cfg.layer_kind(i))
                     for i, c in enumerate(caches["first"])],
           "blocks": None, "rem": [pad_block(c, cfg.pattern[i])
                                   for i, c in enumerate(caches["rem"])]}
    if caches["blocks"] is not None:
        out["blocks"] = tuple(
            pad_block(caches["blocks"][pos], cfg.pattern[pos])
            for pos in range(len(caches["blocks"])))
    return out


# ----------------------------------------------------------------------------
# staged backbone execution
# ----------------------------------------------------------------------------

def embed_tokens(params, cfg: ArchConfig, tokens, frontend_embeds=None):
    """tokens: (B, S) int32. For vlm archs, frontend_embeds (B, P, d) replace
    the first P positions (image patches). For audio decode-side, tokens embed
    normally (the encoder consumes frontend embeds directly)."""
    h = embed(params["embed"], tokens).astype(cfg.act_dtype())
    if frontend_embeds is not None and cfg.frontend == "vit_stub":
        P = frontend_embeds.shape[1]
        h = jnp.concatenate([frontend_embeds.astype(h.dtype), h[:, P:]], axis=1)
    return h


def run_layers(params, cfg: ArchConfig, h, lo: int, hi: int, *, mode: str,
               caches=None, step=None, memory=None, causal: bool = True,
               cache_base_sb: int = 0, param_base_sb: int = 0):
    """Run backbone layers [lo, hi). lo/hi must land on superblock boundaries
    (or 0 / n_layers). Returns (h, new_caches_for_segment, aux).

    ``cache_base_sb``: when the caller passes a PRE-SLICED segment cache
    (ee.split_caches output), the superblock index its 'blocks' leaves start
    at — run_layers subtracts it before slicing. ``param_base_sb`` is the
    same offset for a PRE-SLICED param tree (ee.split_params output, a
    stage's resident slice on its own submesh).

    The stacked superblock params are never copied in ``decode`` and
    ``prefill``: the scan runs over layer indices and each iteration reads
    its layer from the stack in place. Only ``train`` slices the stack to
    [lo, hi) and scans over the slice, so that the backward pass
    accumulates a segment-sized gradient."""
    aux = jnp.zeros((), jnp.float32)
    new_caches: Dict[str, Any] = {"first": [], "blocks": None, "rem": []}

    # --- leading dense layers ------------------------------------------------
    for i in range(cfg.first_k_dense):
        if lo <= i < hi:
            c = caches["first"][i] if caches else None
            h, nc, a = _apply_block(params["first"][i], cfg, cfg.layer_kind(i), h,
                                    mode=mode, cache=c, step=step, causal=causal,
                                    memory=memory, dense_mlp=True)
            new_caches["first"].append(nc)
            aux = aux + a

    # --- scanned superblocks --------------------------------------------------
    pl = cfg.pattern_len
    s_lo = max(0, (lo - cfg.first_k_dense + pl - 1) // pl)
    s_hi_layer = min(hi, cfg.first_k_dense + cfg.n_superblocks * pl)
    s_hi = max(s_lo, (s_hi_layer - cfg.first_k_dense) // pl)
    if s_hi > s_lo and cfg.n_superblocks:
        p_lo, p_hi = s_lo - param_base_sb, s_hi - param_base_sb
        if mode == "train":
            # a sliced xs keeps the reverse scan's weight gradients
            # segment-sized; a closed-over stack carries full-stack ones,
            # which float32 programs pay for in temporaries, and rounds
            # the gradients differently on the chip
            param_xs = jax.tree.map(lambda x: x[p_lo:p_hi], params["blocks"])
            layer_params = lambda bp: bp
        else:
            # index the stack in place: a sliced xs is materialized as a
            # segment-sized copy before the loop on every call
            param_xs = jnp.arange(p_lo, p_hi, dtype=jnp.int32)
            layer_params = lambda i: jax.tree.map(
                lambda x: jax.lax.dynamic_index_in_dim(
                    x, i, keepdims=False, allow_negative_indices=False),
                params["blocks"])
        c_lo, c_hi = s_lo - cache_base_sb, s_hi - cache_base_sb
        seg_caches = (jax.tree.map(lambda x: x[c_lo:c_hi], caches["blocks"])
                      if caches else None)

        def body(carry, xs):
            hh = carry
            bp, bc = xs
            bp = layer_params(bp)
            a_tot = jnp.zeros((), jnp.float32)
            ncs = []
            for pos in range(pl):
                c = bc[pos] if bc is not None else None
                hh, nc, a = _apply_block(bp[pos], cfg, cfg.pattern[pos], hh,
                                         mode=mode, cache=c, step=step,
                                         causal=causal, memory=memory)
                ncs.append(nc)
                a_tot = a_tot + a
            return hh, (tuple(ncs) if mode != "train" else None, a_tot)

        if mode == "train":
            body_fn = jax.checkpoint(body)  # remat each superblock
        else:
            body_fn = body
        h, (ncs, aux_s) = jax.lax.scan(body_fn, h, (param_xs, seg_caches))
        new_caches["blocks"] = ncs
        aux = aux + jnp.sum(aux_s, axis=0)

    # --- remainder -------------------------------------------------------------
    rem_base = cfg.first_k_dense + cfg.n_superblocks * pl
    for i in range(cfg.n_remainder):
        li = rem_base + i
        if lo <= li < hi:
            c = caches["rem"][i] if caches else None
            h, nc, a = _apply_block(params["rem"][i], cfg, cfg.pattern[i], h,
                                    mode=mode, cache=c, step=step, causal=causal,
                                    memory=memory)
            new_caches["rem"].append(nc)
            aux = aux + a
    return h, new_caches, aux


def head(params, cfg: ArchConfig, h):
    """Final norm + unembed -> fp32 logits."""
    h = rmsnorm(params["final_norm"], h, cfg.norm_eps)
    if cfg.tie_embeddings:
        return unembed(params["embed"], h)
    return jnp.einsum("...d,dv->...v", h.astype(jnp.float32),
                      params["head"].astype(jnp.float32))


def encode(params, cfg: ArchConfig, frame_embeds):
    """Encoder stack (audio family). frame_embeds: (B, F, d)."""
    enc = params["encoder"]
    h = frame_embeds.astype(cfg.act_dtype())

    def body(hh, bp):
        hh, _, _ = _apply_block(bp, cfg, "attn", hh, mode="train", causal=False)
        return hh, None

    h, _ = jax.lax.scan(body, h, enc["blocks"][0])
    return rmsnorm(enc["final_norm"], h, cfg.norm_eps)


# ----------------------------------------------------------------------------
# whole-model entry points (single-exit baseline; EE staging lives in
# core/early_exit.py and reuses run_layers over partial ranges)
# ----------------------------------------------------------------------------

def forward(params, cfg: ArchConfig, tokens, *, frontend_embeds=None):
    """Training/eval forward to final logits. Returns (logits, aux)."""
    memory = None
    if cfg.encdec:
        memory = encode(params, cfg, frontend_embeds)
        frontend_embeds = None
    h = embed_tokens(params, cfg, tokens, frontend_embeds)
    h, _, aux = run_layers(params, cfg, h, 0, cfg.n_layers, mode="train",
                           memory=memory)
    return head(params, cfg, h), aux


def forward_hidden(params, cfg: ArchConfig, tokens, *, frontend_embeds=None):
    """Forward returning final hidden states (B, S, d) — used by losses that
    chunk the unembedding."""
    memory = None
    if cfg.encdec:
        memory = encode(params, cfg, frontend_embeds)
        frontend_embeds = None
    h = embed_tokens(params, cfg, tokens, frontend_embeds)
    h, _, aux = run_layers(params, cfg, h, 0, cfg.n_layers, mode="train",
                           memory=memory)
    return h, aux


def prefill(params, cfg: ArchConfig, tokens, *, frontend_embeds=None,
            max_len: int = 0, with_load: bool = False):
    """Returns (last_logits (B, V), caches, memory), and with
    ``with_load`` the MoE layers' summed load counters after them.
    ``max_len`` > seq pads the caches so subsequent decode steps have
    write slots."""
    memory = None
    if cfg.encdec:
        memory = encode(params, cfg, frontend_embeds)
        frontend_embeds = None
    h = embed_tokens(params, cfg, tokens, frontend_embeds)
    h, caches, load = run_layers(params, cfg, h, 0, cfg.n_layers,
                                 mode="prefill", memory=memory)
    if max_len > tokens.shape[1]:
        caches = pad_caches(cfg, caches, max_len)
    out = (head(params, cfg, h[:, -1]), caches, memory)
    return out + (load,) if with_load else out


def decode_step(params, cfg: ArchConfig, token, caches, step, *, memory=None):
    """token: (B, 1) int32; step: scalar absolute position.
    Returns (logits (B, V), new_caches)."""
    h = embed_tokens(params, cfg, token)
    h, new_caches, _ = run_layers(params, cfg, h, 0, cfg.n_layers, mode="decode",
                                  caches=caches, step=step, memory=memory)
    return head(params, cfg, h[:, 0]), new_caches
