"""Analytic performance / resource model (the fpgaConvNet model analogue).

Two families:

1. **CNN pipeline model** — faithful to fpgaConvNet's folding model: each
   layer l has workload W_l (MACs/sample); with parallelism P_l (DSP-analogue
   units) its initiation interval is W_l / P_l cycles; a streaming pipeline's
   rate is clock / max_l(W_l / P_l). Resources consumed scale with sum(P_l).
   This generates the discrete TAP fronts the paper's optimizer produces, and
   is what the Table I/IV and Fig. 9 benchmarks use.

2. **TPU LM stage model** — the same three roofline terms the dry-run
   measures (compute / HBM / ICI), evaluated analytically per layer range so
   the DSE can search sharding configs quickly. The dry-run's HLO-derived
   numbers are ground truth; this model is the search heuristic.

Hardware constants (TPU v5e, per assignment): 197 TFLOP/s bf16, 819 GB/s
HBM, ~50 GB/s/link ICI.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

from repro.core.tap import DesignPoint, TAPFunction
from repro.models.cnn import CNNConfig, _stage_out_shape
from repro.models.config import ArchConfig

PEAK_FLOPS = 197e12          # bf16 / chip
HBM_BW = 819e9               # B/s / chip
ICI_BW = 50e9                # B/s / link
HBM_GB = 16.0                # v5e HBM capacity
FPGA_CLOCK = 125e6           # paper's conservative 125 MHz


# ============================================================================
# 1. CNN pipeline (fpgaConvNet folding model)
# ============================================================================

def cnn_stage_workloads(cfg: CNNConfig, stage_idx: int) -> List[float]:
    """MACs per sample for each conv/linear layer of a backbone stage."""
    h, w, c = cfg.in_shape if stage_idx == 0 else _stage_out_shape(cfg, stage_idx)
    loads = []
    st = cfg.stages[stage_idx]
    for cv in st.convs:
        s = cv.get("stride", 1)
        oh, ow = -(-h // s), -(-w // s)
        loads.append(oh * ow * cv["kernel"] ** 2 * c * cv["out"])
        h, w, c = oh, ow, cv["out"]
        if cv.get("pool"):
            h, w = h // cv["pool"], w // cv["pool"]
    if st.flatten:
        feat = h * w * c
        dims = list(st.linear) + (
            [cfg.n_classes] if stage_idx == len(cfg.stages) - 1 else [])
        din = feat
        for dout in dims:
            loads.append(din * dout)
            din = dout
    return loads


def cnn_exit_workloads(cfg: CNNConfig, exit_idx: int) -> List[float]:
    h, w, c = _stage_out_shape(cfg, exit_idx + 1)
    loads = []
    ex = cfg.exits[exit_idx]
    for cv in ex.convs:
        s = cv.get("stride", 1)
        oh, ow = -(-h // s), -(-w // s)
        loads.append(oh * ow * cv["kernel"] ** 2 * c * cv["out"])
        h, w, c = oh, ow, cv["out"]
        if cv.get("pool"):
            h, w = h // cv["pool"], w // cv["pool"]
    din = h * w * c
    for dout in list(ex.linear) + [cfg.n_classes]:
        loads.append(din * dout)
        din = dout
    return loads


def pipeline_rate(workloads: Sequence[float], parallelism: Sequence[int],
                  clock: float = FPGA_CLOCK) -> float:
    """Streaming pipeline throughput (samples/s) = clock / max II."""
    ii = max(w / max(p, 1) for w, p in zip(workloads, parallelism))
    return clock / ii


def optimal_folding(workloads: Sequence[float], budget: int,
                    levels: Optional[Sequence[int]] = None) -> List[int]:
    """Allocate parallelism units to maximize pipeline rate under
    sum(P) <= budget. Water-filling (P_l proportional to W_l) projected onto
    the discrete folding levels fpgaConvNet uses (powers of two)."""
    if levels is None:
        levels = [1 << i for i in range(11)]
    tot = sum(workloads)
    alloc = []
    for wl in workloads:
        ideal = budget * wl / tot
        lv = max(l for l in levels if l <= max(ideal, 1))
        alloc.append(lv)
    # the one-unit floor per layer can push the projection past the budget:
    # step back down the levels where the lower level hurts the rate least
    while sum(alloc) > max(budget, len(alloc)):
        lower = {i: max(l for l in levels if l < a)
                 for i, a in enumerate(alloc) if a > min(levels)}
        if not lower:
            break
        i = min(lower, key=lambda i: workloads[i] / lower[i])
        alloc[i] = lower[i]
    # greedily spend leftover budget on the bottleneck layer
    def bump(a):
        while True:
            iis = [w / p for w, p in zip(workloads, a)]
            i = iis.index(max(iis))
            nxt = next((l for l in levels if l > a[i]), None)
            if nxt is None or sum(a) - a[i] + nxt > budget:
                return a
            a[i] = nxt
    return bump(alloc)


def cnn_stage_tap(workloads: Sequence[float], budgets: Sequence[int],
                  name: str = "", clock: float = FPGA_CLOCK,
                  bram_per_unit: float = 0.25) -> TAPFunction:
    """TAP curve for one pipeline stage: for each resource budget, the best
    folding's throughput. Resource axis 0 = MAC units (DSP analogue),
    axis 1 = buffer memory (BRAM analogue, grows with parallelism)."""
    pts = []
    for b in budgets:
        alloc = optimal_folding(workloads, b)
        thr = pipeline_rate(workloads, alloc, clock)
        used = sum(alloc)
        pts.append(DesignPoint(resources=(used, used * bram_per_unit), throughput=thr,
                               meta={"folding": tuple(alloc), "budget": b}))
    return TAPFunction(pts, name=name)


# ============================================================================
# 2. TPU LM stage roofline model
# ============================================================================

@dataclass(frozen=True)
class ShardPlan:
    dp: int                  # data-parallel ways
    tp: int                  # tensor-parallel ways
    fsdp: bool = False       # shard params over dp too
    microbatch: int = 0      # 0 = no microbatching
    seq_shard: bool = False  # sequence (context) parallel for long prefill

    @property
    def chips(self) -> int:
        return self.dp * self.tp


def _layer_param_bytes(cfg: ArchConfig, kind: str, dense_mlp: bool) -> float:
    d, H, KH, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    by = 2.0  # bf16
    p = 0.0
    if kind in ("attn", "lattn"):
        if cfg.mla:
            m = cfg.mla
            qk = m.qk_nope_head_dim + m.qk_rope_head_dim
            p += d * H * qk + d * (m.kv_lora_rank + m.qk_rope_head_dim)
            p += m.kv_lora_rank * H * (m.qk_nope_head_dim + m.v_head_dim)
            p += H * m.v_head_dim * d
        else:
            p += d * H * hd + 2 * d * KH * hd + H * hd * d
    elif kind == "mamba2":
        s = cfg.ssm
        di = s.expand * d
        gn = s.n_groups * s.d_state
        p += d * (2 * di + 2 * gn + di // s.head_dim) + di * d
    elif kind == "rglru":
        w = cfg.rglru.lru_width or d
        p += 2 * d * w + 2 * w * w + w * d
    # mlp / moe
    if cfg.moe is not None and not dense_mlp:
        m = cfg.moe
        p += m.held * 3 * d * m.d_ff_expert + d * m.n_experts
        p += m.n_shared * 3 * d * m.d_ff_expert
    elif cfg.d_ff > 0 or dense_mlp:
        ff = cfg.dense_ff if (dense_mlp and cfg.dense_ff) else cfg.d_ff
        n_mats = 3 if cfg.mlp_act in ("swiglu", "geglu") else 2
        p += n_mats * d * ff
    return p * by


def _layer_flops_per_token(cfg: ArchConfig, kind: str, dense_mlp: bool,
                           ctx_len: float) -> float:
    """Matmul FLOPs per token (fwd). ctx_len = average attended length."""
    d, H, KH, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    f = 0.0
    if kind in ("attn", "lattn"):
        if cfg.mla:
            m = cfg.mla
            qk = m.qk_nope_head_dim + m.qk_rope_head_dim
            f += 2 * d * H * qk + 2 * d * (m.kv_lora_rank + m.qk_rope_head_dim)
            f += 2 * m.kv_lora_rank * H * (m.qk_nope_head_dim + m.v_head_dim)
            f += 2 * H * m.v_head_dim * d
            f += 2 * 2 * H * qk * ctx_len
        else:
            f += 2 * (d * H * hd + 2 * d * KH * hd + H * hd * d)
            f += 2 * 2 * H * hd * ctx_len          # scores + out
    elif kind == "mamba2":
        s = cfg.ssm
        di = s.expand * d
        gn = s.n_groups * s.d_state
        f += 2 * d * (2 * di + 2 * gn + di // s.head_dim) + 2 * di * d
        f += 2 * 2 * di * s.d_state                # state update + output
        f += 2 * 2 * (di // s.head_dim) * s.chunk * s.head_dim  # intra-chunk
    elif kind == "rglru":
        w = cfg.rglru.lru_width or d
        f += 2 * (2 * d * w + 2 * w * w + w * d) + 10 * w
    if cfg.moe is not None and not dense_mlp:
        m = cfg.moe
        # routed rows land on held experts in proportion to their share
        f += 2 * 3 * d * m.d_ff_expert * (m.top_k * m.held / m.n_experts
                                          + m.n_shared)
        f += 2 * d * m.n_experts                   # router
    elif cfg.d_ff > 0 or dense_mlp:
        ff = cfg.dense_ff if (dense_mlp and cfg.dense_ff) else cfg.d_ff
        n_mats = 3 if cfg.mlp_act in ("swiglu", "geglu") else 2
        f += 2 * n_mats * d * ff
    return f


def stage_params_bytes(cfg: ArchConfig, lo: int, hi: int,
                       include_embed: bool = True) -> float:
    tot = 0.0
    for i in range(lo, hi):
        tot += _layer_param_bytes(cfg, cfg.layer_kind(i), i < cfg.first_k_dense)
    if include_embed and lo == 0:
        tot += cfg.vocab * cfg.d_model * 2.0
    if hi == cfg.n_layers and not cfg.tie_embeddings:
        tot += cfg.vocab * cfg.d_model * 2.0
    if cfg.encdec and lo == 0:
        enc_layer = (2 * (cfg.d_model * cfg.n_heads * cfg.resolved_head_dim) +
                     2 * cfg.d_model * cfg.n_kv_heads * cfg.resolved_head_dim +
                     2 * cfg.d_model * cfg.d_ff) * 2.0
        tot += cfg.n_enc_layers * enc_layer
        # decoder cross-attention adds another attention block per layer
        tot += (hi - lo) * _layer_param_bytes(cfg, "attn", False) * 0.5
    return tot


def stage_flops_per_sample(cfg: ArchConfig, lo: int, hi: int, *, kind: str,
                           seq_len: int) -> float:
    """Forward matmul FLOPs per sample for layers [lo, hi).
    kind: train|prefill -> seq_len tokens, causal avg ctx seq_len/2;
          decode -> 1 token, ctx = seq_len."""
    if kind == "decode":
        n_tok, ctx = 1.0, float(seq_len)
    else:
        n_tok, ctx = float(seq_len), seq_len / 2.0
    f = 0.0
    for i in range(lo, hi):
        k = cfg.layer_kind(i)
        c = ctx if k != "lattn" else min(ctx, (cfg.window or ctx))
        if k in ("mamba2", "rglru"):
            c = 0.0
        f += n_tok * _layer_flops_per_token(cfg, k, i < cfg.first_k_dense, c)
    if lo == 0:
        if cfg.encdec:
            enc_tok = min(max(seq_len // 4, 256), 4096)
            enc_f = (2 * 4 * cfg.d_model * cfg.n_heads * cfg.resolved_head_dim +
                     2 * 2 * cfg.d_model * cfg.d_ff +
                     2 * 2 * cfg.n_heads * cfg.resolved_head_dim * enc_tok / 2)
            f += cfg.n_enc_layers * enc_tok * enc_f
    if hi == cfg.n_layers:
        f += n_tok * 2 * cfg.d_model * cfg.vocab          # unembed
    if kind == "train":
        f *= 3.0                                           # bwd ~ 2x fwd
    return f


def stage_roofline(cfg: ArchConfig, lo: int, hi: int, *, kind: str,
                   seq_len: int, batch: int, plan: ShardPlan) -> Dict[str, float]:
    """Three roofline terms (seconds per global batch) + feasibility."""
    n = plan.chips
    fl = stage_flops_per_sample(cfg, lo, hi, kind=kind, seq_len=seq_len) * batch
    pb = stage_params_bytes(cfg, lo, hi)

    # --- compute term ---
    t_comp = fl / (n * PEAK_FLOPS)

    # --- memory term: weights stream once per step + activation traffic ---
    n_tok = batch * (seq_len if kind != "decode" else 1)
    act_bytes = n_tok * cfg.d_model * 2.0 * (hi - lo) * 6      # rough per-layer io
    w_bytes = pb / plan.tp / (plan.dp if plan.fsdp else 1)
    if kind == "train":
        w_traffic = (pb / plan.tp) * 4                         # grads + opt rw
    else:
        w_traffic = pb / plan.tp
    cache_bytes = 0.0
    if kind == "decode":
        cache_bytes = _decode_cache_bytes(cfg, lo, hi, seq_len, batch)
    t_mem = (w_traffic + act_bytes / n + cache_bytes / n) / HBM_BW

    # --- collective term ---
    coll = 0.0
    n_attn = sum(1 for i in range(lo, hi) if cfg.layer_kind(i) in ("attn", "lattn"))
    n_layers = hi - lo
    if plan.tp > 1:
        # 2 all-reduces of (tokens, d) per layer (Megatron-style)
        per_ar = 2.0 * (plan.tp - 1) / plan.tp * n_tok / plan.dp * cfg.d_model * 2.0
        coll += 2 * n_layers * per_ar
    if cfg.moe is not None:
        # all-to-all dispatch+combine of (tokens*topk, d), within dp group
        a2a = 2.0 * n_tok / plan.dp * cfg.moe.top_k * cfg.d_model * 2.0
        coll += n_layers * a2a
    if kind == "train" and plan.dp > 1:
        coll += 2.0 * (plan.dp - 1) / plan.dp * pb / plan.tp   # grad all-reduce
    if plan.fsdp:
        coll += (plan.dp - 1) / plan.dp * pb / plan.tp          # param all-gather
    t_ici = coll / ICI_BW
    del n_attn

    # --- HBM feasibility ---
    opt_bytes = 0.0
    if kind == "train":
        opt_bytes = (pb / 2.0) * 8 / plan.tp / (plan.dp if plan.fsdp else plan.dp)
        # fp32 m+v sharded over all chips (ZeRO-1)
    live_act = n_tok / plan.dp * cfg.d_model * 2.0 * (4 if kind == "train" else 2)
    hbm_need = (w_bytes + opt_bytes + live_act + cache_bytes / n) / 1e9
    t_total = max(t_comp, t_mem, t_ici)
    return {
        "t_compute": t_comp, "t_memory": t_mem, "t_ici": t_ici,
        "t_total": t_total,
        "throughput": batch / t_total if t_total > 0 else float("inf"),
        "hbm_gb_per_chip": hbm_need,
        "feasible": hbm_need <= HBM_GB * 0.92,
        "flops": fl, "param_bytes": pb, "coll_bytes": coll,
    }


def _decode_cache_bytes(cfg: ArchConfig, lo: int, hi: int, seq_len: int,
                        batch: int) -> float:
    by = 0.0
    for i in range(lo, hi):
        k = cfg.layer_kind(i)
        if k == "attn":
            if cfg.mla:
                by += batch * seq_len * (cfg.mla.kv_lora_rank +
                                         cfg.mla.qk_rope_head_dim) * 2.0
            else:
                by += 2 * batch * seq_len * cfg.n_kv_heads * cfg.resolved_head_dim * 2.0
        elif k == "lattn":
            w = min(cfg.window or seq_len, seq_len)
            by += 2 * batch * w * cfg.n_kv_heads * cfg.resolved_head_dim * 2.0
        elif k == "mamba2":
            s = cfg.ssm
            di = s.expand * cfg.d_model
            by += batch * (di // s.head_dim) * s.head_dim * s.d_state * 4.0
        elif k == "rglru":
            w = cfg.rglru.lru_width or cfg.d_model
            by += batch * w * 4.0
    return by


def lm_stage_tap(cfg: ArchConfig, lo: int, hi: int, *, kind: str, seq_len: int,
                 batch: int, chip_budgets: Sequence[int],
                 name: str = "") -> TAPFunction:
    """TAP curve for a layer range: best (dp, tp) plan per chip budget.
    Resource axes: (chips, hbm_gb_total)."""
    pts = []
    for n in chip_budgets:
        best = None
        tp = 1
        while tp <= n:
            if n % tp == 0:
                for fsdp in (False, True):
                    plan = ShardPlan(dp=n // tp, tp=tp, fsdp=fsdp)
                    r = stage_roofline(cfg, lo, hi, kind=kind, seq_len=seq_len,
                                       batch=batch, plan=plan)
                    if r["feasible"] and (best is None or
                                          r["throughput"] > best[0]["throughput"]):
                        best = (r, plan)
            tp *= 2
        if best:
            r, plan = best
            pts.append(DesignPoint(
                resources=(n, r["hbm_gb_per_chip"] * n),
                throughput=r["throughput"],
                meta={"plan": plan, "roofline": r}))
    return TAPFunction(pts, name=name)
