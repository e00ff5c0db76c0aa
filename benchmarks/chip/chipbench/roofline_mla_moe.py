"""The yardstick's arithmetic for a decoder with latent attention (MLA) and
sparse experts (DeepSeek-V2), beside ``roofline.py``'s for dense decoders:
operations and bytes from shapes and from the program's MoE load counters
(``moe_load``), never from a device. ``m`` is the reference's ``dims``.
"""
from __future__ import annotations


def mla_token_flops(m: dict, context: float, absorbed: bool) -> float:
    """One token through one MLA layer attending over ``context`` positions:
    the projections, and scores and values either through the latent (the
    absorbed decode: W_uk into the query, W_uv after the context) or over
    per-head keys and values decompressed from it (prefill)."""
    d, H, r = m["d_model"], m["n_heads"], m["kv_lora_rank"]
    nope, rope, v = m["nope"], m["rope"], m["v_head_dim"]
    proj = 2.0 * d * (H * (nope + rope) + r + rope + H * v)
    if absorbed:
        per_pos = 2.0 * H * (r + rope + r)
        return proj + 2.0 * H * r * (nope + v) + per_pos * context
    return proj + 2.0 * r * H * (nope + v) \
        + 2.0 * H * (nope + rope + v) * context


def expert_row_flops(m: dict) -> float:
    """One row through one routed SwiGLU expert (gate, up, down)."""
    return 6.0 * m["d_model"] * m["d_ff_expert"]


def ffn_token_flops(m: dict, dense: bool) -> float:
    """A token's feed-forward outside the routed experts: the dense MLP of
    a leading layer, or the router and the shared experts."""
    d = m["d_model"]
    if dense:
        return 6.0 * d * m["dense_ff"]
    return 2.0 * d * m["n_experts"] + 6.0 * d * m["d_ff_expert"] \
        * m["n_shared"]


def decode_flops(m: dict, lo: int, hi: int, context: float,
                 held_per_token: float) -> float:
    """One token through layers [lo, hi) and one head, attending over
    ``context`` positions, with ``held_per_token`` routed rows per token
    and MoE layer landing on held experts."""
    n_dense = max(0, min(hi, m["first_k_dense"]) - lo)
    n_moe = (hi - lo) - n_dense
    return ((hi - lo) * mla_token_flops(m, context, absorbed=True)
            + n_dense * ffn_token_flops(m, dense=True)
            + n_moe * (ffn_token_flops(m, dense=False)
                       + held_per_token * expert_row_flops(m))
            + 2.0 * m["vocab"] * m["d_model"])


def prefill_flops(m: dict, prompt_len: int, held_per_token: float) -> float:
    """A whole prompt through every layer, causal attention over the lower
    triangle (mean context (S + 1) / 2), and the final head at its last
    position."""
    s = prompt_len
    per_token = decode_flops(m, 0, m["n_layers"], 0.0, held_per_token) \
        - 2.0 * m["vocab"] * m["d_model"] \
        - m["n_layers"] * (mla_token_flops(m, 0.0, True)
                           - mla_token_flops(m, (s + 1) / 2.0, False))
    return s * per_token + 2.0 * m["vocab"] * m["d_model"]


def grouped_matmul_cost(m: dict, held_rows: float, touched: float,
                        itemsize: int = 2):
    """(FLOPs, bytes) of the held experts' grouped matmuls over a set of
    MoE layers: every held row through gate, up and down; each touched
    expert's three matrices read once, and each row's inputs and outputs
    (d in and f out for gate and up, f in and d out for down)."""
    d, f = m["d_model"], m["d_ff_expert"]
    flops = held_rows * expert_row_flops(m)
    nbytes = itemsize * (touched * 3 * d * f + held_rows * 3 * (d + f))
    return flops, nbytes


def paged_gather_append_bytes(rows: int, max_pages: int, page: int,
                              widths, itemsize: int,
                              live_pages: float) -> float:
    """``roofline.paged_gather_append_bytes`` for pools of different
    widths (MLA's latent and rope key): each row's gathered block of each
    pool written whole, its live pages read, its new row appended."""
    return sum(rows * (max_pages + live_pages) * page * w * itemsize
               + rows * w * itemsize for w in widths)


def moe_loads(program: str = None) -> list:
    """The ``moe_load`` args the program recorded in the traced window
    (``observe.last_window()``), of one program (``tick``, ``bucket``,
    ``prefill``) or all; empty where the program keeps no such record."""
    from repro.runtime import observe
    last = getattr(observe, "last_window", None)
    rec = last() if last is not None else None
    if rec is None:
        return []
    return [r[4] for r in rec.spans if r[0] == "moe_load"
            and (program is None or r[4].get("program") == program)]


def held_per_token(loads: list, top_k: int) -> float:
    """Routed rows per token and MoE layer that landed on held experts."""
    routed = sum(a["routed"] for a in loads)
    return top_k * sum(a["held_rows"] for a in loads) / routed \
        if routed else 0.0
