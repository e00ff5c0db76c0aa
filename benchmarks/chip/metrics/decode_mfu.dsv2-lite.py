"""``decode_mfu.offline`` for latent attention and held experts: model FLOPs
the traced window completed over the chip's bf16 peak for that window.
Stage 1 and the exit head count for every decision, stage 2 and the final
head for the hard share; MLA as the absorbed decode computes it over each
token's real context; the dense layer, the router and the shared experts
for every token; routed experts for the rows per token that landed on held
experts, as the window's ``moe_load`` counters measured them (decode
programs and prefill apart); prefill with per-head keys and values over
the causal triangle."""
from chipbench.roofline_mla_moe import (decode_flops, held_per_token,
                                        moe_loads, prefill_flops)


def read(ctx):
    c, k, t = ctx["counts"], ctx["counters"], ctx["trace"]
    m = ctx["dims"]
    decode = moe_loads("tick") + moe_loads("bucket")
    if not c["decisions"] or t.window_s <= 0 or not decode:
        return None
    held = held_per_token(decode, m["top_k"])
    held_pf = held_per_token(moe_loads("prefill"), m["top_k"]) or held
    q = k["hard"] / max(k["dec"], 1)
    ctxlen = c["mean_context"]
    flops = c["decisions"] * (
        decode_flops(m, 0, m["exit"], ctxlen, held)
        + q * decode_flops(m, m["exit"], m["n_layers"], ctxlen, held))
    flops += sum(prefill_flops(m, s, held_pf) for s in c["prompt_lens"])
    peak = ctx["peaks"]["bf16_flops_per_s"] * ctx["chips"]
    return 100.0 * flops / (t.window_s * peak)
