"""Model FLOPs the traced window completed over the chips' bf16 peak for
that window. Stage 1 and the exit head count for every decision, stage 2
and the final head only for the hard share (the window's realized q),
prefill 2N per prompt token with causal attention, and attention over each
token's real context rather than the cache width."""
from chipbench.roofline import decode_flops, prefill_flops


def read(ctx):
    c, k = ctx["counts"], ctx["counters"]
    t = ctx["trace"]
    if not c["decisions"] or t.window_s <= 0:
        return None
    m = ctx["dims"]
    q = k["hard"] / max(k["dec"], 1)
    ctxlen = c["mean_context"]
    flops = c["decisions"] * (decode_flops(m, m["exit"], ctxlen)
                              + q * decode_flops(m, m["n_layers"] - m["exit"],
                                                 ctxlen))
    flops += sum(prefill_flops(m, s) for s in c["prompt_lens"])
    peak = ctx["peaks"]["bf16_flops_per_s"] * ctx["chips"]
    return 100.0 * flops / (t.window_s * peak)
