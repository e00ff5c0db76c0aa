"""Share of its roofline the fused dispatch reaches (the exit-decision
kernel that streams the tick's (slots, V) float32 logits once, and the
scatter-merge kernels that write compacted rows into the ring slabs): the
bytes those calls need at the chip's HBM bandwidth, over the two kernels'
device time in the trace."""
from chipbench.roofline import (exit_decision_bytes, least_time_s,
                                scatter_merge_bytes)
from chipbench.system import stage2_rows


def read(ctx):
    tr = ctx["trace"]
    t_ex, n_ex = tr.ops_matching("exit_decision")
    t_sm, n_sm = tr.ops_matching("_scatter_merge")
    if not n_ex or t_ex + t_sm <= 0:
        return None
    m, srv = ctx["dims"], ctx["conf"]["serving"]
    ring_rows = srv["queue_depth"] * stage2_rows(ctx["conf"], ctx["mix"])
    row_bytes = (2 * m["d_model"]                       # hidden row, bf16
                 + 4 * (srv["max_len"] // srv["page_size"])  # block table
                 + 4 + 4)                               # step, sample id
    per_tick = (exit_decision_bytes(srv["n_slots"], m["vocab"])
                + scatter_merge_bytes(ring_rows, row_bytes))
    return 100.0 * n_ex * least_time_s(0.0, per_tick, ctx["peaks"]) \
        / (t_ex + t_sm)
