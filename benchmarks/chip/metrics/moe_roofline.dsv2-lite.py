"""Share of their roofline the held experts' grouped matmuls reach: the
FLOPs of the rows they computed and the bytes of the touched experts'
matrices and of those rows (``roofline_mla_moe.grouped_matmul_cost``, from
the window's ``moe_load`` counters: ``held_rows``, ``touched``, per MoE
layer), over the device time of the chip's ragged-dot ops (``ragged-dot``)
in the trace. None where the program records no ``moe_load``."""
from chipbench.roofline import least_time_s
from chipbench.roofline_mla_moe import grouped_matmul_cost, moe_loads

OPS = "ragged-dot"


def read(ctx):
    t, n = ctx["trace"].ops_matching(OPS)
    loads = moe_loads()
    if not n or t <= 0 or not loads:
        return None
    least = 0.0
    for a in loads:                 # per program call, per MoE layer
        f, b = grouped_matmul_cost(ctx["dims"], a["held_rows"] / a["layers"],
                                   a["touched"] / a["layers"])
        least += a["layers"] * least_time_s(f, b, ctx["peaks"])
    return 100.0 * least / t
