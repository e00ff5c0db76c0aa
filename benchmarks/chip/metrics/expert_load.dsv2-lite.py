"""How uneven the held experts' groups are: per program call in the traced
window (``moe_load``), the largest held group per MoE layer over the mean
group of the experts that got rows, averaged over the calls. 1 is an even
load; larger means one expert's group sets the grouped matmul's length."""
from chipbench.roofline_mla_moe import moe_loads


def read(ctx):
    ratios = [(a["max_group"] / a["layers"]) / (a["held_rows"] / a["touched"])
              for a in moe_loads() if a["touched"]]
    return sum(ratios) / len(ratios) if ratios else None
