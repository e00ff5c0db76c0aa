"""``paged_attn_roofline.offline`` at MLA's widths: the paged gather+append
kernel moves the latent (``kv_lora_rank``) and the rope key (``rope``) per
token, where a dense decoder moves K and V heads. Bytes of its calls at the
chip's HBM bandwidth over the kernel's device time in the trace; live
pages are the traced window's mean decision context in pages, for the
share of bucket lanes that carried a row."""
import math

from chipbench.roofline import least_time_s
from chipbench.roofline_mla_moe import paged_gather_append_bytes
from chipbench.system import stage2_rows


def read(ctx):
    t, n = ctx["trace"].ops_matching("paged_gather_append_kernel")
    if not n or t <= 0:
        return None
    m, srv, k = ctx["dims"], ctx["conf"]["serving"], ctx["counters"]
    fill = k["fill"] / k["buckets"] if k["buckets"] else 0.0
    live = fill * math.ceil(ctx["counts"]["mean_context"] / srv["page_size"])
    per_call = paged_gather_append_bytes(
        stage2_rows(ctx["conf"], ctx["mix"]),
        srv["max_len"] // srv["page_size"], srv["page_size"],
        (m["kv_lora_rank"], m["rope"]), 2, live)
    return 100.0 * n * least_time_s(0.0, per_call, ctx["peaks"]) / t
