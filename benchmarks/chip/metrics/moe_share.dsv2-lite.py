"""Share of the chip's busy time in the traced window that the held
experts' grouped matmuls (the ``ragged-dot`` ops) take."""

OPS = "ragged-dot"


def read(ctx):
    tr = ctx["trace"]
    t, n = tr.ops_matching(OPS)
    if not n or tr.busy_s <= 0:
        return None
    return 100.0 * t / (tr.busy_s * ctx["chips"])
