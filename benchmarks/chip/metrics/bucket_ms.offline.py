"""Device time of one stage-2 bucket (the paged stage-2 decode program),
from the profiler trace's program events."""


def read(ctx):
    s, n = ctx["trace"].modules_matching("s2_paged")
    return 1e3 * s / n if n else None
