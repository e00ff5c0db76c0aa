"""Device time of one pool tick (the fused single-launch tick, or the
composed one), from the profiler trace's program events."""


def read(ctx):
    s, n = ctx["trace"].modules_matching("pool_tick")
    return 1e3 * s / n if n else None
