"""Share of the traced window in which no operation ran on the device,
from the profiler trace (busy = union of device-op intervals; mean over
the chips used)."""


def read(ctx):
    t = ctx["trace"]
    if t.window_s <= 0 or t.busy_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)
