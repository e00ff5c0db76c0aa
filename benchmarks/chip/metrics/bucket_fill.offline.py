"""Mean fill of the stage-2 buckets dispatched in the window: hard tokens
over bucket lanes (``ServeStats.bucket_fill_sum`` / ``n_buckets``)."""


def read(ctx):
    k = ctx["counters"]
    return 100.0 * k["fill"] / k["buckets"] if k["buckets"] else None
