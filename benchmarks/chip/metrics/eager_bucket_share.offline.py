"""Share of the stage-2 bucket dispatches in the traced window that the
eager-drain rule asked for (``stage2_bucket_dispatch`` spans with
``reason=eager``; the others are ``full``, ``forced`` and
``backpressure``), from the program's record (``observe.last_window()``).
None where the program keeps no such record or dispatched no bucket."""


def read(ctx):
    from repro.runtime import observe
    last = getattr(observe, "last_window", None)
    rec = last() if last is not None else None
    n = rec.count("stage2_bucket_dispatch") if rec is not None else 0
    if not n:
        return None
    return 100.0 * rec.count("stage2_bucket_dispatch", reason="eager") / n
