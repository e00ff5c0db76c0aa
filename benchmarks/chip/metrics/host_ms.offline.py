"""Host time of one scheduler step outside its device syncs: the mean per
``sched_step`` span of its time less the ``finish_tick_sync``,
``admit_sync`` and ``harvest_sync`` spans under it, from the program's
record of the traced window (``observe.last_window()``). None where the
program keeps no such record."""

SYNCS = ("finish_tick_sync", "admit_sync", "harvest_sync")


def read(ctx):
    from repro.runtime import observe
    last = getattr(observe, "last_window", None)
    rec = last() if last is not None else None
    n = rec.count("sched_step") if rec is not None else 0
    if not n:
        return None
    return 1e3 * rec.self_s("sched_step", exclude=SYNCS) / n
