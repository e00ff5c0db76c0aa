"""Share of the traced window's wall time spent admitting: the
``admit_chunk`` spans (prefill dispatch, page writes, the first-token
sync) over the window's anchor span ``profile_window``, from the
program's record (``observe.last_window()``). None where the program
keeps no such record."""


def read(ctx):
    from repro.runtime import observe
    last = getattr(observe, "last_window", None)
    rec = last() if last is not None else None
    wall = rec.total_s("profile_window") if rec is not None else 0.0
    if wall <= 0 or not rec.count("sched_step"):
        return None
    return 100.0 * rec.total_s("admit_chunk") / wall
