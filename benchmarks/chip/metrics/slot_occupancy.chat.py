"""Mean share of the slot pool holding a request (active or parked),
sampled after every scheduler step of the window (``n_busy``)."""


def read(ctx):
    occ = ctx["occupancy"]
    return 100.0 * sum(occ) / len(occ) if occ else None
