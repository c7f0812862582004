"""Share of the device IPM's row iterations that advanced a real LP: the
real rows' own iterations over batch rows times loop trips (counter
deltas ``ipm.sample_iters`` / ``ipm.row_trips``)."""


def read(ctx):
    c = ctx["counters"]
    trips = c.get("ipm.row_trips", 0.0)
    return c.get("ipm.sample_iters", 0.0) / trips if trips else None
