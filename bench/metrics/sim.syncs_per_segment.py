"""Scalars of the event loop's state read back on the host per device
segment (counter deltas ``sim.host_syncs`` / ``sim.segments``)."""


def read(ctx):
    c = ctx["counters"]
    segs = c.get("sim.segments", 0.0)
    return c.get("sim.host_syncs", 0.0) / segs if segs else None
