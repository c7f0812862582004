"""Trips of the device IPM's vmapped loop per device call, each the
iterations of the call's slowest LP (counter deltas ``ipm.loop_trips`` /
``ipm.device_calls``)."""


def read(ctx):
    c = ctx["counters"]
    calls = c.get("ipm.device_calls", 0.0)
    return c.get("ipm.loop_trips", 0.0) / calls if calls else None
