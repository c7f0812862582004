"""Device microseconds of the event-loop program per simulated event."""

PROGRAM = r"_segment"


def read(ctx):
    red, events = ctx["trace"], ctx["work"].get("events")
    if red is None or not events:
        return None
    n, s = red.program(PROGRAM)
    return s / events * 1e6 if n else None
