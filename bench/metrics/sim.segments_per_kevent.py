"""Executions of the event-loop program (``_segment``), each a host round
trip, per thousand simulated events."""

PROGRAM = r"_segment"


def read(ctx):
    red, events = ctx["trace"], ctx["work"].get("events")
    if red is None or not events:
        return None
    n = red.program(PROGRAM)[0]
    return n / (events / 1000.0) if n else None
