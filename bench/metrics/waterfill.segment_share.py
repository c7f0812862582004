"""Share of the event-loop program's device time spent in the rate solver
kernel."""

KERNEL = r"waterfill"
PROGRAM = r"_segment"


def read(ctx):
    red = ctx["trace"]
    if red is None:
        return None
    k, seg = red.op(KERNEL)[1], red.program(PROGRAM)[1]
    return k / seg if k and seg else None
