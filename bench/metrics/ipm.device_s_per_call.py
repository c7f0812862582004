"""Device seconds per execution of the device IPM program."""

PROGRAM = r"_solve"


def read(ctx):
    red = ctx["trace"]
    if red is None:
        return None
    n, s = red.program(PROGRAM)
    return s / n if n else None
