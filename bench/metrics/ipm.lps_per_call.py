"""LPs the planner handed to its batch engines, per execution of the
device IPM program (``_solve_batched``) in the trace."""

PROGRAM = r"_solve"


def read(ctx):
    red = ctx["trace"]
    if red is None:
        return None
    calls = red.program(PROGRAM)[0]
    lps = sum(v for k, v in ctx["counters"].items()
              if k.startswith("planner.batch_lps."))
    return lps / calls if calls else None
