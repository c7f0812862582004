"""Plans completed per second of the window (host clock)."""


def read(ctx):
    plans = ctx["work"].get("plans")
    return None if plans is None else plans / ctx["elapsed_s"]
