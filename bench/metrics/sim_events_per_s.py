"""Simulated events completed per second of the window (host clock)."""


def read(ctx):
    events = ctx["work"].get("events")
    return None if events is None else events / ctx["elapsed_s"]
