"""Share of the traced window in which no operation ran on the device."""


def read(ctx):
    red = ctx["trace"]
    if red is None or "plans" not in ctx["work"]:
        return None
    return 1.0 - red.busy_s / red.window_s
