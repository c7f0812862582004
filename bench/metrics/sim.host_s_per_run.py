"""Host seconds per simulation: each request's span (its bench.request
annotation in the trace) less the device busy time inside it, averaged
over the window's simulations."""


def read(ctx):
    red = ctx["trace"]
    if red is None or "events" not in ctx["work"]:
        return None
    spans = [(s, e) for name, s, e in red.annotations
             if name == "bench.request"]
    if not spans:
        return None
    return sum((e - s) - red.busy_in(s, e) for s, e in spans) / len(spans)
