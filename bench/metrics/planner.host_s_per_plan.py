"""Host seconds per plan: each request's span (its bench.request
annotation in the trace) less the device busy time inside it, summed over
the window's requests, over the plans they completed."""


def read(ctx):
    red, plans = ctx["trace"], ctx["work"].get("plans")
    if red is None or not plans:
        return None
    host = sum((e - s) - red.busy_in(s, e)
               for name, s, e in red.annotations if name == "bench.request")
    return host / plans
