"""Share of the batched LPs that failed their certificate and were solved
again on the host (counter deltas over the window)."""


def read(ctx):
    c = ctx["counters"]
    lps = sum(v for k, v in c.items() if k.startswith("planner.batch_lps."))
    if not lps:
        return None
    return c.get("planner.batch_host_resolves", 0.0) / lps
