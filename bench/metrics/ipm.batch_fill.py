"""Share of the device IPM's padded batch rows that carry a real LP; the
rest repeat a call's first LP (counter deltas ``ipm.batch_rows_real`` /
``ipm.batch_rows``)."""


def read(ctx):
    c = ctx["counters"]
    rows = c.get("ipm.batch_rows", 0.0)
    return c.get("ipm.batch_rows_real", 0.0) / rows if rows else None
