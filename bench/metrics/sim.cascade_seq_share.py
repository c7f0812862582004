"""Share of the event loop's iterations that ran the sequential relay
cascade, the one taken while a relay buffer is full (counter deltas
``sim.cascade_seq_iters`` / ``sim.loop_iters``)."""


def read(ctx):
    c = ctx["counters"]
    iters = c.get("sim.loop_iters", 0.0)
    return c.get("sim.cascade_seq_iters", 0.0) / iters if iters else None
