"""Chunk copies the jax event loop enqueued to child stages per loop
iteration (counter deltas ``sim.fanout_enqueues`` / ``sim.loop_iters``):
how much of each step is a multicast tree's fan-out. Nothing to read in
a program that does not count its enqueues."""


def read(ctx):
    c = ctx["counters"]
    iters = c.get("sim.loop_iters", 0.0)
    if not iters or "sim.fanout_enqueues" not in c:
        return None
    return c["sim.fanout_enqueues"] / iters
