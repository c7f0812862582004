"""Stage-batched passes per event-loop iteration that ran the relay
cascade with a buffer full (counter deltas ``sim.cascade_seq_passes`` /
``sim.cascade_seq_iters``): how deep into the stage DAG the unblocking
goes. Nothing to read where no iteration ran that cascade, or in a
program that does not count its passes."""


def read(ctx):
    c = ctx["counters"]
    iters = c.get("sim.cascade_seq_iters", 0.0)
    if not iters or "sim.cascade_seq_passes" not in c:
        return None
    return c["sim.cascade_seq_passes"] / iters
