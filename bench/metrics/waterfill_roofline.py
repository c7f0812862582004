"""Share of its roofline that the rate solver kernel reaches: executions
times the least time of one solve (``bench/roofline.py``, from the
scenario's lanes, VMs and edges and the chip's peaks) over the kernel's
device time. Silent where the kernel did not run."""

import sys

from roofline import least_time, waterfill_cost

KERNEL = r"waterfill"


def read(ctx):
    red, sizes, peaks = ctx["trace"], ctx["sizes"], ctx["peaks"]
    if red is None or "lanes" not in sizes:
        return None
    n, s = red.op(KERNEL)
    if not n or s <= 0:
        return None
    ops, nbytes = waterfill_cost(sizes["lanes"], sizes["vms"], sizes["edges"])
    t, bound = least_time(sizes["lanes"], sizes["vms"], sizes["edges"], peaks)
    print(f"[waterfill_roofline] {n} solves of {sizes['lanes']} lanes, "
          f"{sizes['vms']} VMs, {sizes['edges']} edges: {ops} operations "
          f"and {nbytes} bytes each, {bound} bound", file=sys.stderr)
    return 100.0 * n * t / s
