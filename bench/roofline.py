"""Essential work of one max-min rate solve, from the problem's own sizes.

A solve reads each connection lane's cap, active flag and its source VM,
destination VM and shared-edge indices, each VM's egress and ingress
budget and each shared edge's capacity, and writes each lane's rate: one
4-byte word apiece. Its operations are those of one water-filling round
over the lanes, the least any solve does: per lane three segment-sum
adds (VM out, VM in, edge), three gathers and two minimums of the
shares, one compare against the cap, one choice of rate, and three adds
of the fixed rate back into the budgets. Neither count depends on how a
kernel tiles, replicates or iterates.
"""

from __future__ import annotations

WORD = 4  # bytes: f32 rates and budgets, i32 indices
LANE_WORDS = 6  # cap, active, src VM, dst VM, edge -> rate
LANE_OPS = 13


def waterfill_cost(lanes: int, vms: int, edges: int) -> tuple[int, int]:
    """(operations, bytes) of one rate solve."""
    return LANE_OPS * lanes, WORD * (LANE_WORDS * lanes + 2 * vms + edges)


def least_time(lanes: int, vms: int, edges: int, peaks: dict):
    """(seconds, bound): the least time of one solve on a chip with these
    peaks, and which of ``"memory"`` or ``"compute"`` sets it."""
    ops, nbytes = waterfill_cost(lanes, vms, edges)
    t_mem = nbytes / peaks["hbm_bytes_per_s"]
    t_ops = ops / peaks["flops_bf16"]
    return max(t_mem, t_ops), ("memory" if t_mem >= t_ops else "compute")
