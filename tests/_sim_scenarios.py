"""The jax event loop's three shapes, as ``chip_smoke.py`` runs them.

One bulk direct-plan job of 1e5 64 MB chunks; overlay jobs (relay
fan-out) with a VM kill; and a multicast job (tree fan-out to several
children) with a VM kill. Planning only: nothing here touches a device.
"""

from __future__ import annotations

from repro.core import Planner, PlanSpec, direct_plan
from repro.transfer import TransferJob, VMFailure

SRC, DST = "azure:canadacentral", "gcp:asia-northeast1"  # chip_smoke route


def sim_scenarios(top):
    """name -> (jobs, faults) for ``bulk``, ``overlay`` and ``multicast``."""
    bulk = [TransferJob(
        direct_plan(top, "aws:us-west-2", "aws:eu-central-1", 100_000 / 16,
                    num_vms=2),
        "bulk", chunk_mb=64.0,
    )]
    ceiling = direct_plan(top, SRC, DST, 16.0).cost_per_gb * 1.15
    plan = Planner(top).plan(PlanSpec(
        objective="tput_max", src=SRC, dst=DST, volume_gb=16.0,
        cost_ceiling_per_gb=ceiling, n_samples=8,
    ))
    overlay = [TransferJob(plan, "a"), TransferJob(plan, "b", arrival_s=0.5)]
    kill = [VMFailure(t_s=2.0, job=0, region=top.index(SRC), count=1)]
    mc = Planner(top, max_relays=6).plan(PlanSpec(
        objective="cost_min", src="gcp:us-central1",
        dsts=("gcp:europe-west1", "gcp:europe-west3", "gcp:europe-west4"),
        tput_goal_gbps=2.0, volume_gb=4.0,
    ))
    mc_kill = [VMFailure(t_s=1.5, job=0, region=mc.dsts[0], count=1)]
    return {"bulk": (bulk, []), "overlay": (overlay, kill),
            "multicast": ([TransferJob(mc, "repl")], mc_kill)}
