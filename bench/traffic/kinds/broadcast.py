"""``broadcast`` kind: one multicast job under a gateway VM kill, per
request ``transfer.sim.simulate([job], [kill], engine="jax")``.

Set-up plans on the host: the multicast max throughput from ``src`` to
the configuration's ``dsts``, then one ``plan`` (cost_min) plan at
``goal_fraction`` of it, for ``chunks`` chunks of ``chunk_mb`` arriving
at 0 s. It fails unless the plan validates, is optimal and has some
destination forwarding to another region. The kill is
``VMFailure(t_s, count)`` in the destination with the largest envelope
out-flow to other regions. Every request draws its own simulation seed
from the run's seed and ``k`` (stragglers).

Checked against ``bench/reference/broadcast.py``, which peels its own
distribution trees from the plan's arrays, water-fills the rates in
float32 as the program states (its TPU rate kernel) and keeps times and
volumes in float64:

* ``sim_gap``: the relative gap of the job's completion time, or 1 where
  its status, its delivered chunks or any destination's delivered
  chunks differ;
* ``event_gap``: the relative gap of the program's event count (the work
  ``sim_events_per_s`` counts) from the reference's.

The precision control runs the reference with its whole state in float32
(one step below the stated float64 times) in the program's place.
"""

from __future__ import annotations

import numpy as np

import workload
from reference import broadcast as ref


class Requests:
    work_unit = "events"

    def __init__(self, config, traffic, seed):
        from repro.core import Planner, PlanSpec, default_topology
        from repro.transfer import TransferJob

        self.config, self.traffic, self.seed = config, traffic, int(seed)
        self.top = t = default_topology()
        if t.limit_vm != config["limit_vm"]:
            raise ValueError(f"topology limit_vm {t.limit_vm} is not the "
                             f"configuration's {config['limit_vm']}")
        planner = Planner(t)
        src, dsts = config["src"], tuple(config["dsts"])
        mx = float(planner.plan(PlanSpec(objective="max_throughput",
                                         src=src, dsts=dsts)))
        chunk_mb = float(traffic["chunk_mb"])
        plan = planner.plan(PlanSpec(
            objective=traffic["plan"], src=src, dsts=dsts,
            volume_gb=traffic["chunks"] * chunk_mb / 1024.0,
            tput_goal_gbps=traffic["goal_fraction"] * mx))
        errs = plan.validate()
        if errs or plan.solver_status != "optimal":
            raise RuntimeError(f"broadcast plan {plan.solver_status}: {errs}")
        out = {int(d): float(plan.G[d, :].sum()) for d in plan.dsts}
        if max(out.values()) <= 1e-9:
            raise RuntimeError("no destination of the broadcast plan "
                               "forwards to another region")
        self.kill_region = max(out, key=lambda d: (out[d], -d))
        self.job = TransferJob(plan, "broadcast", 0.0, chunk_mb)
        self.grid = ref.Grid(t.tput, t.limit_egress, t.limit_ingress,
                             int(t.limit_conn))
        self.ref_jobs = ref.McJob(
            np.array(plan.G), np.array(plan.F), np.array(plan.N),
            np.array(plan.M), int(plan.src), tuple(int(d) for d in plan.dsts),
            float(plan.volume_gb), chunk_mb, 0.0)

    def _seed(self, k: int) -> int:
        """Request k's seed (k = -1: the warm-up's)."""
        return int(np.random.default_rng([self.seed, k + 1]).integers(2**62))

    def _simulate(self, seed: int, horizon_s=None):
        from repro.transfer import VMFailure, simulate

        f = self.traffic["vm_failure"]
        kill = VMFailure(t_s=f["t_s"], job=0, region=self.kill_region,
                         count=f["count"])
        res = simulate([self.job], [kill], engine="jax", seed=seed,
                       horizon_s=horizon_s)
        j = res.jobs[0]
        return workload.Outcome(float(res.events), {
            "seed": seed,
            "faults": [("vm", kill.t_s, 0, kill.region, kill.count)],
            "job": ref.McOut(j.status, int(j.chunks_delivered),
                             {int(d): int(c)
                              for d, c in (j.per_dst_delivered or {}).items()},
                             float(j.time_s)),
            "events": int(res.events)})

    def warmup(self):
        self._simulate(self._seed(-1), self.traffic["warmup_horizon_s"])

    def request(self, k: int) -> workload.Outcome:
        return self._simulate(self._seed(k))

    def _reference(self, a, dtype=np.float64):
        return ref.simulate(self.grid, self.ref_jobs, a["faults"],
                            seed=a["seed"], dtype=dtype,
                            rate_dtype=np.float32)

    @staticmethod
    def _numbers(job, events, ref_job, ref_events) -> dict:
        if (job.status, job.chunks_delivered, job.per_dst) != (
                ref_job.status, ref_job.chunks_delivered, ref_job.per_dst):
            gap = 1.0
        else:
            gap = abs(job.time_s - ref_job.time_s) / max(ref_job.time_s, 1e-9)
        return {"sim_gap": float(gap),
                "event_gap": abs(events - ref_events) / max(ref_events, 1)}

    def check(self, outcome) -> dict:
        a = outcome.answer
        ref_job, ref_events = self._reference(a)
        return self._numbers(a["job"], a["events"], ref_job, ref_events)

    def control(self, outcome) -> dict:
        a = outcome.answer
        ref_job, ref_events = self._reference(a)
        low_job, low_events = self._reference(a, np.float32)
        return self._numbers(low_job, low_events, ref_job, ref_events)

    def sizes(self) -> dict:
        lanes, vms, edges = ref.problem_sizes(self.grid, self.ref_jobs)
        return {"lanes": lanes, "vms": vms, "edges": edges}
