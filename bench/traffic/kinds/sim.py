"""``sim`` kind: ``transfer.sim.simulate(engine="jax")`` per request.

The mix's jobs are planned in set-up on the host (``plan``: ``direct``
with ``num_vms`` VMs, or an objective solved with ``plan_backend`` at
each job's ``goal_fraction`` of the route's max throughput), each of
``chunks_per_job`` chunks of ``chunk_mb``, arriving at ``arrival_s``.
Every request draws its own simulation seed from the run's seed and
``k``; with it a seeded ``ChaosScenario`` (``chaos``) on the plans' links,
and the mix's ``vm_failures``.

Checked against ``bench/reference/sim.py``, which water-fills the rates
in float32 as the program states (its TPU rate kernel) and keeps times
and volumes in float64:

* ``sim_gap``: the widest relative gap of the jobs' completion times, or
  1 where a job's status or delivered chunks differ;
* ``event_gap``: the relative gap of the program's event count (the work
  ``sim_events_per_s`` counts) from the reference's.

The precision control runs the reference with its whole state in float32
(one step below the stated float64 times) in the program's place.
"""

from __future__ import annotations

import numpy as np

import workload
from reference import sim as ref


class Requests(workload.Base):
    work_unit = "events"

    def __init__(self, config, traffic, seed):
        from repro.core import PlanSpec, direct_plan
        from repro.transfer import TransferJob

        super().__init__(config, traffic, seed)
        chunk_mb = float(traffic["chunk_mb"])
        vol = traffic["chunks_per_job"] * chunk_mb / 1024.0
        jobs = []
        if traffic["plan"] == "direct":
            for i, j in enumerate(traffic["jobs"]):
                plan = direct_plan(self.top, self.src, self.dst, vol,
                                   num_vms=traffic["num_vms"])
                jobs.append(TransferJob(plan, f"job{i}", j["arrival_s"],
                                        chunk_mb))
        else:
            mx = self.max_throughput()
            for i, j in enumerate(traffic["jobs"]):
                plan = self.planner.plan(PlanSpec(
                    objective=traffic["plan"], src=self.src, dst=self.dst,
                    volume_gb=vol, tput_goal_gbps=j["goal_fraction"] * mx,
                    backend=traffic["plan_backend"]))
                jobs.append(TransferJob(plan, f"job{i}", j["arrival_s"],
                                        chunk_mb))
        if traffic.get("require_relay") and not any(
            int((j.plan.N > 0).sum()) > 2 for j in jobs
        ):
            raise RuntimeError("no plan of the overlay mix routes through a "
                               "relay region")
        self.jobs = jobs
        self.links = sorted({tuple(e) for j in jobs
                             for e in np.argwhere(j.plan.F > 0).tolist()})
        t = self.top
        self.grid = ref.Grid(t.tput, t.limit_egress, t.limit_ingress,
                             int(t.limit_conn))
        self.ref_jobs = [ref.Job(j.plan.F, j.plan.N, j.plan.M, j.plan.src,
                                 j.plan.dst, j.plan.volume_gb, j.chunk_mb,
                                 j.arrival_s) for j in jobs]

    def _faults(self, seed: int):
        from repro.transfer import ChaosScenario, VMFailure

        faults = []
        chaos = self.traffic.get("chaos")
        if chaos:
            faults += ChaosScenario(self.top, seed=seed, links=self.links,
                                    **chaos).events(len(self.jobs))
        for f in self.traffic.get("vm_failures", []):
            region = {"src": self.src, "dst": self.dst}.get(f["region"],
                                                            f["region"])
            faults.append(VMFailure(t_s=f["t_s"], job=f["job"],
                                    region=self.top.index(region),
                                    count=f["count"]))
        return faults

    def _seed(self, k: int) -> int:
        """Request k's seed (k = -1: the warm-up's)."""
        return int(np.random.default_rng([self.seed, k + 1]).integers(2**62))

    def _simulate(self, seed: int, horizon_s=None):
        from repro.transfer import VMFailure, simulate

        faults = self._faults(seed)
        res = simulate(self.jobs, faults, engine="jax", seed=seed,
                       horizon_s=horizon_s)
        evs = [("vm", f.t_s, f.job, f.region, f.count)
               if isinstance(f, VMFailure)
               else ("rate", f.t_s, f.src, f.dst, f.factor) for f in faults]
        jobs = [ref.JobOut(j.status, int(j.chunks_delivered), float(j.time_s))
                for j in res.jobs]
        return workload.Outcome(float(res.events), {
            "seed": seed, "faults": evs, "jobs": jobs,
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
    def _numbers(jobs, events, ref_jobs, ref_events) -> dict:
        gap = 0.0
        for a, b in zip(jobs, ref_jobs):
            if (a.status, a.chunks_delivered) != (b.status,
                                                  b.chunks_delivered):
                gap = 1.0
                break
            gap = max(gap, abs(a.time_s - b.time_s) / max(b.time_s, 1e-9))
        return {"sim_gap": float(gap),
                "event_gap": abs(events - ref_events) / max(ref_events, 1)}

    def check(self, outcome) -> dict:
        a = outcome.answer
        ref_jobs, ref_events = self._reference(a)
        return self._numbers(a["jobs"], a["events"], ref_jobs, ref_events)

    def control(self, outcome) -> dict:
        a = outcome.answer
        ref_jobs, ref_events = self._reference(a)
        low_jobs, low_events = self._reference(a, np.float32)
        return self._numbers(low_jobs, low_events, ref_jobs, ref_events)

    def sizes(self) -> dict:
        lanes, vms, edges = ref.problem_sizes(self.grid, self.ref_jobs)
        return {"lanes": lanes, "vms": vms, "edges": edges}
