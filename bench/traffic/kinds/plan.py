"""``plan`` kind: cost_min plans on the configuration's route.

Each request is one ``Planner.plan(PlanSpec(...))``, or with ``cohort``
one ``Planner.plan_cohort`` of that many specs. Every throughput goal is
drawn from the seed uniform in ``goal_fraction`` x the route's max
throughput (computed in set-up), a fresh one for every plan.

Checked on every plan against ``bench/reference/plan.py``:

* ``plan_gap``: the widest of its violation of Eq. 4b-4j, its stated
  throughput against what its (N, M) carry, and its $/GB against the
  least its (N, M) admit;
* ``plan_shortfall``: its achieved throughput below the requested goal.

The precision control re-fits each plan's flow in float32 (stated:
float64) and holds that to the same numbers. Calibration also reads
``cost_excess``, the $/GB above the integer optimum of Eq. 4a-4j at the
goal; no run compares it (PERF.md says why).
"""

from __future__ import annotations

import numpy as np

import workload
from reference import plan as ref


class Requests(workload.Base):
    work_unit = "plans"

    def __init__(self, config, traffic, seed):
        super().__init__(config, traffic, seed)
        self.mx = self.max_throughput()
        per = int(traffic.get("cohort", 1))
        lo, hi = traffic["goal_fraction"]
        rng = np.random.default_rng(self.seed)
        self.fractions = rng.uniform(lo, hi, (traffic["max_requests"], per))
        self.warmup_fractions = np.full(per, traffic["warmup_fraction"])
        t = self.top
        self.grid = ref.Grid(t.tput, t.price_egress, t.price_vm,
                             t.limit_egress, t.limit_ingress,
                             int(t.limit_conn), int(t.limit_vm))

    def _spec(self, goal):
        from repro.core import PlanSpec

        return PlanSpec(objective=self.traffic["objective"], src=self.src,
                        dst=self.dst, volume_gb=self.config["volume_gb"],
                        tput_goal_gbps=float(goal),
                        backend=self.traffic.get("backend", "numpy"))

    def _run(self, fractions):
        goals = fractions * self.mx
        if "cohort" in self.traffic:
            plans = self.planner.plan_cohort([self._spec(g) for g in goals])
        else:
            plans = [self.planner.plan(self._spec(goals[0]))]
        answer = [(float(g), p.src, p.dst,
                   ref.Plan(np.array(p.F), np.array(p.N), np.array(p.M),
                            float(p.tput_goal), float(p.cost_per_gb)))
                  for g, p in zip(goals, plans)]
        return workload.Outcome(len(plans), answer)

    def warmup(self):
        self._run(self.warmup_fractions)

    def request(self, k: int) -> workload.Outcome:
        return self._run(self.fractions[k])

    def _numbers(self, outcome, refit_dtype=None) -> dict:
        gap, short = 0.0, 0.0
        for goal, src, dst, p in outcome.answer:
            if refit_dtype is not None:
                F = ref.refit(self.grid, p.N, p.M, src, dst, p.tput_goal,
                              refit_dtype)
                p = ref.Plan(F, p.N, p.M, p.tput_goal,
                             ref.cost_per_gb(self.grid, F, p.N, src))
            got = ref.check(self.grid, p, src, dst, goal)
            gap = max(gap, *map(float, got.values()))
            short = max(short, ref.shortfall(p, src, goal))
        return {"plan_gap": gap, "plan_shortfall": short}

    def check(self, outcome) -> dict:
        return self._numbers(outcome)

    def control(self, outcome) -> dict:
        return self._numbers(outcome, np.float32)

    def diagnostics(self, outcome) -> dict:
        """Readings for calibration only: ``cost_excess``, the widest
        relative gap of a plan's $/GB above the integer optimum."""
        worst = 0.0
        for goal, src, dst, p in outcome.answer:
            best = ref.optimum(self.grid, src, dst, goal)
            worst = max(worst, (p.cost_per_gb - best) / best)
        return {"cost_excess": worst}
