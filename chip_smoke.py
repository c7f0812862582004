"""Smoke run of the transfer system's device paths on one TPU.

    python chip_smoke.py

One process, no arguments, the normal entry points only:

1. device    - the first JAX device must be a TPU, else exit 1;
2. planner   - ``Planner(default_topology())`` on the paper's Fig. 6
               inter-cloud route: a 100-sample Pareto frontier and a
               tput_max plan with ``backend="jax"`` (the batched device
               IPM), then the same specs with ``backend="numpy"``. Costs
               and integer plans must agree to the tolerances of
               tests/test_solver_equivalence.py, and the device must
               certify LPs in both;
3. simulator - ``sim.simulate(engine="jax")`` against ``engine="soa"`` on
               1e5 chunks of 64 MB over a direct plan, and on three jobs
               riding the phase-2 overlay plans under a seeded
               ChaosScenario plus a VM kill;
4. service   - ``TransferService(backend="jax")`` with three jobs and one
               VMFailure, run on the jax engine and on soa.

Every phase prints what it checked and, on lines of their own, its wall
and compile seconds (smoke observations, not benchmark numbers). A failed
check raises: the script then exits non-zero and prints no result line.
The last line of a passing run is one JSON object naming the device.
"""

from __future__ import annotations

import dataclasses
import json
import sys
import time
from pathlib import Path

SRC, DST = "azure:canadacentral", "gcp:asia-northeast1"  # Fig. 6 inter-cloud
VOLUME_GB = 16.0
# tests/test_solver_equivalence.py: round-down plan costs agree to 1e-6 $/GB
COST_ABS = 1e-6
# The TPU rate solver is the f32 Pallas kernel, whose saturation tolerance
# is 1e-6 (relative to rates of O(1) Gbit/s); completion times may drift by
# that much per event. 10x that bounds the relative time difference.
TIME_RTOL = 1e-5


class SmokeFailure(Exception):
    """A smoke check that did not hold."""


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


class Clock:
    """Wall and JAX compile seconds (tracing, lowering and backend compile
    events from ``jax.monitoring``) of one phase."""

    def __init__(self):
        import jax

        self.compile_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, name, secs, **_):
        if name.startswith("/jax/core/compile/"):
            self.compile_s += secs

    def phase(self, name, fn, *args):
        t0, c0 = time.perf_counter(), self.compile_s
        out = fn(*args)
        print(f"[{name}] wall_s={time.perf_counter() - t0:.3f}")
        print(f"[{name}] compile_s={self.compile_s - c0:.3f}")
        return out


def phase_device():
    import jax

    devs = jax.devices()
    d = devs[0]
    print(f"[device] platform={d.platform} kind={d.device_kind} "
          f"count={len(devs)}")
    check(d.platform == "tpu", f"no TPU: the first JAX device is {d.platform}")
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devs)}


def _batch_counts():
    from repro.obs.metrics import REGISTRY

    return {k: REGISTRY.counter(f"planner.{k}").value for k in (
        "batch_lps.jax", "batch_certified.jax", "batch_lps.numpy",
        "batch_host_resolves",
    )}


def _same_plan(a, b, what):
    check(abs(a.tput_goal - b.tput_goal) <= 1e-9 * max(1.0, b.tput_goal),
          f"{what}: tput goal {a.tput_goal} vs numpy {b.tput_goal}")
    check(abs(a.cost_per_gb - b.cost_per_gb) <= COST_ABS,
          f"{what}: cost {a.cost_per_gb} vs numpy {b.cost_per_gb} $/GB")
    check((a.N == b.N).all() and (a.M == b.M).all(),
          f"{what}: integer plan (N, M) differs from numpy")
    return abs(a.cost_per_gb - b.cost_per_gb)


def phase_planner(top, n_samples=100):
    from repro.core import Planner, PlanSpec, direct_plan
    from repro.core.solver.ipm_batch import _pick_engine

    check(_pick_engine("auto") == "jax",
          "the planner's batch engine on this host is not the device IPM")
    planner = Planner(top)
    ceiling = direct_plan(top, SRC, DST, VOLUME_GB).cost_per_gb * 1.15
    specs = {
        "pareto": PlanSpec(objective="pareto", src=SRC, dst=DST,
                           volume_gb=VOLUME_GB, n_samples=n_samples,
                           backend="jax"),
        "tput_max": PlanSpec(objective="tput_max", src=SRC, dst=DST,
                             volume_gb=VOLUME_GB, cost_ceiling_per_gb=ceiling,
                             backend="jax"),
    }
    plans = {}
    for name, spec in specs.items():
        c0 = _batch_counts()
        dev = planner.plan(spec)
        lps, certified, numpy_lps, host = (
            v - c0[k] for k, v in _batch_counts().items()
        )
        print(f"[planner] {name}: device IPM certified {certified} of {lps} "
              f"LPs, {host} re-solved on the host")
        check(certified > 0, f"{name}: the device IPM certified no LP")
        check(numpy_lps == 0,
              f"{name}: the numpy batch engine ran on a TPU host")
        ref = planner.plan(dataclasses.replace(spec, backend="numpy"))
        if name == "pareto":
            check(len(dev) == len(ref),
                  f"pareto: {len(dev)} points vs numpy {len(ref)}")
            gap = max(_same_plan(p.plan, q.plan, f"pareto point {i}")
                      for i, (p, q) in enumerate(zip(dev, ref)))
            print(f"[planner] pareto: {len(dev)} points match numpy, "
                  f"max |d cost| {gap:.3e} $/GB")
        else:
            gap = _same_plan(dev, ref, "tput_max")
            print(f"[planner] tput_max: {dev.tput_goal:.6f} Gbit/s at "
                  f"{dev.cost_per_gb:.9f} $/GB matches numpy "
                  f"(|d cost| {gap:.3e})")
        plans[name] = dev
    return plans


def _solver_counts():
    from repro.obs.metrics import REGISTRY

    return {s: REGISTRY.counter(f"sim.rate_solver.{s}").value
            for s in ("masked", "pallas")}


def _segment_custom_calls(jobs, faults):
    """tpu_custom_call ops in the compiled sim segment of a scenario run
    with the Pallas rate solver (a kernel in interpret mode has none)."""
    import jax

    from repro.transfer import flowsim_jax
    from repro.transfer.events import materialize_jobs, sorted_schedule
    from repro.transfer.simconfig import SimConfig

    cfg = SimConfig()
    su = materialize_jobs(
        jobs, seed=cfg.seed, straggler_prob=cfg.straggler_prob,
        straggler_speed=cfg.straggler_speed, exec_top=cfg.exec_top,
    )
    with jax.enable_x64(True):
        sc, cn, st = flowsim_jax._build(
            su, cfg, sorted_schedule(jobs, faults), "pallas"
        )
        hlo = flowsim_jax._segment.lower(st, cn, sc).compile().as_text()
    return hlo.count("tpu_custom_call")


def _compare_sim(name, jobs, faults):
    from repro.transfer import simulate

    c0 = _solver_counts()
    t0 = time.perf_counter()
    jx = simulate(jobs, faults, engine="jax", seed=0)
    t_jax = time.perf_counter() - t0
    ran = [s for s, v in _solver_counts().items() if v > c0[s]]
    check(len(ran) == 1, f"{name}: rate solvers run: {ran}")
    t0 = time.perf_counter()
    soa = simulate(jobs, faults, engine="soa", seed=0)
    t_soa = time.perf_counter() - t0
    worst = 0.0
    for a, b in zip(jx.jobs, soa.jobs):
        check((a.status, a.chunks_delivered) == (b.status, b.chunks_delivered),
              f"{name}/{a.name}: jax {a.status} {a.chunks_delivered} chunks "
              f"vs soa {b.status} {b.chunks_delivered}")
        rel = abs(a.time_s - b.time_s) / max(b.time_s, 1e-9)
        check(rel <= TIME_RTOL, f"{name}/{a.name}: time_s {a.time_s} vs soa "
              f"{b.time_s} (rel {rel:.2e} > {TIME_RTOL})")
        worst = max(worst, rel)
    print(f"[sim] {name}: rate solver {ran[0]}; "
          + ", ".join(f"{j.name} {j.status} {j.chunks_delivered}/{j.n_chunks}"
                      for j in jx.jobs)
          + f"; equal to soa, max rel time_s diff {worst:.2e}; "
          f"{jx.events} events")
    print(f"[sim] {name}: jax wall_s={t_jax:.3f} soa wall_s={t_soa:.3f}")
    if ran[0] == "pallas":
        n = _segment_custom_calls(jobs, faults)
        print(f"[sim] {name}: compiled segment holds {n} tpu_custom_call")
        check(n > 0, f"{name}: the Pallas kernel is not compiled for the TPU")
    return ran[0]


def phase_sim(top, plans, n_chunks=100_000):
    import numpy as np

    from repro.core import direct_plan
    from repro.transfer import ChaosScenario, TransferJob, VMFailure

    vol = n_chunks * 64 / 1024  # 64 MB chunks
    bulk = [TransferJob(
        direct_plan(top, "aws:us-west-2", "aws:eu-central-1", vol, num_vms=2),
        "bulk", chunk_mb=64.0,
    )]
    _compare_sim(f"bulk-{n_chunks}", bulk, [])

    frontier = plans["pareto"]
    overlay = [
        TransferJob(plans["tput_max"], "overlay-tput"),
        TransferJob(frontier[len(frontier) // 2].plan, "overlay-mid",
                    arrival_s=0.5),
        TransferJob(frontier[-1].plan, "overlay-max", arrival_s=1.0),
    ]
    links = sorted({tuple(e) for j in overlay
                    for e in np.argwhere(j.plan.F > 0).tolist()})
    chaos = ChaosScenario(top, seed=7, horizon_s=10.0, n_brownouts=0,
                          n_gray=1, n_flapping=1, links=links)
    faults = chaos.events(len(overlay)) + [
        VMFailure(t_s=2.0, job=0, region=top.index(SRC), count=1),
    ]
    kinds = [type(a).__name__ for a in chaos.archetypes] + ["VMFailure"]
    print(f"[sim] overlay-chaos: {len(faults)} faults from", ", ".join(kinds))
    _compare_sim("overlay-chaos", overlay, faults)


def phase_service(top):
    from repro.transfer import TransferRequest, TransferService, VMFailure

    def run(engine):
        svc = TransferService(top, backend="jax")
        svc.submit(TransferRequest("a", SRC, DST, 8.0, 4.0))
        svc.submit(TransferRequest("b", SRC, DST, 8.0, 4.0, arrival_s=1.0))
        svc.submit(TransferRequest("c", "gcp:us-central1", DST, 8.0, 4.0))
        return svc.run(
            faults=[VMFailure(t_s=2.0, job=0, region=top.index(SRC), count=1)],
            engine=engine,
        )

    jx, soa = run("jax"), run("soa")
    for a, b in zip(jx.jobs, soa.jobs):
        check(a.lost_chunks == 0, f"service/{a.request.name}: "
              f"{a.lost_chunks} lost chunks")
        check((a.status, a.delivered_chunks) == (b.status, b.delivered_chunks),
              f"service/{a.request.name}: jax {a.status} {a.delivered_chunks} "
              f"vs soa {b.status} {b.delivered_chunks}")
    print("[service] " + ", ".join(
        f"{j.request.name} {j.status} {j.delivered_chunks}/{j.n_chunks} "
        f"lost {j.lost_chunks} replans {len(j.replans)}" for j in jx.jobs
    ) + f"; {jx.segments} segments; delivered counts equal to soa")


def main() -> int:
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    try:
        from repro.compile_cache import use_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repro package is not next to this script "
              f"({e})", file=sys.stderr)
        return 2
    print(f"[setup] compile cache {use_compile_cache()}")
    from repro.core import default_topology

    clock = Clock()
    try:
        device = clock.phase("device", phase_device)
        top = default_topology()
        plans = clock.phase("planner", phase_planner, top)
        clock.phase("sim", phase_sim, top, plans)
        clock.phase("service", phase_service, top)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
