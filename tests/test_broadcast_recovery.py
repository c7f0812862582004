"""A gateway VM loss in the upstream six-region broadcast.

The upstream broadcast test replicates a checkpoint from ``gcp:us-east1``
to six GCP regions. Its cost_min plan at 28 Gbit/s peels into some two
dozen distribution trees whose per-edge connection shares leave many
stages with a single connection on a single VM, so before the re-open
rule any one VM loss orphaned stages and stalled the job. Now a stage
left with no live connection re-opens one between the least-loaded live
VMs of its two regions (``events.reopen_dead_stages``), in every engine,
and only a region without a live VM of the job stalls it.

The engines are pinned to each other and to the benchmark's plain
multicast oracle (``bench/reference/broadcast.py``); the unicast overlay
mix of the benchmark, where the rule never fires, keeps its answers.
"""

from __future__ import annotations

from pathlib import Path

import numpy as np
import pytest

from repro.core import Planner, PlanSpec, default_topology
from repro.core.solver import bnb
from repro.obs import trace
from repro.obs.metrics import REGISTRY
from repro.transfer import ChaosScenario, TransferJob, VMFailure, simulate
from repro.transfer.events import materialize_jobs, reopen_dead_stages

BENCH = Path(__file__).resolve().parents[1] / "bench"
SRC = "gcp:us-east1"  # bc_objstore.py's source and destinations
DSTS = ("gcp:australia-southeast1", "gcp:southamerica-east1",
        "gcp:europe-west4", "gcp:europe-west6", "gcp:asia-east1",
        "gcp:europe-west2")
GOAL = 28.0  # Gbit/s per destination: half the route's multicast max
CHUNKS = 96
ENGINES = ("soa", "ref", "jax")


def _value(name):
    return REGISTRY.counter(name).value


@pytest.fixture(scope="module")
def top():
    return default_topology()


@pytest.fixture(scope="module")
def plan(top):
    p = Planner(top).plan(PlanSpec(
        objective="cost_min", src=SRC, dsts=DSTS, tput_goal_gbps=GOAL,
        volume_gb=CHUNKS * 16.0 / 1024.0,
    ))
    assert p.solver_status == "optimal" and p.validate() == []
    return p


@pytest.fixture(scope="module")
def oracle():
    """``bench/reference/broadcast.py``, imported from the bench tree."""
    import importlib
    import sys

    with pytest.MonkeyPatch.context() as mp:
        mp.syspath_prepend(str(BENCH))
        mod = importlib.import_module("reference.broadcast")
    sys.modules.pop("reference.broadcast", None)
    sys.modules.pop("reference.sim", None)
    sys.modules.pop("reference", None)
    return mod


def _forwarder(plan):
    """The destination with the largest envelope out-flow to others."""
    out = {int(d): float(plan.G[d, :].sum()) for d in plan.dsts}
    return max(out, key=lambda d: (out[d], -d))


def _run(plan, faults, engine, seed=5):
    tr = trace.enable(capacity=1 << 16)
    try:
        res = simulate([TransferJob(plan, "bcast")], faults, engine=engine,
                       seed=seed)
        fails = [e for e in tr.events() if e[1] == "sim.vm_failure"]
    finally:
        trace.disable()
    return res, fails


def _answer(res):
    j = res.jobs[0]
    return (j.status, j.chunks_delivered, j.per_dst_delivered,
            j.retried_chunks, res.events)


@pytest.mark.parametrize("k", range(7))
def test_one_vm_kill_in_any_region_ends_done(top, plan, k):
    """One VM of the job killed at 2 s, in each region of the plan (the
    source and every destination keep a live VM): done in every engine,
    with the same answer, where the stall used to be."""
    regions = [int(r) for r in np.flatnonzero(plan.N > 0)]
    assert len(regions) == 7 and all(plan.N[r] >= 2 for r in regions)
    kill = [VMFailure(t_s=2.0, job=0, region=regions[k], count=1)]
    got = {eng: _run(plan, kill, eng) for eng in ENGINES}
    res, fails = got["soa"]
    j = res.jobs[0]
    assert j.status == "done"
    assert j.per_dst_delivered == {int(d): j.n_chunks for d in plan.dsts}
    # the rule fired: some stage lost its every connection to the kill
    (_, _, _, _, _, args), = fails
    assert args["killed"] == 1 and args["reopened"] > 0
    for eng in ("ref", "jax"):
        other, other_fails = got[eng]
        assert _answer(other) == _answer(res)
        assert other.time_s == pytest.approx(res.time_s, rel=1e-12)
        assert other_fails == fails


def test_engines_match_the_plain_broadcast_reference(top, plan, oracle):
    """soa, ref and jax against the benchmark's multicast oracle (its own
    tree peel and materialization), in float64 rates: per-destination
    counts, events and the completion time."""
    region = _forwarder(plan)
    kill = [VMFailure(t_s=2.0, job=0, region=region, count=1)]
    grid = oracle.Grid(top.tput, top.limit_egress, top.limit_ingress,
                       int(top.limit_conn))
    job = oracle.McJob(plan.G, plan.F, plan.N, plan.M, plan.src,
                       tuple(plan.dsts), plan.volume_gb, 16.0, 0.0)
    want, want_events = oracle.simulate(
        grid, job, [("vm", 2.0, 0, region, 1)], seed=5,
        rate_dtype=np.float64,
    )
    assert want.status == "done"
    for eng in ENGINES:
        res, _ = _run(plan, kill, eng)
        j = res.jobs[0]
        assert (j.status, j.chunks_delivered, j.per_dst_delivered) == (
            want.status, want.chunks_delivered, want.per_dst)
        assert res.events == want_events
        assert j.time_s == pytest.approx(want.time_s, rel=1e-9)
    assert oracle.problem_sizes(grid, job)[0] == len(
        materialize_jobs([TransferJob(plan, "bcast")]).conn_job)


def test_reopen_picks_the_least_loaded_live_vms(top, plan):
    """The re-opened lane is the stage's lowest-index connection, between
    the live VMs of its regions with the fewest live connections (lowest
    id on ties), stage by stage in ascending order; its rate stays."""
    su = materialize_jobs([TransferJob(plan, "bcast")], seed=5)
    region = _forwarder(plan)
    victim = int(np.flatnonzero(su.vm_region == region)[0])
    vm_alive = np.ones(su.vm_job.shape[0], dtype=bool)
    vm_alive[victim] = False
    hit = (su.conn_src == victim) | (su.conn_dst == victim)
    alive = ~hit
    src0, dst0, rate0 = su.conn_src.copy(), su.conn_dst.copy(), \
        su.conn_rate.copy()
    n = reopen_dead_stages(su, su.conn_sid[hit], alive, vm_alive)
    assert n > 0
    # replay the rule by hand over the dead stages, in ascending order
    live = ~hit
    src, dst = src0.copy(), dst0.copy()
    reopened = 0
    for sid in sorted(set(su.conn_sid[hit].tolist())):
        lanes = np.flatnonzero(su.conn_sid == sid)
        if live[lanes].any():
            continue
        lane = lanes[0]
        a, b = su.edges_used[su.conn_edge[lane]]
        pick = []
        for r in (a, b):
            cands = [v for v in range(len(vm_alive))
                     if vm_alive[v] and su.vm_region[v] == r]
            load = {v: int(((src == v) & live).sum()
                           + ((dst == v) & live).sum()) for v in cands}
            pick.append(min(cands, key=lambda v: (load[v], v)))
        src[lane], dst[lane] = pick
        live[lane] = True
        reopened += 1
        assert victim not in pick
    assert reopened == n
    np.testing.assert_array_equal(alive, live)
    np.testing.assert_array_equal(su.conn_src, src)
    np.testing.assert_array_equal(su.conn_dst, dst)
    np.testing.assert_array_equal(su.conn_rate, rate0)


def test_pallas_rate_solver_follows_reopened_lanes(top, plan):
    """The TPU path's f32 Pallas solver (interpret mode here) reads lane
    endpoints from tiles the host rebuilds on a re-open: the discrete
    outcomes of soa, times to the kernel's f32 tolerance."""
    from repro.transfer.flowsim_jax import simulate_multi_jax

    jobs = [TransferJob(plan.with_volume(24 * 16.0 / 1024.0), "bcast")]
    kill = [VMFailure(t_s=0.3, job=0, region=_forwarder(plan), count=1)]
    soa = simulate(jobs, kill, engine="soa", seed=5)
    reopened = _value("sim.conn_reopened")
    got = simulate_multi_jax(jobs, kill, seed=5, _rate_solver="pallas")
    assert reopened > 0 and _value("sim.conn_reopened") == 2 * reopened
    a, b = got.jobs[0], soa.jobs[0]
    assert (a.status, a.per_dst_delivered, a.retried_chunks) == (
        b.status, b.per_dst_delivered, b.retried_chunks)
    assert a.status == "done" and got.events == soa.events
    assert a.time_s == pytest.approx(b.time_s, rel=1e-5)

    # the host hands _segment operands that follow every re-open
    import jax

    from repro.transfer import flowsim_jax
    from repro.transfer.events import sorted_schedule
    from repro.transfer.simconfig import SimConfig

    kill0 = [VMFailure(t_s=0.0, job=0, region=_forwarder(plan), count=1)]
    su = materialize_jobs(jobs, seed=5)
    before = su.conn_src.copy(), su.conn_dst.copy()
    with jax.enable_x64(True):
        sc, cn, st = flowsim_jax._build(su, SimConfig(),
                                        sorted_schedule(jobs, kill0),
                                        "pallas")
        vm_alive = np.ones(su.vm_job.shape[0], dtype=bool)
        _, cn2, _ = flowsim_jax._host_apply_due(
            st, su, sorted_schedule(jobs, kill0), 0, vm_alive,
            np.zeros(1, dtype=np.int64), True, cn, sc, trace.get_tracer())
    nc = su.conn_src.shape[0]
    moved = (su.conn_src != before[0]) | (su.conn_dst != before[1])
    assert moved.any()
    for got_lanes, want in ((cn2.conn_src, su.conn_src),
                            (cn2.conn_dst, su.conn_dst),
                            (cn2.p_src8[0], su.conn_src),
                            (cn2.p_dst8[0], su.conn_dst)):
        np.testing.assert_array_equal(np.asarray(got_lanes)[:nc], want)


def test_region_without_a_live_vm_still_stalls(top, plan):
    """Every VM of a destination killed: its stages cannot re-open, the job
    stalls in every engine, at the same counts."""
    region = _forwarder(plan)
    kill = [VMFailure(t_s=2.0, job=0, region=region,
                      count=int(plan.N[region]))]
    got = {eng: _run(plan, kill, eng)[0] for eng in ENGINES}
    j = got["soa"].jobs[0]
    assert j.status == "stalled"
    assert j.per_dst_delivered[region] < j.n_chunks
    for eng in ("ref", "jax"):
        assert _answer(got[eng]) == _answer(got["soa"])


def test_broadcast_counts_its_trees_stages_and_fanout(top, plan):
    """``sim.mc_trees`` / ``sim.mc_stages`` count the peel, under its
    ``sim.mc_trees`` span; ``sim.fanout_enqueues`` counts every chunk
    copy enqueued to a child stage: on a finished broadcast, each chunk
    once into every stage of its tree but the roots."""
    trees = plan.trees()
    tr = trace.enable(capacity=1 << 16)
    try:
        res = simulate([TransferJob(plan, "bcast")], [], engine="jax",
                       seed=5)
        peels = [e for e in tr.events() if e[1] == "sim.mc_trees"]
    finally:
        trace.disable()
    assert res.jobs[0].status == "done"
    assert [(e[0], e[4]) for e in peels] == [("X", "sim-host")]
    su = materialize_jobs([TransferJob(plan, "bcast")], seed=5)
    assert _value("sim.mc_trees") == 2 * len(trees)
    assert _value("sim.mc_stages") == 2 * su.n_stages
    per_tree = [len(t.edges()) - len(t.roots()) for t in trees]
    want = sum(per_tree[int(t)] for t in su.chunk_path[0])
    assert want > 0
    assert _value("sim.fanout_enqueues") == want
    assert _value("sim.conn_reopened") == 0


def test_multicast_round_down_spans_and_host_lps(top, monkeypatch):
    """``solve_multicast`` runs its stages under the ``bnb.mc_*`` spans and
    counts every LP it solves on the host in ``planner.mc_host_lps``."""
    calls = []
    real = bnb.solve_lp

    def counted(*args, **kw):
        calls.append(1)
        return real(*args, **kw)

    monkeypatch.setattr(bnb, "solve_lp", counted)
    tr = trace.enable(capacity=1 << 16)
    try:
        p = Planner(top, max_relays=6).plan(PlanSpec(
            objective="cost_min", src="gcp:us-central1",
            dsts=("gcp:europe-west1", "gcp:europe-west3", "gcp:europe-west4"),
            tput_goal_gbps=2.0, volume_gb=4.0,
        ))
        spans = [e[1] for e in tr.events() if e[1].startswith("bnb.mc_")]
    finally:
        trace.disable()
    assert p.solver_status == "optimal"
    assert spans == ["bnb.mc_root", "bnb.mc_repair", "bnb.mc_refit",
                     "bnb.mc_scale"]
    assert len(calls) >= 5
    assert _value("planner.mc_host_lps") == len(calls)


# The benchmark's overlay mix (intercontinental.overlay-chaos: three
# cost_min overlay jobs, seeded gray and flapping links, a source VM kill at
# 2 s) on six seeds, as the engines answered before the re-open rule:
# (seed, events, [(status, delivered, retried, time_s) per job]).
OVERLAY_BEFORE = [
    (11, 916, [("done", 256, 21, 8.476254856440871),
               ("done", 256, 0, 8.695796897495397),
               ("done", 256, 0, 19.668728524235473)]),
    (22, 879, [("done", 256, 20, 18.45853733681908),
               ("done", 256, 0, 9.318296004760269),
               ("done", 256, 0, 7.636157859484285)]),
    (33, 964, [("done", 256, 21, 17.256855273377802),
               ("done", 256, 0, 8.679465480506252),
               ("done", 256, 0, 8.36535009027527)]),
    (44, 833, [("done", 256, 20, 14.76515153046473),
               ("done", 256, 0, 7.113034034064073),
               ("done", 256, 0, 10.680616525030949)]),
    (55, 966, [("done", 256, 19, 8.66888100718885),
               ("done", 256, 0, 5.979098384036457),
               ("done", 256, 0, 10.94722924761957)]),
    (66, 805, [("done", 256, 20, 17.203158043820515),
               ("done", 256, 0, 10.156765188685979),
               ("done", 256, 0, 9.60094876328996)]),
]


@pytest.fixture(scope="module")
def overlay_jobs(top):
    src, dst = "azure:canadacentral", "gcp:asia-northeast1"
    planner = Planner(top)
    mx = float(planner.plan(PlanSpec(objective="max_throughput", src=src,
                                     dst=dst)))
    jobs = []
    for i, (frac, arrival) in enumerate([(0.5, 0.0), (0.75, 0.5),
                                         (0.95, 1.0)]):
        p = planner.plan(PlanSpec(
            objective="cost_min", src=src, dst=dst, volume_gb=4.0,
            tput_goal_gbps=frac * mx, backend="numpy",
        ))
        jobs.append(TransferJob(p, f"job{i}", arrival, 16.0))
    return jobs


@pytest.mark.parametrize("seed,events,want", OVERLAY_BEFORE)
def test_overlay_chaos_answers_are_unchanged(top, overlay_jobs, seed,
                                             events, want):
    """The rule never fires on the overlay mix (its killed source keeps a
    live connection on every stage), so each seed's answer is the one the
    engines gave before it."""
    links = sorted({tuple(e) for j in overlay_jobs
                    for e in np.argwhere(j.plan.F > 0).tolist()})
    faults = ChaosScenario(top, seed=seed, links=links, horizon_s=10.0,
                           n_gray=1, n_flapping=1, n_brownouts=0,
                           n_region_outages=0).events(len(overlay_jobs))
    faults.append(VMFailure(t_s=2.0, job=0,
                            region=top.index("azure:canadacentral"),
                            count=1))
    res = simulate(overlay_jobs, faults, engine="soa", seed=seed)
    assert res.events == events
    got = [(j.status, j.chunks_delivered, j.retried_chunks)
           for j in res.jobs]
    assert got == [w[:3] for w in want]
    for j, w in zip(res.jobs, want):
        assert j.time_s == pytest.approx(w[3], rel=1e-12)
    assert _value("sim.conn_reopened") == 0
