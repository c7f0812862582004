"""Three-way sim-engine parity: ref / soa / jax behind transfer.sim.simulate.

The dispatcher contract (ISSUE 10) is that every engine consumes the same
materialized scenario and produces the same answer. The pins are graded by
what the engines actually share:

  * soa vs jax — BITWISE equality of every ``JobSimResult`` field, the
    event count and the wall of the run. The jax engine replays the SoA
    semantics on fixed-shape padded arrays (chunk counts are nowhere near
    the 128-lane pad, so every scenario here exercises the validity
    masks); a single ulp of drift anywhere fails these tests.
  * ref vs soa — semantic equality: statuses, chunk counts, retries,
    per-destination deliveries, times and event counts are exact; costs
    and per-edge GB go through a different accumulation order in the
    object-per-connection oracle, so they are pinned to float tolerance;
    ``per_edge_active_s``/``per_edge_obs_gb`` are vectorized-only
    telemetry (documented on ``JobSimResult``) and excluded.
  * Skytrace — the emitted sim-time streams must be identical tuples
    across all three engines: the observability plane cannot depend on
    the engine. The jax engine's dispatcher adds its own wall spans, on
    the ``sim-host`` track.
"""

from __future__ import annotations

import dataclasses

import numpy as np
import pytest

from _sim_scenarios import sim_scenarios
from repro.core import Planner, PlanSpec, default_topology, direct_plan, milp
from repro.obs import trace
from repro.transfer import (
    GrayFailure,
    LinkDegrade,
    LinkRestore,
    TransferJob,
    VMFailure,
    simulate,
)
from repro.transfer.events import materialize_jobs
from repro.transfer.simconfig import ENGINE_NAMES

SRC, DST = "aws:us-west-2", "aws:eu-central-1"
SRC2 = "gcp:us-central1"
MC_SRC = "gcp:us-central1"
MC_DSTS = ("gcp:europe-west1", "gcp:europe-west3", "gcp:europe-west4")

# ref-vs-soa float-tolerance fields (different accumulation order) and the
# vectorized-only telemetry fields.
_COST_FIELDS = ("egress_cost", "vm_cost", "total_cost", "tput_gbps")
_TELEMETRY = ("per_edge_active_s", "per_edge_obs_gb")


@pytest.fixture(scope="module")
def top():
    return default_topology()


def _unicast_jobs(top, volume=0.5):
    return [
        TransferJob(direct_plan(top, SRC, DST, volume, num_vms=2), "a"),
        TransferJob(direct_plan(top, SRC, DST, volume, num_vms=2), "b",
                    arrival_s=1.0),
        TransferJob(direct_plan(top, SRC2, DST, volume, num_vms=2), "c"),
    ]


def run_engines(jobs, faults=(), **kw):
    """Run the scenario on every registered engine, capturing Skytrace."""
    out, traces = {}, {}
    for eng in ENGINE_NAMES:
        tr = trace.enable(capacity=1 << 16)
        try:
            out[eng] = simulate(jobs, faults, engine=eng, **kw)
            traces[eng] = tr.events()
        finally:
            trace.disable()
    return out, traces


def assert_parity(out, traces):
    ref, soa, jx = out["ref"], out["soa"], out["jax"]

    # soa vs jax: bitwise, every field
    assert jx.time_s == soa.time_s
    assert jx.events == soa.events
    for a, b in zip(jx.jobs, soa.jobs):
        assert dataclasses.asdict(a) == dataclasses.asdict(b)

    # ref vs soa: exact on discrete outcomes and times, tolerant on the
    # differently-accumulated money/byte sums
    assert ref.time_s == soa.time_s
    assert ref.events == soa.events
    for a, b in zip(ref.jobs, soa.jobs):
        da, db = dataclasses.asdict(a), dataclasses.asdict(b)
        for k in _COST_FIELDS:
            assert da.pop(k) == pytest.approx(db.pop(k), rel=1e-9)
        for k in _TELEMETRY:
            da.pop(k), db.pop(k)
        ega, egb = da.pop("per_edge_gb"), db.pop("per_edge_gb")
        assert set(ega) == set(egb)
        for e in ega:
            assert ega[e] == pytest.approx(egb[e], rel=1e-9)
        assert da == db

    # the sim-time Skytrace stream is engine-independent, tuple for tuple;
    # wall spans ride a track of their own: the jax engine's dispatcher
    # spans, and in every engine the shared materialization's multicast
    # tree peel
    def sim(tr):
        return [e for e in tr if e[4] == "sim"]

    assert sim(traces["soa"]) == sim(traces["ref"])
    assert sim(traces["jax"]) == sim(traces["ref"])
    peel = [e for e in traces["ref"] if e[4] != "sim"]
    assert [e[:2] for e in traces["soa"] if e[4] != "sim"] == [
        e[:2] for e in peel
    ]
    assert {e[1] for e in peel} <= {"sim.mc_trees"}
    host = [e for e in traces["jax"] if e[4] != "sim"]
    assert {(e[0], e[4]) for e in host} == {("X", "sim-host")}
    assert {e[1] for e in host} == {
        "sim.run", "sim.materialize", "sim.build", "sim.apply_due",
        "sim.segment", "sim.finalize",
    } | {e[1] for e in peel}


def test_plain_three_jobs(top):
    out, traces = run_engines(_unicast_jobs(top), seed=0)
    assert_parity(out, traces)
    assert all(j.status == "done" for j in out["jax"].jobs)


def test_every_rate_event_and_vm_failure(top):
    """One scripted instance of EVERY events.py event class (the full
    RATE_EVENTS group plus VMFailure) against delayed arrivals."""
    s, d, s2 = top.index(SRC), top.index(DST), top.index(SRC2)
    faults = [
        LinkDegrade(t_s=0.5, src=s, dst=d, factor=0.5),
        GrayFailure(t_s=0.8, src=s2, dst=d, factor=0.4),
        VMFailure(t_s=1.0, job=0, region=s, count=1),
        LinkRestore(t_s=1.4, src=s, dst=d, factor=2.0),
        GrayFailure(t_s=1.6, src=s2, dst=d, factor=2.5),
    ]
    out, traces = run_engines(_unicast_jobs(top), faults, seed=0)
    assert_parity(out, traces)
    assert sum(j.retried_chunks for j in out["jax"].jobs) > 0, (
        "the VM failure must actually force retries for this scenario to "
        "exercise the requeue path"
    )


def test_horizon_cut_and_drain(top):
    jobs = _unicast_jobs(top)
    s, d = top.index(SRC), top.index(DST)
    faults = [LinkDegrade(t_s=0.4, src=s, dst=d, factor=0.3)]
    hard, hard_tr = run_engines(jobs, faults, seed=0, horizon_s=1.0)
    assert_parity(hard, hard_tr)
    assert any(j.status == "running" for j in hard["jax"].jobs), (
        "horizon must cut mid-transfer or the scenario tests nothing"
    )
    assert hard["jax"].time_s <= 1.0 + 1e-9

    soft, soft_tr = run_engines(jobs, faults, seed=0, horizon_s=1.0,
                                drain=True)
    assert_parity(soft, soft_tr)
    assert soft["jax"].time_s >= hard["jax"].time_s


def test_link_contention_disabled(top):
    out, traces = run_engines(
        _unicast_jobs(top), seed=0, link_capacity_scale=None,
    )
    assert_parity(out, traces)


def test_multicast_and_unicast_mix(top):
    planner = Planner(top, max_relays=6)
    mc = planner.plan(PlanSpec(
        objective="cost_min", src=MC_SRC, dsts=MC_DSTS,
        tput_goal_gbps=2.0, volume_gb=1.0,
    ))
    assert mc.solver_status == "optimal"
    jobs = [
        TransferJob(mc, "repl"),
        TransferJob(direct_plan(top, SRC, DST, 0.5, num_vms=2), "uni",
                    arrival_s=0.5),
    ]
    kill = next(int(r) for r in mc.dsts if mc.N[r] >= 1)
    faults = [VMFailure(t_s=0.8, job=0, region=kill, count=1)]
    out, traces = run_engines(jobs, faults, seed=0)
    assert_parity(out, traces)
    repl = out["jax"].jobs[0]
    assert repl.per_dst_delivered is not None
    assert set(repl.per_dst_delivered) == {int(r) for r in mc.dsts}


def test_engines_do_not_rebuild_lp_structures(top):
    """Simulation is planning-free: no engine may touch the LP structure
    cache (the planner hot path the fleet PRs pinned)."""
    jobs = _unicast_jobs(top)
    builds0 = milp.N_STRUCT_BUILDS
    run_engines(jobs, seed=0)
    assert milp.N_STRUCT_BUILDS == builds0


def test_tied_arrivals_order_is_deterministic(top):
    """Jobs arriving at the exact same instant materialize in submission
    order — ``MultiSetup.arrival_order`` is the (arrival, index) sort every
    engine consumes, so ties cannot reorder across runs or engines."""
    jobs = [
        TransferJob(direct_plan(top, SRC, DST, 0.25, num_vms=2), "x",
                    arrival_s=1.0),
        TransferJob(direct_plan(top, SRC2, DST, 0.25, num_vms=2), "y",
                    arrival_s=1.0),
        TransferJob(direct_plan(top, SRC, DST, 0.25, num_vms=2), "z"),
    ]
    orders = [
        materialize_jobs(jobs, seed=0).arrival_order for _ in range(2)
    ]
    assert np.array_equal(orders[0], orders[1])
    assert orders[0].tolist() == [2, 0, 1], (
        "equal arrivals must keep submission order"
    )
    out, traces = run_engines(jobs, seed=0)
    assert_parity(out, traces)


def test_pallas_rate_solver_matches_soa_outcomes(top):
    """The f32 Pallas rate solver inside the x64 event loop (the TPU path,
    in interpret mode here) gives the discrete outcomes of soa under a VM
    kill; times agree to the kernel's f32 tolerance."""
    from repro.transfer.flowsim_jax import simulate_multi_jax

    jobs = _unicast_jobs(top)
    faults = [VMFailure(t_s=1.0, job=0, region=top.index(SRC), count=1)]
    soa = simulate(jobs, faults, engine="soa", seed=0)
    got = simulate_multi_jax(jobs, faults, seed=0, _rate_solver="pallas")
    assert sum(j.retried_chunks for j in got.jobs) > 0
    for a, b in zip(got.jobs, soa.jobs):
        assert (a.status, a.chunks_delivered) == (b.status, b.chunks_delivered)
        assert a.time_s == pytest.approx(b.time_s, rel=1e-5)


@pytest.fixture(scope="module")
def scenarios(top):
    return sim_scenarios(top)


@pytest.mark.parametrize("name", ["overlay", "multicast"])
def test_pallas_event_loop_matches_masked_under_a_vm_kill(scenarios, name):
    """The TPU path's loop (f32 Pallas rates, interpreted here, and the
    dense f64 per-(job, edge) sum that the bitwise soa pins never run)
    gives the masked loop's discrete outcomes; times and per-edge volumes
    agree to the kernel's f32 tolerance."""
    from repro.transfer.flowsim_jax import simulate_multi_jax

    jobs, faults = scenarios[name]
    masked = simulate_multi_jax(jobs, faults, seed=0, _rate_solver="masked")
    got = simulate_multi_jax(jobs, faults, seed=0, _rate_solver="pallas")
    assert sum(j.retried_chunks for j in got.jobs) > 0  # the kill lands
    assert got.events == masked.events
    for a, b in zip(got.jobs, masked.jobs):
        assert (a.status, a.chunks_delivered, a.per_dst_delivered) == (
            b.status, b.chunks_delivered, b.per_dst_delivered)
        assert a.time_s == pytest.approx(b.time_s, rel=1e-5)
        assert a.per_edge_gb.keys() == b.per_edge_gb.keys()
        assert a.per_edge_gb == pytest.approx(b.per_edge_gb, rel=1e-5)


@pytest.mark.parametrize("seed", range(4))
def test_onehot_sum_matches_segment_sum(seed):
    """The loop's dense reduction against ``jax.ops.segment_sum``: masked
    lanes parked on the dump row ``ns``, ids outside the segments (an
    undelivered slot's -1) and empty segments. Counts are exact; the f64
    sum adds in another order."""
    import jax
    from jax.ops import segment_sum

    from repro.transfer.flowsim_jax import _onehot_sum

    rng = np.random.default_rng([16, seed])
    lanes, ns = int(rng.integers(1, 3000)), int(rng.integers(1, 80))
    used = rng.choice(ns, size=max(1, ns // 2), replace=False)
    mask = rng.random(lanes) < 0.7
    idx = np.where(mask, rng.choice(used, lanes), ns)
    idx[rng.random(lanes) < 0.05] = -1
    w = np.where(mask, rng.exponential(size=lanes), 0.0)
    with jax.enable_x64(True):
        idx_j = jax.numpy.asarray(idx)
        cnt = np.asarray(_onehot_sum(jax.numpy.asarray(mask), idx_j, ns + 1))
        want = np.asarray(segment_sum(mask.astype(np.int64), idx_j, ns + 1))
        got_f = np.asarray(_onehot_sum(jax.numpy.asarray(w), idx_j, ns + 1))
        want_f = np.asarray(segment_sum(w, idx_j, ns + 1))
    assert cnt.dtype == np.int32 and np.array_equal(cnt, want)
    empty = np.setdiff1d(np.arange(ns), used)
    assert cnt[ns] == 0 and (cnt[empty] == 0).all()
    assert got_f.dtype == np.float64
    np.testing.assert_allclose(got_f, want_f, rtol=1e-12, atol=0)


def test_rate_solver_rule():
    """Pallas on TPU while the kernel's VMEM estimate fits, masked beyond
    that and on every other platform."""
    from repro.transfer.flowsim_jax import _rate_solver_for

    assert _rate_solver_for("cpu", 3055, 75, 11) == "masked"
    assert _rate_solver_for("tpu", 3055, 75, 11) == "pallas"
    assert _rate_solver_for("tpu", 200_000, 75, 11) == "masked"
