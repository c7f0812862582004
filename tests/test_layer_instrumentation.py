"""Where the host layers count and mark their work.

The jax sim dispatcher and the device IPM count their work in the
``obs.metrics`` registry at the boundary where it happens and wrap it in
``obs.region`` spans, which a JAX profiler trace holds under their exact
names on the calling thread's line; the compiled event loop and IPM carry
named scopes in their op metadata. None of it changes an answer: the jax
sim stays bitwise equal to the numpy engine, and the device IPM's answers
stay those of the numpy reference.
"""

from __future__ import annotations

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _sim_scenarios import sim_scenarios
from repro.core import (
    Planner,
    PlanSpec,
    default_topology,
    direct_plan,
    milp,
    toy_topology,
)
from repro.core.ron import ron_plan
from repro.core.solver import ipm_jax
from repro.core.solver.ipm import solve_lp
from repro.obs.metrics import REGISTRY
from repro.transfer import TransferJob, VMFailure, flowsim_jax, simulate
from repro.transfer.events import materialize_jobs

SRC, DST = "aws:us-west-2", "aws:eu-central-1"
RELAY_SRC, RELAY_DST = "azure:canadacentral", "gcp:asia-northeast1"
MC_SRC = "gcp:us-central1"
MC_DSTS = ("gcp:europe-west1", "gcp:europe-west3", "gcp:europe-west4")
SIM_SPANS = ("sim.run", "sim.materialize", "sim.build", "sim.apply_due",
             "sim.segment", "sim.finalize")
IPM_SPANS = ("ipm.pack", "ipm.device_call", "ipm.certify")


@pytest.fixture(scope="module")
def top():
    return default_topology()


def _value(name):
    return REGISTRY.counter(name).value


def _same(a, b):
    assert a.time_s == b.time_s and a.events == b.events
    for x, y in zip(a.jobs, b.jobs):
        assert dataclasses.asdict(x) == dataclasses.asdict(y)


def _direct_jobs(top):
    return [
        TransferJob(direct_plan(top, SRC, DST, 0.5, num_vms=2), "a"),
        TransferJob(direct_plan(top, SRC, DST, 0.5, num_vms=2), "b",
                    arrival_s=1.0),
    ]


def _toy_lp_batch(goals):
    top = toy_topology(n=6, seed=4)
    lp = milp.build_lp(top, 0, 1, 1.0)
    b = np.tile(lp.b_ub[None, :], (len(goals), 1))
    b[:, lp.row_4c] = -np.asarray(goals)
    b[:, lp.row_4d] = -np.asarray(goals)
    return top, lp, (lp.c, lp.A_ub, b, lp.A_eq, lp.b_eq)


# ------------------------------------------------------------ sim counters


def test_sim_counters_count_the_dispatch_loop(top, monkeypatch):
    jobs = _direct_jobs(top)
    faults = [VMFailure(t_s=0.6, job=0, region=top.index(SRC), count=1)]
    soa = simulate(jobs, faults, engine="soa", seed=0)
    calls = []
    real = flowsim_jax._segment

    def counted(st, cn, sc):
        calls.append(sc)
        return real(st, cn, sc)

    monkeypatch.setattr(flowsim_jax, "_segment", counted)
    got = simulate(jobs, faults, engine="jax", seed=0)
    _same(got, soa)
    segs = _value("sim.segments")
    assert segs == len(calls) >= 3  # the arrival and the fault re-enter
    # each pass of the loop reads draining, td_n and stop, and at most
    # now (applying events), it, draining and now again
    assert 3 * segs <= _value("sim.host_syncs") <= 7 * segs
    assert _value("sim.loop_iters") >= got.events > 0
    assert _value("sim.cascade_seq_iters") == 0  # no relay


@pytest.fixture(scope="module")
def multicast_plan(top):
    """A cost_min replication plan whose stage DAG fans out."""
    planner = Planner(top, max_relays=6)
    return planner.plan(PlanSpec(
        objective="cost_min", src=MC_SRC, dsts=MC_DSTS,
        tput_goal_gbps=2.0, volume_gb=1.0,
    ))


@pytest.mark.parametrize("kind,volume,cap,seq", [
    pytest.param("overlay", 0.25, 1, True, id="1-True"),
    pytest.param("overlay", 0.25, 64, False, id="64-False"),
    pytest.param("overlay", 2.0, 2, True, id="2-True"),
    pytest.param("multicast", 1.0, 1, True, id="multicast-1-True"),
])
def test_sequential_cascade_iterations_are_counted(top, multicast_plan,
                                                   kind, volume, cap, seq):
    """A small relay buffer fills: the loop takes the multi-pass cascade,
    counts the iterations that did and their passes (at least one each,
    at most one per level of the stage DAG plus one), and stays bitwise
    equal to the numpy engine; at the default buffer it never fills
    here."""
    if kind == "overlay":
        plan = ron_plan(top, RELAY_SRC, RELAY_DST, volume, num_vms=1)
    else:
        plan = multicast_plan
        assert plan.volume_gb == volume
    jobs = [TransferJob(plan, "relayed")]
    su = materialize_jobs(jobs, seed=0)
    assert su.max_hops >= 2
    if kind == "multicast":
        assert max(len(c) for c in su.stage_children) >= 2  # fan-out
    soa = simulate(jobs, engine="soa", seed=0, relay_buffer_chunks=cap)
    got = simulate(jobs, engine="jax", seed=0, relay_buffer_chunks=cap)
    _same(got, soa)
    n_seq = _value("sim.cascade_seq_iters")
    passes = _value("sim.cascade_seq_passes")
    assert (n_seq > 0) is seq
    assert n_seq <= _value("sim.loop_iters")
    assert n_seq <= passes <= (su.max_hops + 1) * n_seq


def _reference_cascade(chunk_arr, remaining, q_head, relay_occ, *, q_tail,
                       ready_buf, conn_sid, children, stage_hop,
                       chunk_size, usable, cap):
    """``flowsim``'s cascade: passes of ``try_refill`` over the idle lanes
    with queued work, in ascending lane order, until one takes nothing."""
    chunk_arr, remaining = chunk_arr.copy(), remaining.copy()
    q_head, relay_occ = q_head.copy(), relay_occ.copy()
    qcap = ready_buf.shape[1]
    passes = 0
    while True:
        passes += 1
        idle = (chunk_arr < 0) & usable
        if not idle.any():
            break
        queue_work = (q_tail - q_head)[conn_sid] > 0
        progressed = False
        for ci in np.flatnonzero(idle & queue_work):
            sid = conn_sid[ci]
            if any(relay_occ[k] >= cap for k in children[sid] if k >= 0):
                continue
            if q_tail[sid] == q_head[sid]:
                continue
            chunk_arr[ci] = ready_buf[sid, q_head[sid] % qcap]
            q_head[sid] += 1
            remaining[ci] = chunk_size[ci]
            if stage_hop[sid] > 0:
                relay_occ[sid] -= 1
            progressed = True
        if not progressed:
            break
    return (chunk_arr, remaining, q_head, relay_occ), passes


# Stage DAGs over the four stages of two overlay jobs: children, hop of each
# stage, and per stage (idle lanes, queued chunks); a relay stage's buffer
# holds its queue. Each child drains below the cap only by its own takes.
_DAGS = {
    # the parent's lanes come first: the reference unblocks one level a pass
    "child_sid_above_parent": ({0: [1], 1: [2]}, [0, 1, 2, 0], 3,
                               [(4, 5), (2, 3), (2, 4), (0, 0)]),
    # the child's lanes come first: the reference unblocks in one pass
    "child_sid_below_parent": ({3: [2], 2: [1]}, [0, 2, 1, 0], 3,
                               [(0, 0), (2, 4), (2, 3), (4, 5)]),
    # fan-out: one child drains below the cap, the other stays at it
    "fan_out_one_full": ({0: [1, 2]}, [0, 1, 1, 0], 2,
                         [(3, 6), (1, 2), (1, 3), (0, 0)]),
    "fan_out_both_drain": ({0: [1, 2]}, [0, 1, 1, 0], 2,
                           [(3, 6), (1, 2), (2, 3), (0, 0)]),
}


@pytest.mark.parametrize("dag", sorted(_DAGS))
def test_cascade_passes_reach_the_reference_fixed_point(top, dag):
    """``_cascade_seq`` alone against a transliteration of the reference's
    pass loop, on states where a child stage unblocks its parent only
    through its own takes."""
    from repro.transfer.events import sorted_schedule
    from repro.transfer.simconfig import SimConfig

    kids, hops, cap, per_stage = _DAGS[dag]
    jobs = [TransferJob(ron_plan(top, RELAY_SRC, RELAY_DST, 0.25,
                                 num_vms=1), n) for n in ("a", "b")]
    su = materialize_jobs(jobs, seed=0)
    assert su.n_stages == 4
    rng = np.random.default_rng(7)
    with jax.enable_x64(True):
        sc, cn, st = flowsim_jax._build(
            su, SimConfig(), sorted_schedule(jobs, ()), "masked")
        ns, ncp, qcap = sc.ns, sc.ncp, sc.qcap
        children = np.full((ns + 1, 2), -1, dtype=np.int64)
        for s, ks in kids.items():
            children[s, : len(ks)] = ks
        stage_hop = np.zeros(ns + 1, dtype=np.int64)
        stage_hop[:ns] = hops
        conn_sid = np.asarray(cn.conn_sid)
        valid = np.asarray(cn.conn_valid)
        chunk_arr = np.zeros(ncp, dtype=np.int64)  # busy lanes
        alive = valid.copy()
        q_head = np.zeros(ns + 1, dtype=np.int64)
        q_tail = np.zeros(ns + 1, dtype=np.int64)
        relay_occ = np.zeros(ns + 1, dtype=np.int64)
        ready_buf = np.zeros((ns + 1, qcap), dtype=np.int64)
        for s, (n_idle, n_queued) in enumerate(per_stage):
            lanes = rng.permutation(np.flatnonzero(valid & (conn_sid == s)))
            chunk_arr[lanes[: n_idle + 1]] = -1
            alive[lanes[n_idle]] = False  # idle but dead: never takes
            q_head[s] = qcap - 2  # the queue wraps round the ring
            q_tail[s] = q_head[s] + n_queued
            pos = (q_head[s] + np.arange(n_queued)) % qcap
            ready_buf[s, pos] = rng.permutation(qcap)[:n_queued]
            relay_occ[s] = n_queued if hops[s] > 0 else 0
        remaining = np.where(chunk_arr >= 0, 0.5, 0.0)
        cn = cn._replace(children=jnp.asarray(children),
                         stage_hop=jnp.asarray(stage_hop),
                         relay_cap=jnp.int64(cap))
        st = st._replace(
            arrived=jnp.ones_like(st.arrived),
            conn_alive=jnp.asarray(alive), q_tail=jnp.asarray(q_tail),
            ready_buf=jnp.asarray(ready_buf))
        small = tuple(jnp.asarray(a)
                      for a in (chunk_arr, remaining, q_head, relay_occ))
        got, passes = jax.jit(flowsim_jax._cascade_seq, static_argnums=3)(
            small, st, cn, sc)
        got = jax.device_get(got)
    want, ref_passes = _reference_cascade(
        chunk_arr, remaining, q_head, relay_occ, q_tail=q_tail,
        ready_buf=ready_buf, conn_sid=conn_sid, children=children,
        stage_hop=stage_hop, chunk_size=np.asarray(cn.chunk_size),
        usable=alive, cap=cap)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a, b)
    assert ref_passes >= 2
    assert (want[2] > q_head).any()  # some stage took
    assert 2 <= int(passes) <= _levels(kids) + 1


def _levels(kids):
    """Stages on the longest chain of the DAG ``kids``."""
    def down(s):
        return 1 + max((down(k) for k in kids.get(s, ())), default=0)

    return max(down(s) for s in kids)


def test_vm_kill_at_a_full_relay_buffer_matches_soa(top, monkeypatch):
    """A relay VM dies while its stage's buffer is at the cap, under the
    overlay benchmark's seeded chaos (one gray and one flapping link on
    the plan's links): the jax engine stays bitwise equal to the numpy
    engine."""
    from repro.transfer import ChaosScenario

    cap = 2
    plan = ron_plan(top, RELAY_SRC, RELAY_DST, 1.0, num_vms=2)
    jobs = [TransferJob(plan, "relayed", 0.0, 4.0)]
    relay = next(int(r) for r in np.flatnonzero(plan.N)
                 if r not in (plan.src, plan.dst))
    seed = int(np.random.default_rng([3_000_000_123, 1]).integers(2**62))
    links = sorted({tuple(e) for e in np.argwhere(plan.F > 0).tolist()})
    faults = ChaosScenario(top, seed=seed, links=links, horizon_s=10.0,
                           n_gray=1, n_flapping=1, n_brownouts=0,
                           n_region_outages=0).events(len(jobs))
    faults.append(VMFailure(t_s=0.5, job=0, region=relay, count=1))

    at_kill = []
    real = flowsim_jax._host_apply_due

    def watched(st, su, sched, ptr, *args):
        now = float(st.now)
        if any(isinstance(ev, VMFailure) and t <= now + 1e-9
               for t, _, ev in sched[ptr:]):
            at_kill.append(int(np.max(np.asarray(st.relay_occ))))
        return real(st, su, sched, ptr, *args)

    monkeypatch.setattr(flowsim_jax, "_host_apply_due", watched)
    soa = simulate(jobs, faults, engine="soa", seed=seed,
                   relay_buffer_chunks=cap)
    got = simulate(jobs, faults, engine="jax", seed=seed,
                   relay_buffer_chunks=cap)
    _same(got, soa)
    assert len(at_kill) == 1 and at_kill[0] >= cap
    assert got.jobs[0].retried_chunks > 0
    assert _value("sim.cascade_seq_iters") > 0


# ------------------------------------------------------------ IPM counters


def test_device_ipm_counters_and_answers():
    goals = [0.5, 1.5, 2.5, 3.5, 4.5]
    top, lp, problem = _toy_lp_batch(goals)
    ((x, fun, ok),) = ipm_jax.solve_lp_batches([problem])
    for i, g in enumerate(goals):
        lp_i = milp.build_lp(top, 0, 1, float(g))
        ref = solve_lp(lp_i.c, lp_i.A_ub, lp_i.b_ub, lp_i.A_eq, lp_i.b_eq)
        assert ok[i] == ref.ok
        if ref.ok:
            assert fun[i] == pytest.approx(ref.fun, rel=1e-5, abs=1e-8)

    assert _value("ipm.device_calls") == 1
    rows, real = _value("ipm.batch_rows"), _value("ipm.batch_rows_real")
    assert (rows, real) == (ipm_jax._MIN_BATCH, len(goals))
    trips = _value("ipm.loop_trips")
    assert 0 < trips <= ipm_jax._MAX_ITER
    assert _value("ipm.row_trips") == rows * trips
    batched = _value("ipm.sample_iters")
    assert len(goals) <= batched <= _value("ipm.row_trips")

    # a sample's own count does not depend on its batch: solved alone, the
    # loop runs just its iterations
    alone = 0
    for i in range(len(goals)):
        REGISTRY.reset()
        c, A_ub, b, A_eq, b_eq = problem
        ipm_jax.solve_lp_batches([(c, A_ub, b[i : i + 1], A_eq, b_eq)])
        assert _value("ipm.sample_iters") == _value("ipm.loop_trips")
        alone += _value("ipm.loop_trips")
    assert alone == batched


# --------------------------------------------------- profiler and scopes


def _python_line_events(trace_dir):
    from jax.profiler import ProfileData

    (pb,) = trace_dir.rglob("*.xplane.pb")
    pd = ProfileData.from_file(str(pb))
    for plane in pd.planes:
        for line in plane.lines:
            names = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                     for e in line.events]
            if any(n == "test.request" for n, _, _ in names):
                return line.name, names
    raise AssertionError("no line holds the outer annotation")


def test_profiler_trace_holds_the_layer_spans(top, tmp_path):
    """The spans land in a JAX profiler trace under their exact names, on
    the calling thread's line, inside the caller's annotation."""
    jobs = _direct_jobs(top)
    _, _, problem = _toy_lp_batch([0.5, 1.5])
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("test.request"):
            simulate(jobs, engine="jax", seed=0)
            ipm_jax.solve_lp_batches([problem])
    finally:
        jax.profiler.stop_trace()
    line, events = _python_line_events(tmp_path)
    assert line == "python"
    ((_, lo, hi),) = [e for e in events if e[0] == "test.request"]
    by_name = {}
    for name, s, e in events:
        by_name.setdefault(name, []).append((s, e))
    for name in SIM_SPANS + IPM_SPANS:
        assert name in by_name, name
        assert all(lo <= s <= e <= hi for s, e in by_name[name]), name
    ((run_s, run_e),) = by_name["sim.run"]
    for name in SIM_SPANS[1:]:
        assert all(run_s <= s <= e <= run_e for s, e in by_name[name])
    assert len(by_name["sim.segment"]) == _value("sim.segments")
    assert len(by_name["ipm.device_call"]) == _value("ipm.device_calls")


def _op_names(hlo: str) -> set:
    return set(re.findall(r'op_name="([^"]*)"', hlo))


def test_event_loop_carries_its_scope_names(top):
    from repro.transfer.events import sorted_schedule
    from repro.transfer.simconfig import SimConfig

    jobs = _direct_jobs(top)
    su = materialize_jobs(jobs, seed=0)
    with jax.enable_x64(True):
        sc, cn, st = flowsim_jax._build(
            su, SimConfig(), sorted_schedule(jobs, ()), "masked")
        names = _op_names(
            flowsim_jax._segment.lower(st, cn, sc).compile().as_text())
    for scope in ("cascade_batch", "cascade_seq", "rate_solve",
                  "state_update"):
        assert any(f"/{scope}/" in n for n in names), scope


def _scatter_adds(hlo: str) -> list[tuple[str, str]]:
    """(type, op_name) of each scatter in ``hlo`` whose update
    computation adds."""
    root_op, comp, found = {}, None, []
    for line in hlo.splitlines():
        if head := re.match(r"(?:ENTRY )?(\S+) .*\{$", line):
            comp = head.group(1)
        elif root := re.match(r"\s*ROOT \S+ = .*? ([a-z][\w-]*)\(", line):
            root_op[comp] = root.group(1)
        elif sc := re.search(r"= (\S+) scatter\(.*to_apply=([^,\s]+)",
                             line):
            name = re.search(r'op_name="([^"]*)"', line)
            found.append((sc.group(1), sc.group(2),
                          name.group(1) if name else ""))
    return [(t, n) for t, to_apply, n in found if root_op[to_apply] == "add"]


@pytest.fixture(scope="module")
def scenarios(top):
    return sim_scenarios(top)


@pytest.mark.parametrize("name", ["bulk", "overlay", "multicast"])
def test_event_loop_sums_without_scatter_adds(scenarios, name):
    """The loop's segment sums are dense one-hot reductions. Lowered with
    the TPU's rate solver (the Pallas kernel, interpreted here) no
    scatter-add is left; under "masked" the one outside the rate solve
    (the masked solver's own sums) is the f64 per-(job, edge) Gbit moved,
    kept in ``np.bincount`` order for bitwise parity with soa."""
    from repro.transfer.events import sorted_schedule
    from repro.transfer.simconfig import SimConfig

    jobs, faults = scenarios[name]
    su = materialize_jobs(jobs, seed=0)
    adds = {}
    with jax.enable_x64(True):
        for solver in ("pallas", "masked"):
            sc, cn, st = flowsim_jax._build(
                su, SimConfig(), sorted_schedule(jobs, faults), solver)
            assert (sc.maxch > 0) is (name != "bulk")
            adds[solver] = _scatter_adds(flowsim_jax._segment.lower(
                st, cn, sc).as_text(dialect="hlo", debug_info=True))
    assert adds["pallas"] == []
    loop = [(t, n) for t, n in adds["masked"] if "/rate_solve/" not in n]
    assert len(loop) == 1, loop
    ((typ, op_name),) = loop
    assert typ.startswith("f64[") and "/state_update/" in op_name


def test_device_ipm_carries_its_scope_names():
    shape = jax.ShapeDtypeStruct
    bp, mp, n_pad = ipm_jax._MIN_BATCH, ipm_jax._MIN_ROWS, ipm_jax._MIN_COLS
    with jax.enable_x64(True):
        f64 = jnp.float64
        names = _op_names(ipm_jax._solve_batched.lower(
            shape((bp, mp, n_pad), f64), shape((bp, mp), f64),
            shape((bp, n_pad), f64), shape((bp, mp), f64),
            shape((bp, n_pad), f64),
        ).compile().as_text())
    for scope in ("factor", "predictor", "corrector"):
        assert any(f"/{scope}/" in n for n in names), scope
