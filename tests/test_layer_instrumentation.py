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

from repro.core import default_topology, direct_plan, milp, toy_topology
from repro.core.ron import ron_plan
from repro.core.solver import ipm_jax
from repro.core.solver.ipm import solve_lp
from repro.obs.metrics import REGISTRY
from repro.transfer import TransferJob, VMFailure, flowsim_jax, simulate

SRC, DST = "aws:us-west-2", "aws:eu-central-1"
RELAY_SRC, RELAY_DST = "azure:canadacentral", "gcp:asia-northeast1"
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


@pytest.mark.parametrize("cap,seq", [(1, True), (64, False)])
def test_sequential_cascade_iterations_are_counted(top, cap, seq):
    """A relay buffer of one chunk fills: the loop takes the sequential
    cascade, and counts the iterations that did; at the default buffer
    it never fills here."""
    jobs = [TransferJob(ron_plan(top, RELAY_SRC, RELAY_DST, 0.25,
                                 num_vms=1), "relayed")]
    soa = simulate(jobs, engine="soa", seed=0, relay_buffer_chunks=cap)
    got = simulate(jobs, engine="jax", seed=0, relay_buffer_chunks=cap)
    _same(got, soa)
    n_seq = _value("sim.cascade_seq_iters")
    assert (n_seq > 0) is seq
    assert n_seq <= _value("sim.loop_iters")


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
    from repro.transfer.events import materialize_jobs, sorted_schedule
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
