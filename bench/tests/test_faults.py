"""A run with the timed path broken underneath must come out not correct.

Each test skips the harness's look for a chip (the checks run on the CPU
here), plants one fault in the program, drives a whole run of a cell
through ``run.main`` and reads its result line."""

import json

import numpy as np
import pytest

import run
from repro.core import planner
from repro.core.solver import ipm_batch
from repro.transfer import flowsim_jax


@pytest.fixture
def bench_run(monkeypatch, capsys):
    monkeypatch.setattr(run, "check_devices", lambda devs, chips: None)

    def go(cell, seconds=1):
        rc = run.main(["--workload", cell, "--seed", "2147483659",
                       "--seconds", str(seconds), "--trace", "0"])
        assert rc == 0
        return json.loads(capsys.readouterr().out.strip().splitlines()[-1])

    return go


def _wrap_batches(monkeypatch, fault):
    real = ipm_batch.solve_lp_batches_auto

    def broken(problems, *, engine="auto"):
        return [fault(x, fun, ok) for x, fun, ok in real(problems,
                                                           engine=engine)]

    monkeypatch.setattr(ipm_batch, "solve_lp_batches_auto", broken)


def test_sound_plan_run_is_correct(bench_run):
    assert bench_run("fig6-aws.admit-wave")["correct"] is True


def test_altered_lp_answers_fail(bench_run, monkeypatch):
    _wrap_batches(monkeypatch, lambda x, f, ok: (np.asarray(x) * 1.001, f,
                                                 ok))
    assert bench_run("fig6-aws.admit-wave")["correct"] is False


def test_lp_state_left_unchanged_fails(bench_run, monkeypatch):
    # the solver hands back a start iterate it never moved, as certified
    _wrap_batches(monkeypatch, lambda x, f, ok: (np.ones_like(x), f, ok))
    assert bench_run("fig6-aws.admit-wave")["correct"] is False


def test_under_provisioned_plans_fail(bench_run, monkeypatch):
    # the round-down sizes N and M for 95% of each goal and states the
    # throughput its plan carries: every constraint holds, the goal not
    real = planner.solve_milp_batched

    def short(top, s, t, goals):
        return real(top, s, t, np.asarray(goals) * 0.95)

    monkeypatch.setattr(planner, "solve_milp_batched", short)
    got = bench_run("fig6-aws.admit-wave")
    short_of = got["checks"]["plan_shortfall"]
    assert short_of["value"] > short_of["limit"]
    assert got["correct"] is False


def test_half_of_each_lp_batch_left_out_fails(bench_run, monkeypatch):
    def half(x, f, ok):
        x, f = np.array(x), np.array(f)
        h = (len(x) + 1) // 2
        x[h:], f[h:] = x[0], f[0]
        return x, f, ok

    _wrap_batches(monkeypatch, half)
    assert bench_run("fig6-aws.admit-wave")["correct"] is False


def test_sound_sim_run_is_correct(bench_run):
    assert bench_run("intercontinental.overlay-chaos")["correct"] is True


@pytest.mark.parametrize("cell", ["intercontinental.overlay-chaos",
                                  "fig6-aws.bulk-sim"])
def test_event_loop_state_left_unchanged_fails(bench_run, monkeypatch, cell):
    monkeypatch.setattr(flowsim_jax, "_segment", lambda st, cn, sc: st)
    assert bench_run(cell)["correct"] is False


@pytest.mark.parametrize("cell", ["intercontinental.overlay-chaos",
                                  "fig6-aws.bulk-sim"])
def test_altered_completion_time_fails(bench_run, monkeypatch, cell):
    real = flowsim_jax._finalize

    def altered(*args, **kw):
        res = real(*args, **kw)
        res.jobs[0].time_s *= 1.0 + 1e-4
        return res

    monkeypatch.setattr(flowsim_jax, "_finalize", altered)
    assert bench_run(cell)["correct"] is False


@pytest.mark.parametrize("cell", ["intercontinental.overlay-chaos",
                                  "fig6-aws.bulk-sim"])
def test_altered_event_count_fails(bench_run, monkeypatch, cell):
    real = flowsim_jax._finalize

    def altered(*args, **kw):
        res = real(*args, **kw)
        res.events = round(res.events * 1.01)  # one event in a hundred
        return res

    monkeypatch.setattr(flowsim_jax, "_finalize", altered)
    assert bench_run(cell)["correct"] is False
