"""The broadcast cell's comparison must fail a run whose answers are off.

One module fixture plans the cell's deployment once (the host planning
of its set-up), at fewer chunks so that the CPU runs stay short. Each
test plants one fault in the program's result, drives one request
through the kind and judges it as ``run.main`` does: one failed answer
makes the run not correct. A whole sound run of the cell goes through
``run.main`` as the other cells' do."""

import json

import pytest

import run
import workload
from repro.transfer import flowsim_jax

CELL = "gcp-broadcast.fanout-vmkill"
SEED = 2147483659


@pytest.fixture(scope="module")
def mix():
    bench = run.load_json(run.ROOT / "BENCHMARK.json")
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    config = run.load_json(run.BENCH / "configs" / f"{cell['config']}.json")
    config["broadcast_chunks"] = 256
    traffic = workload.resolve(run.load_json(
        run.BENCH / "traffic" / f"{cell['traffic']}.json"), config)
    return workload.build(config, traffic, SEED)


def _judged(mix, monkeypatch, alter=None):
    if alter is not None:
        real = flowsim_jax._finalize

        def altered(*args, **kw):
            res = real(*args, **kw)
            alter(res)
            return res

        monkeypatch.setattr(flowsim_jax, "_finalize", altered)
    out = mix.request(0)
    records = [{"k": 0, "t1": 0.0, "out": out, "err": None}]
    failed, worst = run.judge(mix, records, run.load_json(run.LIMITS), {0})
    return failed, worst


def _one_short(res):
    j = res.jobs[0]
    d = max(j.per_dst_delivered)
    j.per_dst_delivered[d] -= 1


def _late(res):
    res.jobs[0].time_s *= 1.0 + 1e-4


def _more_events(res):
    res.events = round(res.events * 1.01)  # one event in a hundred


def _stalled(res):
    res.jobs[0].status = "stalled"


def test_sound_broadcast_answer_passes(mix, monkeypatch):
    failed, worst = _judged(mix, monkeypatch)
    assert failed == 0
    assert set(worst) == {"sim_gap", "event_gap"}


@pytest.mark.parametrize("fault", [_one_short, _late, _more_events,
                                   _stalled])
def test_planted_fault_fails(mix, monkeypatch, fault):
    failed, _ = _judged(mix, monkeypatch, fault)
    assert failed == 1


def test_sound_broadcast_run_is_correct(monkeypatch, capsys):
    monkeypatch.setattr(run, "check_devices", lambda devs, chips: None)
    rc = run.main(["--workload", CELL, "--seed", str(SEED), "--seconds", "1",
                   "--trace", "0"])
    assert rc == 0
    got = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert got["correct"] is True
    assert set(got["metrics"]) == {"sim_events_per_s", "setup_s"}
