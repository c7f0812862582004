"""The precision control: the reference one precision step below the
stated one, in the program's place, must fail the comparison that the
program passes.

Here at the cells' own sizes, on the CPU, one request each: the plan
cell's flows re-fitted in float32 (stated: float64), and the sim cells'
reference with its times in float32 (stated: float64). The chip readings
that set the limits are in PERF.md."""

import json

import pytest

import run
import workload

LIMITS = json.loads(run.LIMITS.read_text())


def _mix(cell, seed):
    bench = run.load_json(run.ROOT / "BENCHMARK.json")
    w = next(x for x in bench["workloads"] if x["name"] == cell)
    config = run.load_json(run.BENCH / "configs" / f"{w['config']}.json")
    traffic = workload.resolve(run.load_json(
        run.BENCH / "traffic" / f"{w['traffic']}.json"), config)
    return workload.build(config, traffic, seed)


def _fails(got):
    return [k for k, v in got.items() if not v <= LIMITS[k]]


@pytest.mark.parametrize("cell", ["fig6-aws.admit-wave",
                                  "intercontinental.overlay-chaos",
                                  "fig6-aws.bulk-sim"])
@pytest.mark.parametrize("seed", [7, 2**31 + 11])
def test_control_fails_where_the_program_passes(cell, seed):
    d = _mix(cell, seed)
    out = d.request(0)
    assert _fails(d.check(out)) == []
    assert _fails(d.control(out)) != []


def test_unknown_kind_is_refused():
    with pytest.raises(FileNotFoundError):
        workload.load_kind("no-such-kind")
