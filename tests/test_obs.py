"""Skytrace observability plane: registry, tracer, export, determinism.

Pins the PR-9 invariants: the same seed produces a byte-identical
Chrome-trace across processes, the vectorized and reference simulators
emit identical sim-event streams, the ring buffer bounds memory, and the
disabled tracer is a no-op.
"""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from repro.obs import (
    REGISTRY,
    disable,
    enable,
    get_registry,
    get_tracer,
    region,
    text_timeline,
    to_chrome_trace,
    trace_json,
)
from repro.obs.__main__ import trace_chaos_scenario
from repro.obs.metrics import Counter, Gauge, Histogram

SRC_DIR = Path(__file__).resolve().parent.parent / "src"


# ---------------------------------------------------------------- metrics


def test_registry_get_or_create_and_type_conflict():
    c = REGISTRY.counter("test.hits")
    assert isinstance(c, Counter)
    assert REGISTRY.counter("test.hits") is c  # same instrument back
    with pytest.raises(TypeError, match="already registered"):
        REGISTRY.gauge("test.hits")


def test_snapshot_skips_empty_and_filters_by_prefix():
    REGISTRY.counter("alpha.hits").inc(3)
    REGISTRY.counter("alpha.misses")  # never incremented: absent
    REGISTRY.gauge("alpha.depth").set(2.5)
    REGISTRY.histogram("beta.lat_s").observe(0.25)
    REGISTRY.histogram("beta.lat_s").observe(0.75)
    snap = REGISTRY.snapshot(("alpha.",))
    assert snap == {"alpha.hits": 3, "alpha.depth": 2.5}
    hist = REGISTRY.snapshot(("beta.",))["beta.lat_s"]
    assert hist == {"count": 2, "total": 1.0, "min": 0.25, "max": 0.75}
    full = REGISTRY.snapshot()
    assert "alpha.hits" in full and "beta.lat_s" in full


def test_reset_zeroes_in_place_so_cached_refs_stay_live():
    c = REGISTRY.counter("test.cached")
    g = REGISTRY.gauge("test.gauge")
    h = REGISTRY.histogram("test.hist")
    c.inc(7)
    g.set(1.0)
    h.observe(4.0)
    get_registry().reset()
    assert c.value == 0 and g.value == 0.0 and h.count == 0
    c.inc()  # the pre-reset reference still feeds the registry
    assert REGISTRY.counter("test.cached").value == 1
    assert REGISTRY.snapshot(("test.gauge",)) == {}  # gauge unset again


def test_milp_struct_builds_alias_tracks_registry_counter():
    from repro.core import Planner, PlanSpec, milp, toy_topology

    b0 = milp.N_STRUCT_BUILDS
    assert b0 == REGISTRY.counter("planner.struct_builds").value
    top = toy_topology(n=4, seed=11)
    Planner(top, max_relays=2).plan(PlanSpec(
        objective="cost_min", src="toy:r0", dst="toy:r1",
        tput_goal_gbps=1.0, volume_gb=0.01,
    ))
    built = milp.N_STRUCT_BUILDS - b0
    assert built >= 1  # fresh topology: at least one structure build
    assert milp.N_STRUCT_BUILDS == (
        REGISTRY.counter("planner.struct_builds").value
    )


# ----------------------------------------------------------------- tracer


def test_ring_buffer_bounds_memory_keeping_newest():
    tr = enable(capacity=8)
    for i in range(20):
        tr.instant("tick", float(i))
    assert len(tr) == 8
    names_ts = [e[2] for e in tr.events()]
    assert names_ts == [float(i) for i in range(12, 20)]  # oldest dropped
    tr.clear()
    assert len(tr) == 0


def test_disabled_tracer_is_a_noop():
    disable()
    tr = get_tracer()
    assert tr.enabled is False
    tr.instant("x", 0.0)
    tr.span("y", 0.0, 1.0)
    tr.sample("z", 0.0, 3)
    assert len(tr) == 0 and tr.events() == []


def test_enable_installs_and_disable_restores():
    tr = enable(capacity=4)
    assert get_tracer() is tr and tr.enabled
    disable()
    assert get_tracer().enabled is False


def test_region_records_a_span_only_while_a_tracer_records():
    with region("planner.x", track="planner", n=1) as args:
        args["m"] = 2
    tr = enable()
    with region("planner.x", track="planner", n=1) as args:
        args["m"] = 2  # a value known only at the end
    (ev,) = tr.events()
    assert ev[:2] == ("X", "planner.x") and ev[4] == "planner"
    assert ev[5] == {"n": 1, "m": 2}
    assert 0.0 <= ev[2] and 0.0 <= ev[3]
    disable()
    with region("planner.x", track="planner"):
        pass
    assert len(tr) == 1


def test_region_records_its_span_when_the_block_raises():
    tr = enable()
    with pytest.raises(ValueError):
        with region("sim.build", track="sim-host"):
            raise ValueError("boom")
    assert [e[1] for e in tr.events()] == ["sim.build"]


def test_planner_spans_keep_their_names_and_args():
    from repro.core import Planner, PlanSpec, toy_topology

    top = toy_topology(n=6, seed=4)
    planner = Planner(top)
    src, dst = top.regions[0].key, top.regions[1].key
    tr = enable()
    planner.plan(PlanSpec(objective="max_throughput", src=src, dst=dst))
    planner.plan_cohort([PlanSpec(objective="cost_min", src=src, dst=dst,
                                  tput_goal_gbps=0.5, volume_gb=1.0)])
    spans = {e[1]: e for e in tr.events() if e[4] == "planner"}
    assert set(spans["planner.plan"][5]) == {
        "objective", "src", "dst", "struct_builds"}
    assert spans["planner.plan_cohort"][5] == {
        "n_specs": 1, "n_batched_routes": 1}
    assert {f"bnb.stage{k}" for k in range(5)} <= set(spans)


# ----------------------------------------------------------------- export


def test_chrome_trace_shape_and_canonical_json():
    events = [
        ("X", "work", 0.0015, 0.0000004, "planner", {"n": 2}),
        ("i", "mark", 0.002, 0.0, "sim", None),
        ("C", "queue", 0.003, 0.0, "sim", {"value": 5}),
    ]
    doc = to_chrome_trace(events)
    assert doc["displayTimeUnit"] == "ms"
    meta, meta2, span, mark, ctr = doc["traceEvents"]
    assert meta["ph"] == "M" and meta["args"] == {"name": "planner"}
    assert meta2["ph"] == "M" and meta2["args"] == {"name": "sim"}
    assert span == {
        "name": "work", "ph": "X", "ts": 1500, "pid": 1, "tid": 1,
        "dur": 1, "args": {"n": 2},  # sub-µs spans still render (dur >= 1)
    }
    assert mark["tid"] == 2 and "args" not in mark  # second track -> tid 2
    assert ctr["args"] == {"value": 5}
    payload = trace_json(events)
    assert payload == json.dumps(
        doc, sort_keys=True, separators=(",", ":")
    )
    assert json.loads(payload) == doc


def test_text_timeline_renders_and_limits():
    events = [
        ("i", "a", 0.001, 0.0, "sim", None),
        ("X", "b", 0.002, 0.004, "sim", {"job": 1}),
    ]
    text = text_timeline(events)
    lines = text.splitlines()
    assert len(lines) == 2
    assert "[sim] a" in lines[0]
    assert "b +4.000ms job=1" in lines[1]
    assert text_timeline(events, limit=1).splitlines() == [lines[1]]


# ----------------------------------------------------- determinism pins


def test_flowsim_and_reference_emit_identical_traces():
    fast = trace_chaos_scenario(seed=5, volume_gb=0.5, horizon_s=8.0)
    ref = trace_chaos_scenario(
        seed=5, volume_gb=0.5, horizon_s=8.0, reference=True
    )
    assert len(fast) > 10
    assert {e[4] for e in fast} == {"sim"}  # sim-time events only
    assert trace_json(fast) == trace_json(ref)


def test_same_seed_same_process_is_deterministic():
    a = trace_chaos_scenario(seed=2, volume_gb=0.5, horizon_s=8.0)
    b = trace_chaos_scenario(seed=2, volume_gb=0.5, horizon_s=8.0)
    assert trace_json(a) == trace_json(b)
    c = trace_chaos_scenario(seed=3, volume_gb=0.5, horizon_s=8.0)
    assert trace_json(a) != trace_json(c)  # the seed actually matters


def test_cli_export_is_byte_identical_across_processes(tmp_path):
    env = dict(os.environ)
    env["PYTHONPATH"] = str(SRC_DIR)
    outs = []
    for run in ("a", "b"):
        out = tmp_path / f"trace-{run}.json"
        res = subprocess.run(
            [
                sys.executable, "-m", "repro.obs", "--seed", "9",
                "--volume-gb", "0.5", "--horizon-s", "8",
                "--out", str(out),
            ],
            env=env, capture_output=True, text=True, timeout=300,
        )
        assert res.returncode == 0, res.stderr
        outs.append(out.read_bytes())
    assert outs[0] == outs[1]
    doc = json.loads(outs[0])  # and it is valid Chrome-trace JSON
    assert doc["traceEvents"][0]["ph"] == "M"
    assert any(e["ph"] == "i" for e in doc["traceEvents"])
