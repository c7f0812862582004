"""The readers of the program's own counters, on hand-made counter deltas;
each finds nothing in a program without the counters."""

import pytest

import run


@pytest.mark.parametrize("name,counters,want", [
    ("sim.syncs_per_segment",
     {"sim.host_syncs": 12.0, "sim.segments": 3.0}, 4.0),
    ("sim.cascade_seq_share",
     {"sim.cascade_seq_iters": 5.0, "sim.loop_iters": 50.0}, 0.1),
    # the snapshot leaves out a counter that stayed at zero
    ("sim.cascade_seq_share", {"sim.loop_iters": 50.0}, 0.0),
    ("ipm.batch_fill",
     {"ipm.batch_rows_real": 41.0, "ipm.batch_rows": 128.0}, 41 / 128),
    ("ipm.iters_per_call",
     {"ipm.loop_trips": 90.0, "ipm.device_calls": 2.0}, 45.0),
    ("ipm.useful_iter_share",
     {"ipm.sample_iters": 1200.0, "ipm.row_trips": 128.0 * 45}, 1200 / 5760),
])
def test_counter_readers(name, counters, want):
    read = run.load_reader(name)
    ctx = {"trace": None, "work": {}, "counters": counters}
    assert read(ctx) == pytest.approx(want)
    # a program without the counters: none of them in the deltas
    ctx["counters"] = {"planner.batch_lps.jax": 41.0}
    assert read(ctx) is None
