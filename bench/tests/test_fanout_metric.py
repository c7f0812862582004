"""The reader of the jax event loop's fan-out counter, on hand-made
counter deltas; it finds nothing in a program that does not count its
enqueues, or where no loop iteration ran."""

import pytest

import run


@pytest.mark.parametrize("counters,want", [
    ({"sim.fanout_enqueues": 11746.0, "sim.loop_iters": 13771.0},
     11746 / 13771),
    # a program without the counter (the snapshot also leaves out a
    # counter that stayed at zero)
    ({"sim.loop_iters": 13771.0}, None),
    ({"sim.fanout_enqueues": 3.0}, None),
])
def test_fanout_reader(counters, want):
    read = run.load_reader("sim.fanout_per_event")
    got = read({"trace": None, "work": {}, "counters": counters})
    assert got == (pytest.approx(want) if want is not None else None)
