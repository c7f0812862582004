"""The reader of the relay cascade's pass counter, on hand-made counter
deltas; it finds nothing where the cascade did not run or the program
does not count its passes."""

import pytest

import run


@pytest.mark.parametrize("counters,want", [
    ({"sim.cascade_seq_passes": 7.0, "sim.cascade_seq_iters": 3.0,
      "sim.loop_iters": 900.0}, 7 / 3),
    # no iteration ran the cascade: the snapshot leaves both out
    ({"sim.loop_iters": 900.0}, None),
    # a program that counts iterations but not passes
    ({"sim.cascade_seq_iters": 3.0, "sim.loop_iters": 900.0}, None),
])
def test_cascade_passes_reader(counters, want):
    read = run.load_reader("sim.cascade_passes_per_iter")
    got = read({"trace": None, "work": {}, "counters": counters})
    assert got == (pytest.approx(want) if want is not None else None)
