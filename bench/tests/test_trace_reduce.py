"""The trace reduction: busy union, per-program sums and gap labels, on
hand-made intervals and on a small trace recorded on one TPU v5e."""

from pathlib import Path

import pytest

import trace_reduce as tr

RECORDED = Path(__file__).parent / "data" / "small.xplane.pb"


def test_union_merges_overlaps_and_touching_spans():
    assert tr.union([(3, 4), (0, 1), (0.5, 2), (2, 2.5)]) == [[0, 2.5],
                                                               [3, 4]]
    assert tr.covered([[0, 2.5], [3, 4]], 1, 3.5) == pytest.approx(2.0)


def test_op_name_is_the_instruction_name():
    assert tr.op_name("%fusion.3 = f32[8]{0} fusion(%p0)") == "fusion.3"
    assert tr.op_name("waterfill_8x.1") == "waterfill_8x.1"


def _made():
    return tr.Reduction(
        window=(0.0, 10.0),
        busy=[[[1.0, 2.0], [4.0, 5.0], [9.0, 9.5]]],
        programs={"jit_a": [2, 1.5], "jit__segment": [3, 0.9]},
        ops={"waterfill_8x.1": [5, 0.4], "fusion.2": [1, 0.1]},
        annotations=[("bench.window", 0.0, 10.0),
                     ("bench.request", 0.5, 5.5),
                     ("bench.request", 6.0, 9.8)],
        n_devices=1)


def test_busy_and_sums():
    r = _made()
    assert r.busy_s == pytest.approx(2.5)
    assert r.busy_in(1.5, 4.5) == pytest.approx(1.0)
    assert r.program("_segment") == [3, 0.9]
    assert r.op("waterfill") == [5, 0.4]
    assert r.top_ops(1) == [["waterfill_8x.1", 0.4]]


def test_gaps_are_labelled_by_the_innermost_annotation():
    gaps = _made().gaps()
    assert [round(s, 6) for _, s in gaps] == [1.0, 2.0, 4.0, 0.5]
    assert [label for label, _ in gaps] == [
        "bench.request", "bench.request", "bench.request", "bench.request"]
    assert _made().top_gaps(1) == [["bench.request", 4.0]]


def test_dropped_spans_leave_the_window_and_make_no_gap():
    r = _made()
    r.dropped = [[5.0, 9.0]]
    assert r.window_s == pytest.approx(6.0)
    assert [round(s, 6) for _, s in r.gaps()] == [1.0, 2.0, 0.5]


def test_recorded_trace():
    # recorded by three 512x512 matmuls in one bench.request, a 50 ms
    # sleep in bench.idle, then two adds in a second bench.request
    r = tr.reduce_file(str(RECORDED), 1)
    assert r.n_devices == 1
    assert 0.05 < r.window_s < 1.0
    assert 0 < r.busy_s < r.window_s - 0.05
    assert r.program("mm")[0] == 3
    assert r.program("add")[0] == 2
    n, s = r.program("mm")
    assert 0 < s < 0.01
    label, longest = r.top_gaps(1)[0]
    assert label == "bench.idle" and longest >= 0.05
