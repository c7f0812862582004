"""The rate solve's essential work on hand-worked sizes."""

import pytest

from roofline import least_time, waterfill_cost

V5E = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}


def test_cost_of_one_solve():
    # 512 lanes x 13 operations; 4 bytes x (6 x 512 + 2 x 16 + 1)
    assert waterfill_cost(512, 16, 1) == (6656, 12420)
    assert waterfill_cost(3056, 22, 9) == (39728, 73556)


def test_least_time_is_memory_bound_on_v5e():
    t, bound = least_time(512, 16, 1, V5E)
    assert bound == "memory"
    assert t == pytest.approx(12420 / 819e9, rel=1e-12)


def test_counts_ignore_padding():
    # the same problem padded to whole tiles costs the same
    assert waterfill_cost(300, 5, 3) != waterfill_cost(512, 5, 3)
    assert waterfill_cost(300, 5, 3)[1] == 4 * (1800 + 13)
