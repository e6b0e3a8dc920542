"""Byte and operation counts against hand-worked shapes, and the peaks."""

import pytest

from lib import counts, peaks


def test_topk_counts_by_hand():
    # 64 queries x 1,000 items x 64 dims, 10 kept
    c = counts.topk_counts(64, 1000, 64, 10)
    assert c["flops"] == 2 * 64 * 1000 * 64 == 8_192_000
    assert c["bytes"] == 4 * (1000 * 64 + 64 * 64 + 64 * 10 * 2) == 277_504


def test_topk_is_memory_bound_at_the_cells_size():
    c = counts.topk_counts(128, 15_200_000, 64, 10)
    least, bound = peaks.roofline_seconds(c["flops"], c["bytes"],
                                          "TPU v5 lite")
    assert bound == "memory"
    assert least == pytest.approx(c["bytes"] / 819e9)
    assert 0.0047 < least < 0.0048      # 3.89 GB over 819 GB/s


def test_als_iteration_counts_by_hand():
    # 1,000 ratings, 10 users, 5 items, rank 4, 2 CG iterations
    c = counts.als_iteration_counts(1000, 10, 5, 4, 2)
    per_half_flops = 1000 * (2 * 16 + 2 * 4)        # gramian + rhs
    assert c["flops"] == 2 * per_half_flops + 15 * 2 * 2 * 16 == 80_960
    per_half_bytes = 1000 * (4 * 4 + 8)             # factor row + id, value
    assert c["bytes"] == 2 * per_half_bytes + 15 * 4 * 4 == 48_240


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v9")
    assert peaks.roofline_seconds(197e12, 1.0, "TPU v5 lite") == (
        pytest.approx(1.0), "compute")
