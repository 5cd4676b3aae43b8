"""The count functions against cases worked by hand."""

from benchmark import counts
from benchmark.peaks import PEAKS, peaks_for

import pytest


def test_hist_bytes_one_row_of_28_features():
    # 28 bin ids of one byte + one float32 gradient + one float32 hessian
    assert counts.hist_bytes(1, 28) == 36
    assert counts.hist_bytes(1000, 28) == 36000


def test_hist_additions():
    # (grad, hess, count) added once a (row, feature) pair
    assert counts.hist_additions(10, 4) == 120


def test_tree_hist_rows_three_leaves():
    # 100 rows; the root splits 30 | 70, then the 70 splits 50 | 20:
    # passes over 100 (root), 30 (smaller child), 20 (smaller child)
    assert counts.tree_hist_rows(100, [(30, 70), (50, 20)]) == 150
    assert counts.tree_least_bytes(100, [(30, 70), (50, 20)], 4) == 150 * 12


def test_tree_with_no_split_is_one_pass():
    assert counts.tree_hist_rows(64, []) == 64


def test_bytes_bind_on_the_v5e():
    p = peaks_for("TPU v5 lite")
    rows = 10_500_000
    seconds, which = counts.least_seconds(
        counts.hist_bytes(rows, 28), counts.hist_additions(rows, 28), p)
    # 378,000,000 bytes over 819e9 bytes/s
    assert which == "bytes"
    assert seconds == pytest.approx(378e6 / 819e9)
    # the additions alone would take 882e6 / 197e12 s, far less
    assert counts.hist_additions(rows, 28) / p["bf16_flops_per_s"] < seconds / 100


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        peaks_for("cpu")
    assert PEAKS["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9
    assert PEAKS["TPU v5 lite"]["bf16_flops_per_s"] == 197e12
