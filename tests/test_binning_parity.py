"""``ops/quantize.apply_bins`` against a numpy statement of its contract,
bit for bit: ``np.searchsorted(side="left")`` over a feature's boundaries,
the clamp into the real-value bins, the NaN rule (the dedicated NaN bin
where the feature has one, else the last real bin) and identity bins for
categorical columns. Below ``COMPARE_MAX_BOUNDARIES`` boundaries the program
counts them, above it searches; both have to say the same."""

import itertools

import numpy as np
import pytest

from synapseml_tpu.ops.quantize import (COMPARE_MAX_BOUNDARIES, BinMapper,
                                        apply_bins, bins_by_compare,
                                        compute_bin_mapper)

MAX_BINS = (16, 63, 255, 1024)          # the last one searches, in uint16
ROWS = (1, 7, 2049, 3331)               # 3331 is prime: no tile divides it
CASES = ("random", "on_boundaries", "infinities", "nan_with_nan_bin",
         "nan_without_nan_bin", "negative_zero", "constant_feature",
         "numeric_categorical_mix")


def contract(mapper: BinMapper, X: np.ndarray) -> np.ndarray:
    X = np.asarray(X, np.float32)
    last = np.asarray(mapper.num_bins, np.int64) - 1
    real_limit = last - mapper.nan_mask
    out = np.empty(X.shape, np.int64)
    for f in range(X.shape[1]):
        col = X[:, f]
        if mapper.is_categorical[f]:
            ident = np.clip(np.where(np.isnan(col), 0.0, col), 0,
                            mapper.max_bin - 1).astype(np.int64)
            out[:, f] = np.minimum(ident, last[f])
            continue
        b = np.searchsorted(mapper.boundaries[f], col, side="left")
        b = np.minimum(b, real_limit[f])
        if mapper.nan_mask[f]:
            b = np.where(np.isnan(col), last[f], b)
        out[:, f] = b
    return out.astype(np.uint8 if mapper.max_bin <= 256 else np.uint16)


def _table(case: str, max_bin: int, n: int):
    """(what the boundaries are made from, what is binned, categorical
    columns): four columns, the first of which carries the case."""
    rng = np.random.default_rng([max_bin, n, CASES.index(case)])
    fit = rng.normal(size=(5000, 4)).astype(np.float32)
    fit[:, 1] = np.round(fit[:, 1] * 3)          # few distinct values
    fit[:, 2] = rng.exponential(size=5000)
    cats = None
    if case == "nan_with_nan_bin":
        fit[::9, 0] = np.nan
    elif case == "constant_feature":
        fit[:, 0] = 2.5                          # every boundary is +inf
    elif case == "numeric_categorical_mix":
        fit[:, 0] = rng.integers(0, 300, size=5000)
        fit[:, 3] = rng.integers(0, 12, size=5000)
        cats = [0, 3]
    X = rng.normal(size=(n, 4)).astype(np.float32) * 2
    X[:, 2] = np.abs(X[:, 2])
    if case == "numeric_categorical_mix":
        # beyond max_bin, negative, fractional and missing categories
        X[:, 0] = rng.integers(-5, 400, size=n) + rng.choice([0.0, 0.5], n)
        X[:, 3] = rng.integers(0, 14, size=n)
        X[::3, 3] = np.nan
    elif case == "constant_feature":
        X[::2, 0] = 2.5
    return fit, X, cats


def _plant(case: str, mapper: BinMapper, X: np.ndarray) -> np.ndarray:
    n = X.shape[0]
    if case == "on_boundaries":
        # every boundary of every column, the +inf padding too, and the
        # float32 neighbours on both sides of each (the smallest normal
        # number beside a boundary at 0: XLA flushes subnormals to zero)
        tiny = np.finfo(np.float32).tiny
        for f in range(X.shape[1]):
            b = mapper.boundaries[f]
            below = np.where(b == 0, -tiny, np.nextafter(b, np.float32(-np.inf)))
            above = np.where(b == 0, tiny, np.nextafter(b, np.float32(np.inf)))
            X[:, f] = np.resize(np.concatenate([b, below, above]), n)
    elif case == "infinities":
        X[::2, 0], X[1::2, 0] = np.inf, -np.inf
        X[::3, 1] = np.inf
    elif case in ("nan_with_nan_bin", "nan_without_nan_bin"):
        X[::2, 0] = np.nan
        X[::5, 2] = np.nan                       # column 2 never has a NaN bin
    elif case == "negative_zero":
        X[::2, 0], X[1::2, 0] = -0.0, 0.0
        X[::3, 1] = -0.0                         # 0.0 is a value of column 1
    return X


@pytest.mark.parametrize(
    "case,max_bin,n", list(itertools.product(CASES, MAX_BINS, ROWS)),
    ids=lambda v: str(v))
def test_apply_bins_is_the_contract(case, max_bin, n):
    fit, X, cats = _table(case, max_bin, n)
    mapper = compute_bin_mapper(fit, max_bin=max_bin, min_data_in_bin=1,
                                categorical_features=cats)
    assert bins_by_compare(mapper) == (max_bin < 1024)
    assert bool(mapper.nan_mask[0]) == (case == "nan_with_nan_bin")
    if case == "constant_feature":
        assert np.isinf(mapper.boundaries[0]).all()
    X = _plant(case, mapper, X)
    got = np.asarray(apply_bins(mapper, X))
    want = contract(mapper, X)
    assert got.dtype == want.dtype and got.shape == want.shape
    np.testing.assert_array_equal(got, want)
    assert (got < np.asarray(mapper.num_bins)[None, :]).all()


@pytest.mark.parametrize("boundaries", [1, 254, COMPARE_MAX_BOUNDARIES,
                                        COMPARE_MAX_BOUNDARIES + 1, 4095])
def test_path_is_chosen_from_the_boundary_count_alone(boundaries):
    """Same rows, same real boundaries, same ``max_bin``: only the width of
    the padded boundary array differs, and with it the path. Both paths give
    the contract's bins."""
    rng = np.random.default_rng(boundaries)
    real = np.sort(rng.normal(size=(3, 1)).astype(np.float32), axis=1)
    bounds = np.full((3, boundaries), np.inf, np.float32)
    bounds[:, :1] = real
    mapper = BinMapper(boundaries=bounds, num_bins=np.full(3, 3, np.int32),
                       is_categorical=np.zeros(3, bool), max_bin=4096,
                       has_nan=np.array([False, True, False]))
    assert bins_by_compare(mapper) == (boundaries <= COMPARE_MAX_BOUNDARIES)
    X = rng.normal(size=(257, 3)).astype(np.float32)
    X[::4, 1] = np.nan
    X[::6, 2] = np.nan
    X[0] = real[:, 0]
    np.testing.assert_array_equal(np.asarray(apply_bins(mapper, X)),
                                  contract(mapper, X))


def test_a_new_mapper_compiles_nothing():
    """Everything a mapper holds is an argument of the one program."""
    from synapseml_tpu.ops.quantize import _apply_bins

    rng = np.random.default_rng(0)
    X = rng.normal(size=(64, 3)).astype(np.float32)
    apply_bins(compute_bin_mapper(X, max_bin=63), X)
    before = _apply_bins._cache_size()
    Y = rng.exponential(size=(64, 3)).astype(np.float32)
    Y[::5, 1] = np.nan
    apply_bins(compute_bin_mapper(Y, max_bin=63), Y)
    assert _apply_bins._cache_size() == before
