"""Quantile bin mapper — the "reference dataset" concept on TPU.

The reference computes LightGBM bin boundaries on the driver from a row sample and
broadcasts a serialized reference dataset to all workers (LightGBMBase.scala:509-550,
dataset/ReferenceDatasetUtils.scala, dataset/SampledData.scala). Here the bin
boundaries are computed host-side with numpy from a sample (exact same role), and
binning itself (:func:`apply_bins`) is one jitted XLA program, so the (N, F) →
(N, F) uint8/uint16 quantized matrix is produced TPU-resident, with nothing
else of that size in the device's memory on the way. A value's bin is found by
counting the feature's boundaries below it, one dense compare-and-add pass
over the boundary axis; only a mapper with more than
``COMPARE_MAX_BOUNDARIES`` boundaries a feature is searched instead
(``jnp.searchsorted``, a gather per round and value). Both give the same
integers.

Bin semantics (matching LightGBM's BinMapper):
  * boundaries[f] is a sorted vector of bin upper bounds (length <= max_bin - 1);
    bin(x) = first i with x <= boundaries[f][i] = #{i : boundaries[f][i] < x};
    x beyond all bounds → last real-value bin.
  * Features containing NaN get a DEDICATED missing bin at index
    ``num_bins[f] - 1`` (missing_type=NaN); the split finder then learns the
    missing direction per split (``default_left``), matching LightGBM's
    BinMapper + Tree::default_left semantics (SURVEY §7 hard-part 1).
  * categorical features use the category's integer value as its bin, capped by
    max_bin; rare categories overflow into bin 0; NaN categories → bin 0.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np


class BinMapper(NamedTuple):
    """Per-feature binning metadata. ``boundaries`` is padded to a rectangle
    (num_features, max_bin-1) with +inf so it ships to device as one array."""

    boundaries: np.ndarray      # (F, max_bin-1) float32, +inf padded
    num_bins: np.ndarray        # (F,) int32 — actual bin count per feature
    is_categorical: np.ndarray  # (F,) bool
    max_bin: int
    has_nan: np.ndarray = None  # (F,) bool — feature has a dedicated NaN bin
    cat_counts: np.ndarray = None  # (F,) int32 — DISTINCT categories observed
                                   # (sparse id encodings differ from num_bins)

    @property
    def num_features(self) -> int:
        return self.boundaries.shape[0]

    @property
    def total_bins(self) -> int:
        return self.max_bin

    @property
    def nan_mask(self) -> np.ndarray:
        if self.has_nan is None:
            return np.zeros(self.num_features, bool)
        return self.has_nan

    @property
    def nan_bins(self) -> np.ndarray:
        """(F,) int32: the NaN bin index per feature (num_bins-1 when the
        feature has missing values, else an out-of-range sentinel so equality
        against it never fires)."""
        nb = np.asarray(self.num_bins, np.int32) - 1
        return np.where(self.nan_mask, nb, np.int32(0x7FFF))


def cat_presence_bitmap(col: np.ndarray, cap: int) -> np.ndarray:
    """(cap,) bool: which identity bins a categorical column occupies.
    Values clip into [0, cap-1] exactly as identity binning does, so the
    popcount equals the number of distinct OBSERVED bins — the quantity the
    maxCatToOnehot one-vs-rest decision needs (LightGBM decides from
    full-data bin counts). O(n) bincount, no sort."""
    v = col[~np.isnan(col)]
    if not v.size:
        return np.zeros(cap, bool)
    iv = np.clip(v.astype(np.int64), 0, cap - 1)
    return np.bincount(iv, minlength=cap).astype(bool)


def compute_bin_mapper(
    X: np.ndarray,
    max_bin: int = 255,
    sample_count: int = 200_000,
    categorical_features: Optional[Sequence[int]] = None,
    seed: int = 0,
    has_nan: Optional[np.ndarray] = None,
    min_data_in_bin: int = 3,
    max_bin_by_feature: Optional[Sequence[int]] = None,
    cat_presence: Optional[np.ndarray] = None,
) -> BinMapper:
    """Driver-side boundary computation from a sample (the analog of
    LightGBMBase.getSampledRows + LGBM_DatasetCreateFromSampledColumn;
    binSampleCount param default 200000 — params/LightGBMParams.scala).

    ``has_nan`` overrides per-feature missing-ness when the caller has
    computed it on MORE data than ``X`` (e.g. the sparse path samples rows for
    boundaries but elects NaN bins from the full matrix). ``cat_presence``
    ((F, max_bin) bool) similarly overrides categorical bin occupancy when the
    caller saw more data than ``X`` — the sparse and multi-process paths pass
    full-data bitmaps so the maxCatToOnehot decision never depends on the
    sampling seed."""
    X = np.asarray(X, dtype=np.float32)
    n, f = X.shape
    cat = np.zeros(f, dtype=bool)
    if categorical_features:
        cat[list(categorical_features)] = True
    # missing-ness decided on the FULL matrix (binning must route every NaN)
    if has_nan is None:
        has_nan = np.isnan(X).any(axis=0) & ~cat
    else:
        has_nan = np.asarray(has_nan, bool) & ~cat

    X_full = X
    if n > sample_count:
        rng = np.random.default_rng(seed)
        X = X[rng.choice(n, size=sample_count, replace=False)]

    bounds = np.full((f, max_bin - 1), np.inf, dtype=np.float32)
    nbins = np.zeros(f, dtype=np.int32)
    cat_counts = np.zeros(f, dtype=np.int32)
    caps = np.full(f, max_bin, np.int64)
    if max_bin_by_feature is not None:
        mb = np.asarray(max_bin_by_feature, np.int64)
        caps[: len(mb)] = np.clip(mb[:f], 2, max_bin)
    for j in range(f):
        if cat[j]:
            # categories are small non-negative ints; identity binning capped
            # at max_bin. Bin occupancy comes from the FULL column (O(n)
            # bincount — no sort, no sampled-col copy): cat_counts drives the
            # maxCatToOnehot one-vs-rest decision, which LightGBM makes from
            # full-data bin counts — a subsample would flip split modes
            # nondeterministically with bin_sample_count for rare categories.
            # Callers whose X is itself a sample (sparse / multi-process
            # paths) pass the full-data bitmap via ``cat_presence``.
            pres = (np.asarray(cat_presence[j], bool)
                    if cat_presence is not None
                    else cat_presence_bitmap(X_full[:, j], max_bin))
            nz = np.flatnonzero(pres)
            hi = int(nz[-1]) if nz.size else 0
            nbins[j] = min(hi + 1, int(caps[j]) - 1) + 1  # +1 overflow bin
            cat_counts[j] = int(pres.sum())
            continue
        col = X[:, j]
        col = col[~np.isnan(col)]
        # features with NaN reserve one bin; real values get one fewer
        real_cap = int(caps[j]) - 1 if has_nan[j] else int(caps[j])
        uniq = np.unique(col)
        if uniq.size <= 1:
            nbins[j] = 2 + int(has_nan[j])
            continue
        if uniq.size <= real_cap - 1:
            # few distinct values: boundary at midpoints → exact value bins
            b = (uniq[:-1] + uniq[1:]) * 0.5
        else:
            qs = np.linspace(0.0, 1.0, real_cap)[1:-1]
            b = np.unique(np.quantile(col, qs).astype(np.float32))
        if min_data_in_bin > 1 and b.size:
            # merge bins whose SAMPLE occupancy is below min_data_in_bin
            # (LightGBM minDataPerBin): drop a boundary when the bin it
            # closes is under-filled
            # right-closed counting (x <= boundary belongs to the LEFT bin),
            # matching apply_bins: a value's bin is the number of boundaries
            # strictly below it, which is searchsorted(side='left')
            counts = np.bincount(np.searchsorted(b, col, side="left"),
                                 minlength=b.size + 1)
            keep = []
            acc = 0
            for bi in range(b.size):
                acc += counts[bi]
                if acc >= min_data_in_bin:
                    keep.append(bi)
                    acc = 0
            # the trailing (overflow) bin may be under-filled: merge backward
            if keep and counts[b.size] + acc < min_data_in_bin:
                keep.pop()
            b = b[keep]
        bounds[j, : b.size] = b
        # bins: b.size+1 real-value bins (+1 overflow shares the last), plus a
        # dedicated NaN bin when the feature has missing values
        nbins[j] = b.size + 2 + int(has_nan[j])
    return BinMapper(boundaries=bounds, num_bins=nbins, is_categorical=cat,
                     max_bin=max_bin, has_nan=has_nan, cat_counts=cat_counts)


class StreamingQuantileSketch:
    """One-pass bin-boundary builder for out-of-core ingest (gbdt/stream.py):
    feed row chunks through :meth:`update` / :meth:`update_csr` in any number
    of passes-of-one, then :meth:`finalize` into a :class:`BinMapper`.

    Two regimes, switched automatically:

    * **Exact-parity fallback** — while the stream holds at most
      ``sample_count`` rows, every row is buffered and ``finalize()`` runs
      :func:`compute_bin_mapper` over the full buffered matrix: boundaries
      are BIT-IDENTICAL to the resident path's (same rows, same algorithm),
      so fits-in-memory data streams with zero model drift.
    * **Reservoir sketch** — past ``sample_count`` rows the buffer becomes a
      seeded uniform row reservoir (Vitter's algorithm R, vectorized per
      chunk). For a reservoir of m rows, every empirical quantile of the
      sample is within eps = sqrt(ln(2/delta) / (2m)) of the stream's true
      quantile with probability 1-delta (DKW inequality) — at the default
      m=200k, eps ≈ 0.6% rank error at delta=1e-3, far inside one bin of a
      255-bin ladder. This mirrors LightGBM's own boundary-from-sample
      design (binSampleCount), just fed streamwise.

    Missing-ness and categorical bin occupancy are tracked EXACTLY over the
    FULL stream (an O(F) bitmap OR per chunk) and passed to
    :func:`compute_bin_mapper` as overrides, so NaN-bin election and the
    maxCatToOnehot one-vs-rest decision never depend on which rows the
    reservoir kept — the same contract the sparse and multi-process paths
    already hold."""

    def __init__(self, num_features: int, max_bin: int = 255,
                 sample_count: int = 200_000,
                 categorical_features: Optional[Sequence[int]] = None,
                 seed: int = 0, min_data_in_bin: int = 3,
                 max_bin_by_feature: Optional[Sequence[int]] = None):
        self.num_features = int(num_features)
        self.max_bin = int(max_bin)
        self.sample_count = int(sample_count)
        self.categorical_features = (list(categorical_features)
                                     if categorical_features else [])
        self.seed = int(seed)
        self.min_data_in_bin = int(min_data_in_bin)
        self.max_bin_by_feature = max_bin_by_feature
        self.rows_seen = 0
        self._buf = np.empty((min(self.sample_count, 4096), num_features),
                             np.float32)
        self._filled = 0
        self._overflowed = False
        self._rng = np.random.default_rng(self.seed)
        self._has_nan = np.zeros(num_features, bool)
        self._cat_pres = (np.zeros((num_features, self.max_bin), bool)
                          if self.categorical_features else None)

    def _reserve(self, extra: int) -> None:
        need = min(self._filled + extra, self.sample_count)
        if need > self._buf.shape[0]:
            cap = self._buf.shape[0]
            while cap < need:
                cap *= 2
            cap = min(cap, self.sample_count)
            self._buf = np.concatenate(
                [self._buf, np.empty((cap - self._buf.shape[0],
                                      self.num_features), np.float32)])

    def update(self, X: np.ndarray) -> "StreamingQuantileSketch":
        X = np.atleast_2d(np.asarray(X, np.float32))
        if X.shape[1] != self.num_features:
            raise ValueError(f"chunk has {X.shape[1]} features, sketch was "
                             f"built for {self.num_features}")
        c = X.shape[0]
        if c == 0:
            return self
        # exact full-stream stats (independent of the sampling regime)
        self._has_nan |= np.isnan(X).any(axis=0)
        if self._cat_pres is not None:
            for j in self.categorical_features:
                self._cat_pres[j] |= cat_presence_bitmap(X[:, j], self.max_bin)
        t0 = self.rows_seen
        self.rows_seen += c
        take_direct = min(c, self.sample_count - self._filled)
        if take_direct > 0:
            self._reserve(take_direct)
            self._buf[self._filled:self._filled + take_direct] = \
                X[:take_direct]
            self._filled += take_direct
        if take_direct < c:
            # reservoir regime (algorithm R, vectorized): row at global
            # index t replaces a uniform slot with probability m/(t+1)
            self._overflowed = True
            m = self.sample_count
            rest = X[take_direct:]
            t = t0 + take_direct + np.arange(rest.shape[0], dtype=np.int64)
            slot = (self._rng.random(rest.shape[0]) * (t + 1)).astype(
                np.int64)
            hit = np.flatnonzero(slot < m)
            # sequential assignment keeps algorithm-R semantics when two
            # chunk rows draw the same slot (the later row must win)
            for i in hit:
                self._buf[slot[i]] = rest[i]
        return self

    def update_csr(self, data, rows, cols, n_rows: int
                   ) -> "StreamingQuantileSketch":
        """Sparse chunk intake: densify host-side (implicit zeros ARE zeros,
        matching the CSR binning semantics of :class:`CsrBinner`) and feed
        the dense chunk through :meth:`update`. Chunk-sized, not
        dataset-sized — the whole point of the streamed sparse path."""
        X = np.zeros((int(n_rows), self.num_features), np.float32)
        X[np.asarray(rows, np.int64), np.asarray(cols, np.int64)] = \
            np.asarray(data, np.float32)
        return self.update(X)

    @property
    def exact(self) -> bool:
        """True while finalize() is bit-identical to the resident
        compute_bin_mapper over the full stream."""
        return not self._overflowed

    def finalize(self) -> BinMapper:
        if self.rows_seen == 0:
            raise ValueError("finalize() on an empty sketch: no rows seen")
        sample = self._buf[:self._filled]
        return compute_bin_mapper(
            sample, self.max_bin,
            # the buffer IS the sample — never re-subsample it
            sample_count=max(self._filled, 1),
            categorical_features=self.categorical_features or None,
            seed=self.seed, has_nan=self._has_nan,
            min_data_in_bin=self.min_data_in_bin,
            max_bin_by_feature=self.max_bin_by_feature,
            cat_presence=self._cat_pres)


# Where apply_bins stops counting and starts searching. By operations a
# count is one dense compare-and-add per boundary and value, the search
# ceil(log2(boundaries + 1)) dependent gathers per value. On the chip a gather
# round costs what some ten thousand compare-and-adds do (PERF.md section 6,
# PR 26: 3.5 M x 28 values counted in 84 ms at 254 boundaries and 489 ms at
# 4,095, searched in 8.5 s and 12.7 s), so there the count wins at any width.
# It is the backends with cheap gathers that set the threshold: on the CPU
# the count costs 3 times the search at 254 boundaries and 17 times at 1,023.
# 512 keeps every uint8 mapper, the default max_bin of 255 among them, and
# the first uint16 ones on the dense path, and leaves the rare wide mapper,
# whose count grows with its width, to the search.
COMPARE_MAX_BOUNDARIES = 512


def bins_by_compare(mapper: BinMapper) -> bool:
    """Whether :func:`apply_bins` counts boundaries (True) or searches them
    (False) for this mapper: decided by its boundary count alone."""
    return mapper.boundaries.shape[1] <= COMPARE_MAX_BOUNDARIES


@partial(jax.jit, static_argnames=("has_categorical", "out_dtype"))
def _apply_bins(X, boundaries, real_limit, nanbin, nan_mask, is_categorical,
                cat_cap, has_categorical=False, out_dtype=jnp.uint8):
    """The whole of :func:`apply_bins` as one program. Everything a mapper
    holds is an argument, so a new mapper of the same shape compiles nothing."""
    nb = boundaries.shape[1]
    if nb <= COMPARE_MAX_BOUNDARIES:
        # bin(x) = #{k : boundaries[f, k] < x}: searchsorted(side="left") for
        # every x but NaN, which counts 0 here. The boundary axis is the
        # reduction's major axis, so the (nb, N, F) comparison lives in
        # registers only; summed in the output's own width, which holds nb,
        # the count needs no wider (N, F) array either
        count = jnp.sum(boundaries.T[:, None, :] < X[None, :, :], axis=0,
                        dtype=out_dtype)
    else:
        count = jax.vmap(partial(jnp.searchsorted, side="left"),
                         in_axes=(0, 1), out_axes=1)(boundaries, X)
    isnan = jnp.isnan(X)
    # NaN sorts after every boundary, as the search has it
    binned = jnp.where(isnan, nb, count.astype(jnp.int32))
    # clamp real values into the feature's real-value bin range
    binned = jnp.minimum(binned, real_limit[None, :])
    # NaN -> dedicated NaN bin (num_bins-1) for has_nan features
    binned = jnp.where(isnan & nan_mask[None, :], nanbin[None, :], binned)
    if has_categorical:
        ident = jnp.clip(jnp.where(isnan, 0.0, X), 0, cat_cap).astype(jnp.int32)
        ident = jnp.minimum(ident, nanbin[None, :])
        binned = jnp.where(is_categorical[None, :], ident, binned)
    return binned.astype(out_dtype)


def apply_bins(mapper: BinMapper, X) -> jnp.ndarray:
    """(N, F) raw floats → (N, F) bin ids. Non-NaN overflow clamps into the
    last REAL-value bin; NaN goes to the feature's dedicated NaN bin when it
    has one (else the last bin, the legacy always-right behavior)."""
    nan_mask = mapper.nan_mask
    nanbin = np.asarray(mapper.num_bins, np.int32) - 1
    return _apply_bins(
        jnp.asarray(X, jnp.float32), mapper.boundaries,
        nanbin - nan_mask.astype(np.int32), nanbin, nan_mask,
        mapper.is_categorical, np.float32(mapper.max_bin - 1),
        has_categorical=bool(mapper.is_categorical.any()),
        out_dtype=jnp.uint8 if mapper.max_bin <= 256 else jnp.uint16)


@partial(jax.jit, static_argnames=("n_rows", "out_dtype"))
def _bin_csr_entries(data, rows, cols, zero_bins, boundaries, real_limit,
                     nan_mask, nan_bin, is_cat, max_bin, n_rows,
                     out_dtype=jnp.uint8):
    """Device-side CSR chunk binning: O(F) broadcast of each feature's
    zero-bin + O(nnz) per-entry searchsorted and scatter — implicit zeros
    never materialize (the dense detour binned rows x F values regardless of
    density). Semantics identical to :func:`apply_bins` per entry."""
    f = boundaries.shape[0]
    # per-entry numeric bin against the entry's feature boundaries
    b = jax.vmap(lambda v, c: jnp.searchsorted(boundaries[c], v,
                                               side="left"))(data, cols)
    b = jnp.minimum(b.astype(jnp.int32), real_limit[cols])
    isnan = jnp.isnan(data)
    b = jnp.where(isnan & nan_mask[cols], nan_bin[cols], b)
    # categorical identity binning (clip into [0, num_bins-1])
    cat_limit = real_limit + nan_mask.astype(jnp.int32)  # = num_bins - 1
    identb = jnp.minimum(
        jnp.clip(jnp.nan_to_num(data, nan=0.0), 0,
                 max_bin - 1).astype(jnp.int32), cat_limit[cols])
    b = jnp.where(is_cat[cols], identb, b)
    out = jnp.broadcast_to(zero_bins[None, :].astype(out_dtype), (n_rows, f))
    return out.at[rows, cols].set(b.astype(out_dtype))


class CsrBinner:
    """Device-side CSR chunk binning with the mapper state shipped ONCE:
    boundaries / limits / masks / the zero-bin row are chunk-invariant, and
    an 11M-row ingest makes hundreds of chunk calls — re-uploading them per
    chunk would spend the transfer budget the sparse path exists to save.
    nnz pads to power-of-2 buckets (pad rows point out of bounds → dropped
    by the scatter) so varying chunk occupancy reuses a handful of compiled
    programs instead of one per nnz."""

    def __init__(self, mapper: BinMapper):
        self.max_bin = mapper.max_bin
        self.dtype = jnp.uint8 if mapper.max_bin <= 256 else jnp.uint16
        self.zero = apply_bins(mapper, np.zeros((1, mapper.num_features),
                                                np.float32))[0]
        self.boundaries = jnp.asarray(mapper.boundaries)
        self.real_limit = jnp.asarray(
            mapper.num_bins - 1 - mapper.nan_mask.astype(np.int32), jnp.int32)
        self.nan_mask = jnp.asarray(mapper.nan_mask)
        self.nan_bin = jnp.asarray(np.asarray(mapper.num_bins, np.int32) - 1)
        self.is_cat = jnp.asarray(mapper.is_categorical)

    def __call__(self, data, rows, cols, n_rows) -> jnp.ndarray:
        nnz = len(data)
        cap = max(1024, 1 << max(nnz - 1, 1).bit_length())
        pad = cap - nnz
        data = np.pad(np.asarray(data, np.float32), (0, pad))
        rows = np.pad(np.asarray(rows, np.int32), (0, pad),
                      constant_values=n_rows)   # OOB scatter index: no-op
        cols = np.pad(np.asarray(cols, np.int32), (0, pad))
        return _bin_csr_entries(
            jnp.asarray(data), jnp.asarray(rows), jnp.asarray(cols),
            self.zero, self.boundaries, self.real_limit, self.nan_mask,
            self.nan_bin, self.is_cat, self.max_bin, n_rows,
            out_dtype=self.dtype)


def bin_csr_chunk(mapper: BinMapper, data, rows, cols, n_rows) -> jnp.ndarray:
    """One-shot convenience wrapper; loops should hold a :class:`CsrBinner`."""
    return CsrBinner(mapper)(data, rows, cols, n_rows)


def bin_threshold_to_value(mapper: BinMapper, feature: int, bin_id: int) -> float:
    """Real-valued split threshold for a numeric split at ``bin_id`` (the stored
    LightGBM model threshold, i.e. the bin's upper boundary). A threshold at or
    beyond the last real-value bin means "every non-missing value goes left"
    (only reachable for features with a NaN bin, where the right child holds
    the missing rows). Serialized as a large FINITE double (1e308) so model
    strings stay parseable everywhere (LightGBM also emits finite doubles
    for top-bin thresholds) while x <= threshold holds for every real x."""
    b = mapper.boundaries[feature]
    if bin_id < len(b) and np.isfinite(b[bin_id]):
        return float(b[bin_id])
    return 1e308
