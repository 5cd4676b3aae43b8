"""Pre-binned training data — the LightGBM ``Dataset`` concept on TPU.

LightGBM separates dataset construction (``LGBM_DatasetCreateFromMat`` —
quantile binning, the expensive O(N·F·log B) pass) from training
(``LGBM_BoosterUpdateOneIter``); the reference builds the dataset once per
fit and benchmarks only the iteration loop (SURVEY §3.1; reference
dataset/DatasetUtils.scala + LightGBMBase.scala:509-550 do exactly this
split). ``Dataset`` is that same separation TPU-side: binning runs once on
device at construction, the quantized (N, F) uint8/uint16 matrix stays
HBM-resident, and every subsequent ``train_booster(dataset, ...)`` call
skips quantization AND the host→device transfer of the raw floats.
"""

from __future__ import annotations

from typing import Optional, Sequence

import numpy as np

from ..ops.quantize import BinMapper, apply_bins, compute_bin_mapper


def _is_sparse(X) -> bool:
    return hasattr(X, "tocsr") and hasattr(X, "nnz")


def bin_sparse(X_csr, mapper: BinMapper, max_bin: int,
               bin_sample_count: int, categorical_features, seed: int,
               chunk_rows: int = 65_536, min_data_in_bin: int = 3,
               max_bin_by_feature=None):
    """Bin a scipy CSR matrix chunk-wise (the reference's sparse dataset path
    — BulkPartitionTask CSR push + isSparse election — re-shaped for TPU:
    sparse rows stream through host densification into the device-resident
    quantized matrix, which is uint8/16 and therefore 4-32x smaller than the
    dense floats the CSR avoided). Returns (mapper, binned_device)."""
    import jax.numpy as jnp

    X_csr = X_csr.tocsr()
    n, f = X_csr.shape
    if mapper is None:
        rng = np.random.default_rng(seed)
        take = (np.sort(rng.choice(n, size=bin_sample_count, replace=False))
                if n > bin_sample_count else np.arange(n))
        sample = np.asarray(X_csr[take].todense(), np.float32)
        # NaN-bin election must see the FULL matrix (a NaN only in unsampled
        # rows still needs its dedicated bin); explicit CSR entries carry all
        # NaNs — implicit zeros are never NaN
        nan_mask = np.isnan(X_csr.data)
        has_nan = np.zeros(f, bool)
        if nan_mask.any():
            has_nan[np.unique(X_csr.indices[nan_mask])] = True
        # categorical bin occupancy likewise from the FULL matrix (explicit
        # CSC entries per column + the implicit-zero bin), so the
        # maxCatToOnehot decision can't flip with the sampling seed
        cat_presence = None
        if categorical_features:
            from ..ops.quantize import cat_presence_bitmap

            csc = X_csr.tocsc()
            cat_presence = np.zeros((f, max_bin), bool)
            for j in categorical_features:
                vals = csc.data[csc.indptr[j]: csc.indptr[j + 1]]
                cat_presence[j] = cat_presence_bitmap(vals, max_bin)
                if vals.size < n:          # at least one implicit zero
                    cat_presence[j, 0] = True
        mapper = compute_bin_mapper(sample, max_bin, bin_sample_count,
                                    categorical_features, seed,
                                    has_nan=has_nan,
                                    min_data_in_bin=min_data_in_bin,
                                    max_bin_by_feature=max_bin_by_feature,
                                    cat_presence=cat_presence)
    # Device-side sparse binning (VERDICT r2 #7): each chunk's binned matrix
    # starts as a broadcast of the per-feature zero-bin, then ONLY the nnz
    # entries' bins scatter in — O(F + nnz) work and O(nnz) host→device
    # bytes per chunk instead of the dense detour's O(rows·F), preserving
    # CSR's memory advantage through ingest. Chunk-local row ids come from
    # indptr diffs (cheap host O(nnz)).
    from ..ops.quantize import CsrBinner

    binner = CsrBinner(mapper)       # mapper state ships to device ONCE
    chunks = []
    indptr = X_csr.indptr
    for lo in range(0, n, chunk_rows):
        hi = min(lo + chunk_rows, n)
        s, e = int(indptr[lo]), int(indptr[hi])
        counts = np.diff(indptr[lo:hi + 1]).astype(np.int64)
        rows_local = np.repeat(np.arange(hi - lo, dtype=np.int32),
                               counts)
        chunks.append(binner(X_csr.data[s:e], rows_local,
                             X_csr.indices[s:e], hi - lo))
    return mapper, jnp.concatenate(chunks, axis=0)


class Dataset:
    """Bins ``X`` once (device-resident) for repeated training runs.

    Parameters mirror the binning-relevant subset of ``BoosterConfig``
    (max_bin / bin_sample_count / categorical_features / seed). ``label`` /
    ``weight`` / ``init_score`` / ``group_sizes`` ride along so a Dataset is
    a self-contained training input, as in LightGBM's Python API.
    """

    def __init__(
        self,
        X: np.ndarray,
        label: Optional[np.ndarray] = None,
        weight: Optional[np.ndarray] = None,
        init_score: Optional[np.ndarray] = None,
        group_sizes: Optional[np.ndarray] = None,
        categorical_features: Optional[Sequence[int]] = None,
        max_bin: int = 255,
        bin_sample_count: int = 200_000,
        seed: int = 0,
        mapper: Optional[BinMapper] = None,
        keep_raw: bool = True,
        min_data_in_bin: int = 3,
        max_bin_by_feature=None,
    ):
        self.min_data_in_bin = min_data_in_bin
        self.max_bin_by_feature = max_bin_by_feature
        # binning came entirely from a user mapper: the binning knobs above
        # were never used, so config mismatches against them are meaningless
        self._user_mapper = mapper is not None
        if _is_sparse(X):
            X = X.tocsr()                 # one conversion shared by all uses
            self.num_rows, self.num_features = X.shape
            if self.num_rows == 0:
                raise ValueError("Dataset requires a non-empty matrix")
            self.mapper, self.binned = bin_sparse(
                X, mapper, max_bin, bin_sample_count, categorical_features,
                seed, min_data_in_bin=min_data_in_bin,
                max_bin_by_feature=max_bin_by_feature)
            # raw sparse rows kept as-is (cheap); densified lazily by the few
            # paths that need raw floats (warm start / mesh padding)
            self._sparse = X if keep_raw else None
            self.X = None
        else:
            self._sparse = None
            X = np.asarray(X, np.float32)
            if X.ndim != 2 or X.shape[0] == 0:
                raise ValueError(
                    f"Dataset requires a non-empty 2-D matrix, got {X.shape}")
            self.num_rows, self.num_features = X.shape
            self.mapper = mapper if mapper is not None else compute_bin_mapper(
                X, max_bin, bin_sample_count, categorical_features, seed,
                min_data_in_bin=min_data_in_bin,
                max_bin_by_feature=max_bin_by_feature)
            self.binned = apply_bins(self.mapper, X)  # device (N, F) uint8/16
            # raw floats kept host-side for paths that need them (warm start /
            # mesh row padding); drop with keep_raw=False to halve host memory
            self.X = X if keep_raw else None
        self.label = None if label is None else np.asarray(label, np.float32)
        self.weight = None if weight is None else np.asarray(weight, np.float32)
        self.init_score = init_score
        self.group_sizes = group_sizes
        self.categorical_features = categorical_features

    @classmethod
    def from_batches(
        cls,
        batches,
        categorical_features: Optional[Sequence[int]] = None,
        max_bin: int = 255,
        bin_sample_count: int = 200_000,
        seed: int = 0,
        mapper: Optional[BinMapper] = None,
        min_data_in_bin: int = 3,
        max_bin_by_feature=None,
    ) -> "Dataset":
        """Bounded-memory construction from an ITERATOR of chunks — the
        streaming analog of ``Dataset(X, y)`` for data that never fits in
        memory as raw floats (the reference streams partition data into the
        native dataset the same way, LightGBMBase.scala:608-628 mapPartitions
        → chunked dataset appends).

        ``batches`` yields ``X_chunk`` or ``(X_chunk, y_chunk)`` or
        ``(X_chunk, y_chunk, w_chunk)``. Each chunk is binned to uint8 as it
        arrives and the raw floats are dropped; peak memory is
        O(bin_sample_count raw rows + total binned bytes), not O(N raw).

        When ``mapper`` is None the bin boundaries come from the FIRST
        ``bin_sample_count`` rows (a prefix sample — fine for shuffled
        streams; pass a mapper computed from a reservoir sample, as
        ``spark_adapter.dataset_from_spark`` does, when the stream is
        ordered). A NaN appearing in a feature AFTER the mapper was fixed
        without a missing bin raises loudly rather than silently clamping
        into a value bin. Ranking group sizes and init scores are not
        streamable here — build those datasets whole."""
        user_mapper = mapper is not None
        binned_parts: list = []
        y_parts: list = []
        w_parts: list = []
        raw_buf: list = []                  # raw chunks held pre-mapper only
        buffered = 0
        nan_seen = None                     # per-feature, across ALL chunks

        def _bin(Xb):
            # device-binned, pulled back to host uint8: accumulation stays
            # host-side so the device never holds parts + the final matrix
            binned_parts.append(np.asarray(apply_bins(mapper, Xb)))

        def _flush_raw():
            nonlocal buffered
            for Xb in raw_buf:
                _bin(Xb)
            raw_buf.clear()
            buffered = 0

        for batch in batches:
            if isinstance(batch, tuple):
                Xc, yc, wc = (batch + (None, None))[:3]
            else:
                Xc, yc, wc = batch, None, None
            Xc = np.asarray(Xc, np.float32)
            if Xc.ndim != 2:
                raise ValueError(f"chunk must be 2-D, got {Xc.shape}")
            chunk_nan = np.isnan(Xc).any(axis=0)
            nan_seen = (chunk_nan if nan_seen is None
                        else (nan_seen | chunk_nan))
            if yc is not None:
                y_parts.append(np.asarray(yc, np.float32))
            if wc is not None:
                w_parts.append(np.asarray(wc, np.float32))
            if mapper is None:
                raw_buf.append(Xc)
                buffered += len(Xc)
                if buffered >= bin_sample_count:
                    sample = np.concatenate(raw_buf)[:bin_sample_count]
                    mapper = compute_bin_mapper(
                        sample, max_bin, bin_sample_count,
                        categorical_features, seed,
                        min_data_in_bin=min_data_in_bin,
                        max_bin_by_feature=max_bin_by_feature)
                    _flush_raw()
            else:
                _bin(Xc)
        if mapper is None:
            if not raw_buf:
                raise ValueError("from_batches got an empty batch iterator")
            sample = np.concatenate(raw_buf)
            mapper = compute_bin_mapper(
                sample, max_bin, bin_sample_count, categorical_features,
                seed, min_data_in_bin=min_data_in_bin,
                max_bin_by_feature=max_bin_by_feature)
            _flush_raw()
        if not binned_parts:
            raise ValueError("from_batches got an empty batch iterator")
        # a NaN the mapper never allocated a missing bin for would clamp
        # into the last VALUE bin — a silently different model than
        # Dataset(X) on the same data (code-review r5). Fail loud instead.
        late_nan = nan_seen & ~mapper.nan_mask & ~mapper.is_categorical
        if late_nan.any():
            raise ValueError(
                f"features {np.flatnonzero(late_nan).tolist()} contain NaN "
                "but the streamed sample that fixed the bin boundaries had "
                "none — use a full-stream sample (dataset_from_spark's "
                "two-pass reservoir) or pass a mapper with has_nan set")
        import jax.numpy as jnp

        binned = np.concatenate(binned_parts)
        del binned_parts[:]                # host peak: ~2x binned bytes
        ds = cls.__new__(cls)
        ds.min_data_in_bin = min_data_in_bin
        ds.max_bin_by_feature = max_bin_by_feature
        ds._user_mapper = user_mapper
        ds._sparse = None
        ds.X = None                          # raw floats were never kept
        ds.num_rows, ds.num_features = binned.shape
        ds.mapper = mapper
        ds.binned = jnp.asarray(binned)
        ds.label = np.concatenate(y_parts) if y_parts else None
        ds.weight = np.concatenate(w_parts) if w_parts else None
        ds.init_score = None
        ds.group_sizes = None
        ds.categorical_features = categorical_features
        return ds

    @property
    def shape(self):
        return (self.num_rows, self.num_features)

    def raw_dense(self) -> Optional[np.ndarray]:
        """Dense raw rows for the paths that need them (warm start / mesh
        padding); densifies a kept sparse matrix on demand."""
        if self.X is not None:
            return self.X
        if self._sparse is not None:
            return np.asarray(self._sparse.todense(), np.float32)
        return None

    def block_until_ready(self):
        """Wait for the device-side binned matrix (bench staging helper)."""
        import jax

        jax.block_until_ready(self.binned)
        return self
