"""Out-of-core GBDT: train on datasets far larger than device memory by
re-streaming host-cached QUANTIZED chunks through the shared ingestion layer.

The resident growers (grower.py / grower_depthwise.py) require the whole
binned matrix on device; past single-chip HBM the dataset size — not FLOPs —
is the wall (ROADMAP item 2). GPU tree-boosting work (arXiv:1706.08359)
showed that streaming a COMPRESSED feature matrix chunk-wise with per-chunk
histogram accumulation recovers near-resident throughput far beyond memory;
this module is that data plane:

* :class:`StreamedDataset` — ingests raw row chunks ONCE (dense or scipy
  sparse), learns bin boundaries with a one-pass
  :class:`~synapseml_tpu.ops.quantize.StreamingQuantileSketch` (bit-identical
  to the resident boundaries while the stream fits the sample buffer), and
  caches the quantized rows host-side as uniform feature-major uint8 chunks
  — 4x smaller than the raw floats, the compressed stream the device pulls.
  ``cache_dir=`` spills the quantized chunks to disk (.npy, re-read through
  :func:`~synapseml_tpu.io.ingest.read_chunk_file`'s mmap path) so even the
  QUANTIZED stream need not fit host RAM; pair with a
  :class:`~synapseml_tpu.io.ingest.DiskChunkSource` for a fully disk-backed
  pipeline.

* :func:`train_booster_streamed` — streamed tree growth, leafwise (the
  resident default: one best-gain split per pass) or level-synchronous
  depthwise. Per growth step, every chunk makes one device trip: a single
  jitted program routes the chunk's rows against the applied
  :class:`~synapseml_tpu.gbdt.grower_depthwise._LevelPlan` and scatter-adds
  the frontier histogram (ops/hist_kernel._hist_level_xla); chunk partials
  sum on device and flow through the SAME ``hist_allreduce_dtype`` ladder /
  split search / bookkeeping as the resident growers (the helpers are
  shared, not copied). With a ``mesh``, every per-chunk array is sharded
  over :data:`~synapseml_tpu.parallel.mesh.DATA_AXIS` and the per-step
  frontier partials cross the fabric ONCE per growth step through
  ``grower._maybe_psum`` — the {f32, bf16, int8} wire ladder with the
  exact-totals side wire, priced by ``grower.resolve_wire_dtype`` exactly
  like resident runs. Per-iteration bagging / GOSS / feature sampling use
  the SAME fold_in RNG streams as the resident path, generated from each
  chunk's global row offsets, so kill→resume stays bit-for-bit. A held-out
  stream (``valid_data=``) is scored incrementally per tree for
  validation-driven early stopping. Chunks move through a threaded
  :class:`~synapseml_tpu.io.ingest.ChunkPump` (transfer of chunk k+1
  overlaps compute on chunk k), and every chunk boundary is a preemption
  point + watchdog heartbeat (phase ``"gbdt.stream.chunk"``), so PR 2
  checkpoints and PR 10 elastic watchdogs compose with streaming for free.

* :func:`predict_streamed` — out-of-core scoring: raw chunks in, per-chunk
  predictions out, through the same pump.

Parity contract (tests/test_oocore.py): ``resident=True`` runs the IDENTICAL
jitted programs over pre-staged device-resident chunks — the pump, the
double-buffering, and the preemption machinery are bitwise-transparent, so
streamed == resident-mode trees bit for bit. Versus the classic resident
``train_booster`` the accumulation GEOMETRY differs (per-chunk partial sums
vs one whole-matrix scatter), so cross-path parity is a quality bound (AUC
within 1e-3 on the breast-cancer fixture), while boundary parity is exact
whenever the sketch never overflowed. See docs/out-of-core.md.

Remaining scope limits (raise loud, never silently degrade): gbdt/goss
boosting only (no dart/rf), binary/regression-family objectives
(num_class == 1), no ranking validation metrics, single-controller meshes
(``jax.process_count() == 1``).
"""

from __future__ import annotations

import collections
import dataclasses
import os
import time as _time
from typing import Callable, Iterable, List, NamedTuple, Optional, Sequence

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..io.ingest import (ChunkPump, read_chunk_file, stream_chunk_rows,
                         stream_depth)
from ..ops.hist_kernel import _hist_level_xla, features_padded, pad_bins
from ..ops.quantize import (BinMapper, CsrBinner, StreamingQuantileSketch,
                            apply_bins)
from .boosting import (Booster, BoosterConfig, _ckpt_load_gbdt,
                       _ckpt_save_gbdt, _default_metric, _eval_metric,
                       _is_rank_metric, _node_key_data, _sample_features_impl,
                       _train_metadata, _tree_assign_binned)
from .grower import (BITS, GrowerConfig, _best_for_leaf, _finalize_tree,
                     _init_split_state, _maybe_psum, _node_mask_fn,
                     _select_split_leaf)
from .grower_depthwise import (_apply_level_splits, _level_candidates,
                               _route_level)
from .objectives import HIGHER_IS_BETTER, get_objective

STREAM_PHASE = "gbdt.stream.chunk"


def _is_sparse(x) -> bool:
    return hasattr(x, "tocoo")


class StreamedDataset:
    """Out-of-core training data: a re-iterable chunk source plus the
    host-cached quantized form ``train_booster_streamed`` streams from.

    ``batches`` is a CALLABLE returning an iterator of chunks — each chunk a
    dense ``(c, F)`` array or scipy sparse matrix, optionally tupled with
    per-chunk labels/weights: ``X``, ``(X, y)`` or ``(X, y, w)``. The
    callable is invoked once per ingest pass (twice total when boundaries
    must be sketched: sketch pass, then bin+cache pass), so generators must
    be wrapped in a function, not passed pre-consumed. A
    :class:`~synapseml_tpu.io.ingest.DiskChunkSource` qualifies and
    additionally contributes its measured disk bandwidth to the chunk
    geometry choice.

    ``prepare(config)`` resolves the chunk geometry (io/ingest.py:
    explicit > env > bandwidth micro-probe, capped by the
    ``SYNAPSEML_TPU_STREAM_MEM_BUDGET`` device budget), learns boundaries
    (sketch — or adopts ``mapper``), and re-chunks the stream into uniform
    ``(FP, C)`` feature-major quantized host chunks (the last chunk padded
    with zero-mass rows so every device program compiles ONCE). Sparse
    chunks are quantized on device through
    :class:`~synapseml_tpu.ops.quantize.CsrBinner` — implicit zeros never
    densify at dataset scale.

    ``cache_dir`` spills the quantized chunks to ``.npy`` files instead of
    keeping them in host RAM; training re-reads them per pass through the
    mmap reader (``io.ingest.read_chunk_file``). Labels/weights/masks stay
    resident (1/F the data size — see docs/out-of-core.md).
    """

    def __init__(self, batches: Callable[[], Iterable],
                 num_features: Optional[int] = None,
                 mapper: Optional[BinMapper] = None,
                 categorical_features: Optional[Sequence[int]] = None,
                 chunk_rows: Optional[int] = None,
                 depth: Optional[int] = None,
                 exact_second_pass: Optional[bool] = None,
                 cache_dir: Optional[str] = None):
        if not callable(batches):
            raise TypeError(
                "StreamedDataset needs a CALLABLE returning an iterator of "
                "chunks (a consumed iterator cannot support the multiple "
                "ingest passes); wrap it: StreamedDataset(lambda: chunks)")
        self._batches = batches
        self.num_features = num_features
        self.mapper = mapper
        self._user_mapper = mapper is not None
        self.categorical_features = (list(categorical_features)
                                     if categorical_features else None)
        self._chunk_rows_arg = chunk_rows
        self._depth_arg = depth
        # exact second sketch pass when the one-pass sketch overflowed its
        # sample budget (ROADMAP 2d): None = let core/perfmodel price it,
        # True/False forces — the explicit bypass
        self._exact_second_pass = exact_second_pass
        self.second_pass_decision: Optional[dict] = None
        self._cache_dir = cache_dir
        self._rows_sketched = 0
        self.chunk_rows: Optional[int] = None     # C, after prepare()
        self.depth: Optional[int] = None
        self.chunks: List[dict] = []              # bT (FP, C), y/w/m (C,)
        self.chunk_real: List[int] = []           # real (unpadded) rows
        self.n_rows = 0
        self.sketch_exact: Optional[bool] = None  # None = mapper was given
        self._prepared_for = None

    @classmethod
    def from_arrays(cls, X, y=None, w=None, source_chunk: int = 65536,
                    **kwargs) -> "StreamedDataset":
        """Wrap in-memory arrays (dense or scipy sparse rows) as a chunk
        source — the fits-in-memory path of the parity tests and benches."""
        n = X.shape[0]
        f = X.shape[1]

        def batches():
            for i in range(0, n, source_chunk):
                sl = slice(i, min(i + source_chunk, n))
                yield (X[sl],
                       None if y is None else y[sl],
                       None if w is None else w[sl])

        return cls(batches, num_features=f, **kwargs)

    # -- ingest ------------------------------------------------------------
    def _norm_chunk(self, chunk):
        """(X, y, w) from any accepted chunk shape."""
        if isinstance(chunk, tuple):
            X = chunk[0]
            y = chunk[1] if len(chunk) > 1 else None
            w = chunk[2] if len(chunk) > 2 else None
        else:
            X, y, w = chunk, None, None
        if self.num_features is None:
            self.num_features = int(X.shape[1])
        elif int(X.shape[1]) != self.num_features:
            raise ValueError(f"chunk has {X.shape[1]} features, dataset has "
                             f"{self.num_features}")
        return X, y, w

    def _sketch_pass(self, cfg: BoosterConfig) -> None:
        seed = (cfg.seed if cfg.data_random_seed is None
                else int(cfg.data_random_seed))
        sketch = None
        for chunk in self._batches():
            X, _, _ = self._norm_chunk(chunk)
            if sketch is None:
                sketch = StreamingQuantileSketch(
                    self.num_features, cfg.max_bin, cfg.bin_sample_count,
                    self.categorical_features, seed=seed,
                    min_data_in_bin=cfg.min_data_in_bin,
                    max_bin_by_feature=cfg.max_bin_by_feature)
            if _is_sparse(X):
                coo = X.tocoo()
                sketch.update_csr(coo.data, coo.row, coo.col, X.shape[0])
            else:
                sketch.update(np.asarray(X, np.float32))
        if sketch is None or sketch.rows_seen == 0:
            raise ValueError("StreamedDataset source yielded no rows")
        self.sketch_exact = sketch.exact
        self._rows_sketched = int(sketch.rows_seen)
        self.mapper = sketch.finalize()

    def _maybe_exact_second_pass(self, cfg: BoosterConfig,
                                 pass_s: float) -> None:
        """ROADMAP 2d: the one-pass sketch overflowed its sample budget, so
        boundaries are reservoir-sampled. A second full pass with the budget
        raised to the stream length makes them exact — worth it only when
        that pass is cheap next to training. core/perfmodel prices the pass
        (measured sketch rate from THIS stream as the analytic prior) against
        the estimated training cost: num_iterations x tree levels re-streams
        of the same data. ``exact_second_pass=True/False`` bypasses."""
        from ..core import perfmodel

        rows, nfeat = self._rows_sketched, self.num_features
        if self._exact_second_pass is not None:
            take = bool(self._exact_second_pass)
            self.second_pass_decision = {"kind": "gbdt_sketch_pass",
                                         "arm": "exact" if take else "skip",
                                         "source": "explicit"}
        else:
            levels = max(1, int(np.ceil(np.log2(max(cfg.num_leaves, 2)))))
            train_est = pass_s * max(cfg.num_iterations, 1) * levels
            rate = rows / pass_s if pass_s > 0 else None
            take, dec = perfmodel.suggest_sketch_second_pass(
                float(rows), float(nfeat), rate, train_est)
            # an exact sketch buffers the full stream host-side — never
            # trade boundaries for an OOM
            if take and rows * nfeat * 4 > (2 << 30):
                take = False
                dec.arm, dec.used_fallback = "skip", True
                dec.source = "host_budget"
            self.second_pass_decision = dec.audit(observed_s=None)
        if not take:
            return
        t0 = _time.perf_counter()
        self._sketch_pass(dataclasses.replace(
            cfg, bin_sample_count=max(rows, cfg.bin_sample_count)))
        if isinstance(self.second_pass_decision, dict) and \
                self.second_pass_decision.get("source") != "explicit":
            self.second_pass_decision["observed_s"] = round(
                _time.perf_counter() - t0, 6)

    def _bin_chunk(self, X, binner: Optional[CsrBinner]) -> np.ndarray:
        """(c, F) quantized host rows for one raw chunk."""
        if _is_sparse(X):
            coo = X.tocoo()
            return np.asarray(binner(coo.data, coo.row, coo.col, X.shape[0]))
        return np.asarray(apply_bins(self.mapper, np.asarray(X, np.float32)))

    def prepare(self, config: BoosterConfig,
                row_multiple: int = 1) -> "StreamedDataset":
        """Idempotent per binning config: sketch (unless a mapper was given),
        resolve chunk geometry, quantize + cache the stream.

        ``row_multiple`` rounds the chunk row count up to a multiple (mesh
        training shards each chunk over the data axis, so C must divide by
        the worker count); a dataset already prepared under the same binning
        re-chunks — without re-sketching — when the multiple changes."""
        mult = max(int(row_multiple), 1)
        key = (config.max_bin, config.bin_sample_count,
               config.min_data_in_bin,
               tuple(config.max_bin_by_feature or ()),
               config.seed if config.data_random_seed is None
               else int(config.data_random_seed))
        if (self._prepared_for == key and self.chunk_rows
                and self.chunk_rows % mult == 0):
            return self
        if (self._prepared_for is not None and self._prepared_for != key
                and self._user_mapper is False):
            # re-preparing under different binning would silently retrain on
            # different boundaries — make the caller rebuild the dataset
            raise ValueError(
                f"StreamedDataset already prepared for binning {self._prepared_for}; "
                f"got {key} — build a fresh StreamedDataset")
        if self.mapper is None:
            t0 = _time.perf_counter()
            self._sketch_pass(config)
            pass_s = _time.perf_counter() - t0
            if self.sketch_exact is False:
                self._maybe_exact_second_pass(config, pass_s)
        if self.mapper.max_bin != config.max_bin:
            raise ValueError(
                f"mapper has max_bin={self.mapper.max_bin} but config asks "
                f"{config.max_bin}")

        F = self.num_features
        FP = features_padded(F)
        # one streamed row's device footprint: quantized bins (feature-major
        # uint8/16) + y/w/m/score f32 + node i32
        unit = 1 if self.mapper.max_bin <= 256 else 2
        row_bytes = FP * unit + 20
        self.depth = stream_depth(self._depth_arg)
        read_bps = None
        try:
            read_bps = self._batches.read_bytes_per_s
        except Exception:
            read_bps = None
        C = stream_chunk_rows(row_bytes, explicit=self._chunk_rows_arg,
                              depth=self.depth, read_bps=read_bps)
        if C % mult:
            C += mult - C % mult
        self.chunk_rows = C
        # perfmodel provenance when the probe branch picked the geometry
        # (None under the explicit/env bypass)
        from ..io import ingest as _ingest

        self.chunk_decision = _ingest.last_chunk_decision()
        bin_dtype = np.uint8 if unit == 1 else np.uint16
        if self._cache_dir is not None:
            os.makedirs(self._cache_dir, exist_ok=True)

        self.chunks, self.chunk_real, self.n_rows = [], [], 0
        binner = CsrBinner(self.mapper)
        buf_b = np.zeros((C, F), bin_dtype)
        buf_y = np.zeros(C, np.float32)
        buf_w = np.zeros(C, np.float32)
        fill = 0

        def flush():
            nonlocal fill, C
            if fill == 0:
                return
            if not self.chunks and fill < C:
                # the whole stream fit one partial chunk: shrink the chunk
                # to the real row count instead of padding (a probe-derived
                # C far above n_rows would otherwise make every device
                # program chew mostly zero-mass padding) — still a multiple
                # of the mesh worker count
                C = max(-(-fill // mult) * mult, mult)
                self.chunk_rows = C
            bT = np.zeros((FP, C), bin_dtype)
            bT[:F, :fill] = buf_b[:fill].T
            m = np.zeros(C, np.float32)
            m[:fill] = 1.0
            entry = {"y": buf_y[:C].copy(), "w": buf_w[:C].copy(), "m": m}
            bT = np.ascontiguousarray(bT)
            if self._cache_dir is not None:
                path = os.path.join(self._cache_dir,
                                    f"chunk{len(self.chunks):05d}.npy")
                np.save(path, bT)
                entry["bT_path"] = path
            else:
                entry["bT"] = bT
            self.chunks.append(entry)
            self.chunk_real.append(fill)
            buf_y[:] = 0.0
            buf_w[:] = 0.0
            fill = 0

        for chunk in self._batches():
            X, y, w = self._norm_chunk(chunk)
            c = int(X.shape[0])
            if c == 0:
                continue
            binned = self._bin_chunk(X, binner)
            y = (np.zeros(c, np.float32) if y is None
                 else np.asarray(y, np.float32))
            w = (np.ones(c, np.float32) if w is None
                 else np.asarray(w, np.float32))
            off = 0
            while off < c:
                take = min(C - fill, c - off)
                buf_b[fill:fill + take] = binned[off:off + take]
                buf_y[fill:fill + take] = y[off:off + take]
                buf_w[fill:fill + take] = w[off:off + take]
                fill += take
                off += take
                if fill == C:
                    flush()
        flush()
        self.n_rows = int(sum(self.chunk_real))
        if self.n_rows == 0:
            raise ValueError("StreamedDataset source yielded no rows")
        self._prepared_for = key
        return self

    def chunk_bT(self, i: int) -> np.ndarray:
        """Quantized (FP, C) bins of chunk ``i`` — RAM-resident, or re-read
        from the ``cache_dir`` spill through the mmap reader (so the chaos
        disk-fault hook and a real dying disk both surface here, loudly)."""
        ch = self.chunks[i]
        bT = ch.get("bT")
        if bT is not None:
            return bT
        arr = read_chunk_file(ch["bT_path"], i)
        want = (features_padded(self.num_features), int(self.chunk_rows))
        if tuple(arr.shape) != want:
            raise OSError(
                f"torn read of spilled chunk {ch['bT_path']!r}: got shape "
                f"{tuple(arr.shape)}, want {want}")
        return arr

    # -- host-side label access (1/F the data size; see docs/out-of-core.md)
    def labels(self) -> np.ndarray:
        return np.concatenate([ch["y"][:r] for ch, r in
                               zip(self.chunks, self.chunk_real)])

    def weights(self) -> np.ndarray:
        return np.concatenate([ch["w"][:r] for ch, r in
                               zip(self.chunks, self.chunk_real)])


# ---------------------------------------------------------------------------
# Per-chunk device programs — ONE compile each per (geometry, objective,
# mesh): mapper-dependent vectors (featp/catp/monop/nanp/catb), sample
# weights, and RNG keys are ARGUMENTS, never closed-over constants, so the
# lru_cache can only ever key on static shape
# ---------------------------------------------------------------------------

class _StreamState(NamedTuple):
    """Streamed growth state: the shared bookkeeping fields of
    grower._init_split_state plus the driver scalars. Satisfies the state
    contract of _apply_level_splits/_finalize_tree."""

    mask_id: jnp.ndarray
    level: jnp.ndarray
    progress: jnp.ndarray
    hist: jnp.ndarray
    bgain: jnp.ndarray
    bfeat: jnp.ndarray
    bbin: jnp.ndarray
    bdl: jnp.ndarray
    bcl: jnp.ndarray
    depth: jnp.ndarray
    leaf_parent: jnp.ndarray
    leaf_is_right: jnp.ndarray
    split_feature: jnp.ndarray
    split_bin: jnp.ndarray
    split_gain: jnp.ndarray
    split_type: jnp.ndarray
    default_left: jnp.ndarray
    cat_bitset: jnp.ndarray
    left_child: jnp.ndarray
    right_child: jnp.ndarray
    internal_value: jnp.ndarray
    internal_count: jnp.ndarray
    num_splits: jnp.ndarray


class _Programs(NamedTuple):
    root_chunk: Callable
    route_chunk: Callable
    child_chunk: Callable
    root_finish: Callable
    plan_level: Callable
    commit_level: Callable
    plan_leaf: Callable
    commit_leaf: Callable
    update_score: Callable
    finalize: Callable
    # mesh-only cross-shard reductions (None single-chip — _maybe_psum with
    # axis None is the identity, so the bookkeeping programs are shared)
    reduce_level: Optional[Callable] = None
    reduce_child: Optional[Callable] = None

    def cache_sizes(self) -> dict:
        """Compiled-executable counts per program (steady-state recompile
        guard in tests/test_oocore.py)."""
        return {name: getattr(fn, "_cache_size", lambda: -1)()
                for name, fn in zip(self._fields, self) if fn is not None}


@functools.lru_cache(maxsize=16)
def _stream_programs(gcfg: GrowerConfig, B: int, L: int, FP: int, bw: int,
                     C: int, obj_key: tuple, mesh=None) -> _Programs:
    obj = get_objective(obj_key[0], num_class=1, sigmoid=obj_key[1],
                        alpha=obj_key[2], fair_c=obj_key[3],
                        poisson_max_delta_step=obj_key[4],
                        tweedie_variance_power=obj_key[5])
    l1 = jnp.float32(gcfg.lambda_l1)
    l2 = jnp.float32(gcfg.lambda_l2)
    wire = gcfg.hist_allreduce_dtype

    def _gh(score, y, w, m):
        # padding rows carry w=0 but some objectives floor the hessian
        # (binary: max(h*w, 1e-16)) — the explicit mask multiply keeps them
        # at exactly zero, matching the resident growers' grad*in_bag
        g, h = obj.grad_hess(score, y, w)
        return g * m, h * m

    # ---- per-chunk local bodies (row dim from the ARGUMENT shape, so the
    # same body traces over full chunks single-chip and C/W-row shards
    # under shard_map). ``sw`` is the per-row sample weight: ones when
    # bagging/GOSS are off (multiplying by exactly 1.0 is bitwise-neutral),
    # {0,1} bagging masks, {0,amp,1} GOSS amplification — grad/hess scale by
    # it and the histogram mask drops sw==0 rows, mirroring the resident
    # samplers' (g*wmask, in_bag) contract.
    def _root_local(bT, y, w, m, score, sw):
        g, h = _gh(score, y, w, m)
        g, h = g * sw, h * sw
        m2 = m * (sw > 0)
        node = jnp.zeros(y.shape[0], jnp.int32)
        return _hist_level_xla(bT.astype(jnp.int32), g, h, m2, node, B, L)

    def _route_local(bT, y, w, m, score, node, plan, nanp, sw):
        bT32 = bT.astype(jnp.int32)
        node2 = _route_level(bT32, node, plan, nanp, gcfg, bw)
        g, h = _gh(score, y, w, m)
        g, h = g * sw, h * sw
        m2 = m * (sw > 0)
        return node2, _hist_level_xla(bT32, g, h, m2, node2, B, L)

    def _child_local(bT, y, w, m, score, node, plan, nanp, sw, new_right):
        # leafwise: route, then histogram ONLY the fresh right child — a
        # (1, FP, B, 3) partial, 1/L the depthwise wire bytes; the left
        # child comes from parent-minus-right on the committed state
        bT32 = bT.astype(jnp.int32)
        node2 = _route_level(bT32, node, plan, nanp, gcfg, bw)
        g, h = _gh(score, y, w, m)
        rsel = (node2 == new_right).astype(jnp.float32)
        g, h = g * sw * rsel, h * sw * rsel
        m2 = m * (sw > 0) * rsel
        hist = _hist_level_xla(bT32, g, h, m2,
                               jnp.zeros(y.shape[0], jnp.int32), B, 1)
        return node2, hist

    def _update_local(score, node, leaf_value, m):
        return score + leaf_value[node] * m

    reduce_level = reduce_child = None
    if mesh is None:
        root_chunk = jax.jit(_root_local)
        route_chunk = jax.jit(_route_local)
        child_chunk = jax.jit(_child_local)
        update_score = jax.jit(_update_local)
    else:
        from jax.sharding import PartitionSpec as P

        from ..parallel.collectives import shard_apply
        from ..parallel.mesh import DATA_AXIS as _DA

        _pv, _pr, _pm = P(_DA), P(), P(None, _DA)
        # chunk programs keep their histogram partial SHARD-LOCAL (out_specs
        # stack the (1, ...) local partials to (W, ...)); the host
        # accumulates shard-locally across chunks and ONE reduce program per
        # growth step crosses the fabric — chunks/step psums collapse to 1
        root_chunk = jax.jit(shard_apply(
            mesh, lambda *a: _root_local(*a)[None],
            in_specs=(_pm, _pv, _pv, _pv, _pv, _pv), out_specs=_pv))
        route_chunk = jax.jit(shard_apply(
            mesh,
            lambda *a: (lambda nd, hh: (nd, hh[None]))(*_route_local(*a)),
            in_specs=(_pm, _pv, _pv, _pv, _pv, _pv, _pr, _pr, _pv),
            out_specs=(_pv, _pv)))
        child_chunk = jax.jit(shard_apply(
            mesh, _child_local,
            in_specs=(_pm, _pv, _pv, _pv, _pv, _pv, _pr, _pr, _pv, _pr),
            out_specs=(_pv, _pv)))
        update_score = jax.jit(shard_apply(
            mesh, _update_local,
            in_specs=(_pv, _pv, _pr, _pv), out_specs=_pv))

        def _reduce_level_local(hw, ns):
            h = hw[0]
            # mask non-existent leaves BEFORE the wire: the exists predicate
            # is shard-UNIFORM (num_splits is replicated), so every shard
            # zeroes the same slots and the psum'd garbage never rides the
            # quantized rungs (grower_depthwise level_pass invariant)
            exists = jnp.arange(L) <= ns
            h = jnp.where(exists[:, None, None, None], h, 0.0)
            return _maybe_psum(h, _DA, wire)

        reduce_level = jax.jit(shard_apply(
            mesh, _reduce_level_local, in_specs=(_pv, _pr), out_specs=_pr))
        reduce_child = jax.jit(shard_apply(
            mesh, lambda hw: _maybe_psum(hw[0], _DA, wire)[None],
            in_specs=(_pv,), out_specs=_pr))

    # ---- bookkeeping programs (shared single-chip/mesh: their internal
    # _maybe_psum(axis=None) is the identity; mesh reductions happened in
    # reduce_level/reduce_child, so re-masking here is idempotent) --------
    @jax.jit
    def root_finish(hist, featp, catp, monop, nanp, catb, node_key):
        exists0 = jnp.arange(L) == 0
        hist = jnp.where(exists0[:, None, None, None], hist, 0.0)
        hist = _maybe_psum(hist, None, wire)
        nmask = _node_mask_fn(gcfg, featp, 0, node_key)
        rg, rf, rb, rdl, rcl, _ = _best_for_leaf(
            hist[0], nmask(jnp.int32(2 * (L - 1))), catp, monop, nanp, gcfg,
            l1, l2, catb)
        base = _init_split_state(L, B, bw, hist[0], rg, rf, rb, rdl, rcl, FP)
        return _StreamState(
            mask_id=jnp.full(L, 2 * (L - 1), jnp.int32),
            level=jnp.int32(0), progress=jnp.bool_(True), **base)

    @jax.jit
    def plan_level(s, catp, catb):
        do, order = _level_candidates(s, gcfg, L)
        s2, plan = _apply_level_splits(s, do, order, catp, catb, gcfg, B, bw,
                                       L)
        return s2, plan, do.any()

    @jax.jit
    def commit_level(s, hist, do_any, featp, catp, monop, nanp, catb,
                     node_key):
        exists2 = jnp.arange(L) <= s.num_splits
        hist = jnp.where(exists2[:, None, None, None], hist, 0.0)
        hist = _maybe_psum(hist, None, wire)
        nmask = _node_mask_fn(gcfg, featp, 0, node_key)
        masks = jax.vmap(nmask)(s.mask_id)
        bg, bf, bb, bdl_, bcl, _ = jax.vmap(
            lambda hl, fm: _best_for_leaf(hl, fm, catp, monop, nanp, gcfg,
                                          l1, l2, catb))(hist, masks)
        return s._replace(
            hist=hist, bgain=jnp.where(exists2, bg, -jnp.inf),
            bfeat=bf, bbin=bb, bdl=bdl_, bcl=bcl,
            level=s.level + 1, progress=do_any)

    @jax.jit
    def plan_leaf(s, catp, catb):
        # leafwise growth step: apply the single best-gain split (the
        # resident default policy) as a one-hot level plan — the SAME
        # bookkeeping (_apply_level_splits) the depthwise path uses
        l, do = _select_split_leaf(s, gcfg, L)
        do_vec = (jnp.arange(L) == l) & do
        order = jnp.arange(L, dtype=jnp.int32)
        s2, plan = _apply_level_splits(s, do_vec, order, catp, catb, gcfg, B,
                                       bw, L)
        return s2, plan, do, l

    @jax.jit
    def commit_leaf(s, child, l, featp, catp, monop, nanp, catb, node_key):
        nr = s.num_splits               # right-child leaf slot (post-apply)
        hist = _maybe_psum(child, None, wire)
        hist_r = hist[0]
        hist_l = s.hist[l] - hist_r     # parent-minus-right, exact in f32
        nmask = _node_mask_fn(gcfg, featp, 0, node_key)
        gl, fl, bl, dll, cll, _ = _best_for_leaf(
            hist_l, nmask(s.mask_id[l]), catp, monop, nanp, gcfg, l1, l2,
            catb)
        gr, fr, br, dlr, clr, _ = _best_for_leaf(
            hist_r, nmask(s.mask_id[nr]), catp, monop, nanp, gcfg, l1, l2,
            catb)
        return s._replace(
            hist=s.hist.at[l].set(hist_l).at[nr].set(hist_r),
            bgain=s.bgain.at[l].set(gl).at[nr].set(gr),
            bfeat=s.bfeat.at[l].set(fl).at[nr].set(fr),
            bbin=s.bbin.at[l].set(bl).at[nr].set(br),
            bdl=s.bdl.at[l].set(dll).at[nr].set(dlr),
            bcl=s.bcl.at[l].set(cll).at[nr].set(clr),
            level=s.level + 1, progress=jnp.bool_(True))

    finalize = jax.jit(lambda s: _finalize_tree(s, gcfg, L))
    return _Programs(root_chunk, route_chunk, child_chunk, root_finish,
                     plan_level, commit_level, plan_leaf, commit_leaf,
                     update_score, finalize, reduce_level, reduce_child)


# ---------------------------------------------------------------------------
# Streamed training
# ---------------------------------------------------------------------------

def _check_supported(cfg: BoosterConfig, has_valid: bool = False) -> None:
    bad = []
    if cfg.boosting_type not in ("gbdt", "goss"):
        bad.append(f"boosting_type={cfg.boosting_type!r}")
    if cfg.objective in ("multiclass", "softmax", "multiclassova",
                         "lambdarank") or cfg.num_class > 1:
        bad.append(f"objective={cfg.objective!r}/num_class={cfg.num_class}")
    if cfg.early_stopping_round > 0 and not has_valid:
        bad.append("early stopping without a held-out stream "
                   "(pass valid_data=)")
    if has_valid and _is_rank_metric(cfg.metric
                                     or _default_metric(cfg.objective)):
        bad.append("ranking validation metrics")
    if bad:
        raise NotImplementedError(
            "out-of-core streamed training does not support: "
            + ", ".join(bad) + " (use the resident train_booster path)")


def _stream_sample_weights(cfg: BoosterConfig, n: int, key0, it: int,
                           gnorm, in_bag_cur, yj):
    """Per-iteration (n,) sample-weight vector — the weight-vector
    formulation of boosting._sample_rows_impl, drawing from the SAME fold_in
    RNG streams so a streamed run samples the rows a resident run would.
    Returns ``(sw, in_bag)``: ``sw`` is None when sampling is off this
    iteration's config, else the f32 per-row weights ({0,1} bagging,
    {0, amp, 1} GOSS); ``in_bag`` is the bagging mask carried across
    iterations (refreshed every ``bagging_freq`` rounds — checkpointed so
    kill→resume replays identically)."""
    goss_mode = cfg.boosting_type == "goss"
    stratified = (cfg.pos_bagging_fraction < 1.0
                  or cfg.neg_bagging_fraction < 1.0)
    do_bag = (cfg.bagging_freq > 0
              and (cfg.bagging_fraction < 1.0 or stratified))
    key0 = jax.random.PRNGKey(cfg.seed) if key0 is None else key0
    if goss_mode:
        top_n = int(cfg.top_rate * n)
        rand_n = int(cfg.other_rate * n)
        amp = (1.0 - cfg.top_rate) / max(cfg.other_rate, 1e-12)
        order = jnp.argsort(-gnorm)
        ranks = jnp.zeros(n, jnp.int32).at[order].set(
            jnp.arange(n, dtype=jnp.int32))
        kg = (jax.random.fold_in(key0, cfg.extra_seed) if cfg.extra_seed
              else key0)   # default 0 keeps the established stream
        u = jax.random.uniform(jax.random.fold_in(kg, it), (n,))
        rest = ranks >= top_n
        pick = rest & (u < (rand_n / max(n - top_n, 1)))
        sw = jnp.where(ranks < top_n, 1.0, jnp.where(pick, amp, 0.0))
        return sw.astype(jnp.float32), in_bag_cur
    if do_bag:
        kb = (jax.random.fold_in(key0, cfg.bagging_seed)
              if cfg.bagging_seed != 3 else key0)  # default keeps the stream
        u = jax.random.uniform(
            jax.random.fold_in(kb, 20_000_000 + it), (n,))
        if stratified and yj is not None:
            frac = jnp.where(yj > 0, cfg.pos_bagging_fraction,
                             cfg.neg_bagging_fraction)
        else:
            frac = cfg.bagging_fraction
        fresh = (u < frac).astype(jnp.float32)
        bag = fresh if it % max(cfg.bagging_freq, 1) == 0 else in_bag_cur
        return bag, bag
    return None, in_bag_cur


def _tree_to_host(tree) -> "tuple":
    return type(tree)(*(np.asarray(jax.device_get(a)) for a in tree))


def _stream_fingerprint(cfg: BoosterConfig, data: StreamedDataset,
                        mesh=None) -> str:
    """Resume identity: config + chunk geometry + mesh shape + label digest.
    The chunk geometry is part of the identity because per-chunk partial
    sums make the accumulation order — and therefore the grown trees — a
    function of C; the mesh axes likewise fix the shard-local accumulation
    and wire-reduction order."""
    import hashlib
    import zlib

    mesh_axes = (None if mesh is None
                 else tuple(sorted(dict(mesh.shape).items())))
    h = hashlib.sha256()
    h.update(repr(sorted(dataclasses.asdict(cfg).items())).encode())
    h.update(repr((int(data.n_rows), int(data.num_features),
                   int(data.chunk_rows), mesh_axes,
                   zlib.crc32(np.ascontiguousarray(
                       data.labels()).tobytes()))).encode())
    return h.hexdigest()


def train_booster_streamed(
    data: StreamedDataset,
    config: BoosterConfig,
    *,
    resident: bool = False,
    mesh=None,
    valid_data=None,
    measures=None,
    checkpoint_store=None,
    checkpoint_every: int = 0,
    resume: bool = True,
    feature_names: Optional[List[str]] = None,
) -> Booster:
    """Grow ``config.num_iterations`` trees over an out-of-core dataset.

    Leafwise growth makes ``2 + num_splits`` passes over the quantized chunk
    stream per tree (root histogram, one right-child histogram per split,
    leaf-value score update); depthwise makes ``levels + 2``. Every pass is
    a fresh :class:`~synapseml_tpu.io.ingest.ChunkPump` with globally
    monotonic boundary steps, so a preemption lands at a unique chunk
    boundary and resume (tree-boundary snapshots through
    ``checkpoint_store``) replays to a bit-identical model — bagging/GOSS
    masks are re-derived from the per-iteration fold_in streams and the
    checkpointed scores/in-bag state, never from mutable RNG.

    ``mesh`` shards every per-chunk array over
    :data:`~synapseml_tpu.parallel.mesh.DATA_AXIS` (single-controller; C is
    rounded to a worker multiple by ``prepare``): chunk histograms stay
    shard-local and ONE reduction per growth step crosses the fabric through
    the ``hist_allreduce_dtype`` wire ladder.

    ``valid_data`` (a ``(Xv, yv[, wv])`` tuple or a prepared
    :class:`StreamedDataset` sharing this dataset's mapper) is scored
    incrementally per tree — one leaf-assignment pass over the held-out
    chunks — and drives LightGBM-style best-iteration tracking / early
    stopping identically to the resident path.

    ``resident=True`` pre-stages every chunk on device and drives the SAME
    jitted programs without the pump — the bitwise baseline the parity tests
    compare against, and the honest denominator for the streaming-overhead
    bench (identical math, zero transfer).
    """
    from ..core.logging import InstrumentationMeasures

    if measures is None:
        measures = InstrumentationMeasures()
    cfg = config
    has_valid = valid_data is not None
    _check_supported(cfg, has_valid)

    W = 1
    if mesh is not None:
        if jax.process_count() > 1:
            raise NotImplementedError(
                "mesh-streamed GBDT is single-controller: "
                "jax.process_count() must be 1 (multi-process stage groups "
                "route through the resident train_booster path)")
        from ..parallel.mesh import DATA_AXIS as _DA_NAME
        W = int(dict(mesh.shape).get(_DA_NAME, 1))

    _fit_t0 = _time.perf_counter()
    autoconfig_info = {}

    with measures.span("streamIngest"):
        data.prepare(cfg, row_multiple=W)
    mapper = data.mapper
    F = data.num_features
    C = int(data.chunk_rows)
    FP = features_padded(F)
    B = pad_bins(cfg.max_bin)
    L = cfg.num_leaves
    bw = (B + BITS - 1) // BITS
    n = int(data.n_rows)

    # auto-configuration: the wire rung and the tree-learner route resolve
    # through the same perf-model surfaces as resident runs (ISSUE 15 —
    # streamed runs are priced, not special-cased)
    if cfg.hist_allreduce_dtype == "auto":
        from .grower import resolve_wire_dtype

        wd, wdec = resolve_wire_dtype(cfg, mesh, n, F)
        cfg.hist_allreduce_dtype = wd
        autoconfig_info["wire_dtype"] = wdec.provenance()
    routing_info = None
    if cfg.tree_learner == "auto":
        choice = "data" if W > 1 else "serial"
        cfg.tree_learner = choice
        routing_info = {"tree_learner": choice,
                        "router": "streamed_data_plane", "workers": W}
    elif mesh is not None and cfg.tree_learner in ("voting", "feature"):
        raise NotImplementedError(
            f"mesh-streamed GBDT shards over the data axis only "
            f"(tree_learner='data'); got {cfg.tree_learner!r}")

    has_cat = bool(np.asarray(mapper.is_categorical).any())
    gcfg = cfg.grower(has_categorical=has_cat)
    leafwise = cfg.growth_policy == "leafwise"
    max_levels = gcfg.max_depth if gcfg.max_depth > 0 else L - 1

    # per-feature device constants (arguments to every program — see the
    # _stream_programs cache-keying note)
    featp = jnp.zeros(FP, bool).at[:F].set(True)
    catp = jnp.zeros(FP, bool).at[:F].set(jnp.asarray(mapper.is_categorical))
    mono = np.zeros(F, np.int32)
    if cfg.monotone_constraints is not None:
        mc = np.asarray(cfg.monotone_constraints, np.int32)
        mono[:len(mc)] = mc
    monop = jnp.zeros(FP, jnp.int32).at[:F].set(jnp.asarray(mono))
    nanp = jnp.full(FP, 0x7FFF, jnp.int32).at[:F].set(
        jnp.asarray(np.asarray(mapper.nan_bins, np.int32)))
    _cc = (np.asarray(mapper.cat_counts, np.int32)
           if getattr(mapper, "cat_counts", None) is not None
           else np.asarray(mapper.num_bins, np.int32) - 1)
    catb = jnp.full(FP, B, jnp.int32).at[:F].set(jnp.asarray(
        np.where(np.asarray(mapper.is_categorical), _cc, np.int32(0x7FFF))))

    obj_key = (cfg.objective, cfg.sigmoid, cfg.alpha, cfg.fair_c,
               cfg.poisson_max_delta_step, cfg.tweedie_variance_power)
    progs = _stream_programs(gcfg, B, L, FP, bw, C, obj_key, mesh)

    obj = get_objective(cfg.objective, num_class=1, sigmoid=cfg.sigmoid,
                        alpha=cfg.alpha, fair_c=cfg.fair_c,
                        poisson_max_delta_step=cfg.poisson_max_delta_step,
                        tweedie_variance_power=cfg.tweedie_variance_power)
    ys_host, ws_host = data.labels(), data.weights()
    if cfg.boost_from_average:
        base = np.atleast_1d(np.asarray(
            obj.init_score(jnp.asarray(ys_host), jnp.asarray(ws_host)),
            np.float64))
    else:
        base = np.zeros(1)

    # ---- placement: mesh shards the row dim over DATA_AXIS ---------------
    if mesh is not None:
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        from ..parallel.mesh import DATA_AXIS as _DA_NAME

        _sh_mat = NamedSharding(mesh, P(None, _DA_NAME))
        _sh_vec = NamedSharding(mesh, P(_DA_NAME))

        def _put_mat(a):
            return jax.device_put(a, _sh_mat)

        def _put_vec(a):
            return jax.device_put(a, _sh_vec)

        def _put_chunk(tail):
            # ONE batched device_put for the whole chunk tuple ((mat,
            # vec...); None slots pass through as empty pytree nodes,
            # already-placed shared constants are returned as-is) — per-call
            # dispatch overhead is the dominant streaming cost on small
            # chunks, so one call per chunk instead of seven
            shs = tuple(None if a is None else (_sh_mat if k == 0
                                                else _sh_vec)
                        for k, a in enumerate(tail))
            return jax.device_put(tail, shs)
    else:
        _put_mat = _put_vec = jax.device_put

        def _put_chunk(tail):
            return jax.device_put(tail)

    # ---- per-iteration sampling state ------------------------------------
    goss_mode = cfg.boosting_type == "goss"
    stratified = (cfg.pos_bagging_fraction < 1.0
                  or cfg.neg_bagging_fraction < 1.0)
    do_bag = (cfg.bagging_freq > 0
              and (cfg.bagging_fraction < 1.0 or stratified))
    sampling = goss_mode or do_bag
    do_feat = cfg.feature_fraction < 1.0
    key0 = jax.random.PRNGKey(cfg.seed)
    in_bag_vec = np.ones(n, np.float32)
    offs = np.concatenate([[0], np.cumsum(data.chunk_real)]).astype(np.int64)
    yj_dev = jnp.asarray(ys_host) if (do_bag and stratified) else None
    if goss_mode:
        y_flat_dev = jnp.asarray(ys_host)
        w_flat_dev = jnp.asarray(ws_host)

    nchunks = len(data.chunks)
    # per-chunk mutable state. Streamed: host arrays re-placed per pass
    # (the whole point — only depth+1 chunks of device state exist at once).
    # Resident: everything device-pinned once; same programs, same values.
    scores = [np.full(C, np.float32(base[0]), np.float32)
              for _ in range(nchunks)]
    ones_sw_host = np.ones(C, np.float32)
    dev_static = None
    # shared device constants for BOTH modes: the all-rows-at-root node
    # vector and the inactive sample-weight vector are identical for every
    # chunk, so place them once — re-placing an already-committed array is
    # a no-op, which removes two of the per-chunk puts from streamed passes
    zero_nodes_dev = _put_vec(np.zeros(C, np.int32))
    ones_sw_dev = _put_vec(ones_sw_host)
    nodes = [zero_nodes_dev] * nchunks
    if resident:
        dev_static = [(_put_mat(data.chunk_bT(i)),
                       _put_vec(data.chunks[i]["y"]),
                       _put_vec(data.chunks[i]["w"]),
                       _put_vec(data.chunks[i]["m"]))
                      for i in range(nchunks)]
        scores = [_put_vec(s) for s in scores]
    sw_ones = [ones_sw_dev] * nchunks

    # ---- held-out validation stream --------------------------------------
    if has_valid:
        if isinstance(valid_data, StreamedDataset):
            vd = valid_data
        else:
            Xv = valid_data[0]
            yv_in = valid_data[1]
            wv_in = valid_data[2] if len(valid_data) > 2 else None
            vd = StreamedDataset.from_arrays(Xv, yv_in, wv_in)
        if vd.mapper is None:
            # the held-out stream scores against the TRAINING boundaries
            vd.mapper = mapper
            vd._user_mapper = True
        vd.prepare(cfg)
        if vd.num_features != F:
            raise ValueError(
                f"valid_data has {vd.num_features} features, train has {F}")
        yv_host = vd.labels()
        wv_all = vd.weights()
        wv_eval = (None if np.all(wv_all == 1.0)
                   else jnp.asarray(wv_all, jnp.float32))
        nv = int(vd.n_rows)
        score_v = np.full(nv, np.float32(base[0]), np.float32)
        metric_name = cfg.metric or _default_metric(cfg.objective)
        higher_better = metric_name.split("@")[0] in HIGHER_IS_BETTER
        nanv = jnp.asarray(np.asarray(mapper.nan_bins, np.int32))
        best_metric, best_iter = None, -1
        stopped_early = False

    # --- crash-safe snapshots at tree boundaries (PR 2 CheckpointStore) ---
    ckpt_store = checkpoint_store
    if isinstance(ckpt_store, str):
        from ..core.checkpoint import CheckpointStore

        ckpt_store = CheckpointStore(ckpt_store)
    if ckpt_store is not None and checkpoint_every <= 0:
        checkpoint_every = 1
    fingerprint = (None if ckpt_store is None
                   else _stream_fingerprint(cfg, data, mesh))
    ckpt_path = "train_booster_streamed"

    trees: List = []
    start_iter = 0
    if ckpt_store is not None and resume:
        saved = _ckpt_load_gbdt(ckpt_store, fingerprint, ckpt_path)
        if saved is not None:
            start_iter = int(saved["iteration"])
            from .grower import TreeArrays

            trees = [TreeArrays(*[np.asarray(a) for a in t])
                     for t in saved["trees"]]
            flat = np.asarray(saved["score"], np.float32)
            off = 0
            for i, r in enumerate(data.chunk_real):
                sc = np.full(C, np.float32(base[0]), np.float32)
                sc[:r] = flat[off:off + r]
                off += r
                scores[i] = _put_vec(sc) if resident else sc
            bag_saved = saved.get("in_bag")
            if bag_saved is not None:
                in_bag_vec = np.asarray(bag_saved, np.float32)
            if has_valid and saved.get("score_v") is not None:
                score_v = np.asarray(saved["score_v"], np.float32)
                bm = saved.get("best_metric")
                best_metric = (None if bm is None
                               or not np.isfinite(np.float64(bm))
                               else float(bm))
                best_iter = int(saved.get("best_iter", -1))

    step_base = 0       # globally monotonic chunk-boundary step counter

    def passes(sw_list, need_data=True, need_nodes=True):
        """One pass over the chunk stream: yields (idx, device chunk state).
        Streamed mode pumps host chunks through a producer thread (place =
        one batched device_put per chunk, so transfer k+1 overlaps compute
        on k; disk-spilled chunks re-read through the mmap reader inside
        the producer); resident mode walks the pre-staged device list.
        ``need_data=False`` is the score-update pass: ``update_score``
        consumes only (score, node, mask), so the feature matrix is
        neither re-read from its source (a full extra disk pass for
        spilled/disk-backed chunks) nor placed. ``need_nodes=False`` is
        the root pass, which ignores the node vector. Neither flag changes
        the chunk-boundary step count."""
        nonlocal step_base
        if resident:
            for i in range(nchunks):
                yield i, dev_static[i] + (scores[i], nodes[i], sw_list[i])
            return

        def src():
            for i in range(nchunks):
                ch = data.chunks[i]
                if need_data:
                    yield (i, data.chunk_bT(i), ch["y"], ch["w"], ch["m"],
                           scores[i], nodes[i] if need_nodes else None,
                           sw_list[i])
                else:
                    yield (i, None, None, None, ch["m"],
                           scores[i], nodes[i], sw_list[i])

        def place(item):
            return (item[0],) + tuple(_put_chunk(tuple(item[1:])))

        # a producer thread only buys overlap when there is a spare core to
        # run it on; on a single-core host the thread just steals GIL
        # slices from program dispatch, so fall back to the pump's
        # synchronous lookahead (identical chunk order and step counting)
        pump = ChunkPump(src(), place=place, depth=data.depth,
                         threaded=(os.cpu_count() or 2) > 1,
                         phase=STREAM_PHASE, step_base=step_base,
                         name="gbdt")
        try:
            for item in pump:
                yield item[0], item[1:]
        finally:
            step_base += max(pump.chunks_consumed, pump.chunks_produced)

    # Bounded-lag D2H: a pass's per-chunk (C,) result used to be pulled to
    # host synchronously (np.asarray), which blocked Python on the full
    # program+transfer latency of EVERY chunk — the resident path instead
    # dispatches all chunk programs asynchronously and syncs once per
    # growth step, which is exactly why it is faster. So park the device
    # array, start its host copy asynchronously, and materialize it lagged
    # behind the consumer. A parked result is C*4 bytes vs the chunk's
    # C*row_bytes H2D footprint, so capping parked chunks at
    # (depth+1)*row_bytes/4 keeps D2H staging inside the SAME byte
    # envelope the in-flight budget already grants the H2D side — and lets
    # typical passes park everything, collapsing per-chunk host waits into
    # one pass-end sync. Values are untouched, so streamed stays
    # bit-for-bit with resident mode, and the pump producer only ever
    # reads slots AHEAD of the consumer (previous-pass values), so the
    # lagged write can never race a read.
    d2h_lag = max(int(data.depth), (int(data.depth) + 1) * (FP + 20) // 4)

    def _park(pending, out_list, i, dev_arr):
        copy_async = getattr(dev_arr, "copy_to_host_async", None)
        if copy_async is not None:
            copy_async()
        pending.append((i, dev_arr))
        while len(pending) > d2h_lag:
            j, a = pending.popleft()
            out_list[j] = np.asarray(a)

    def _flush(pending, out_list):
        while pending:
            j, a = pending.popleft()
            out_list[j] = np.asarray(a)

    def _tree_sample_weights(t):
        """Per-chunk (C,) sample-weight slices for iteration ``t``, cut from
        the full (n,) vector by each chunk's global row offsets (padding
        rows get sw=0 — already zero-mass through m)."""
        nonlocal in_bag_vec
        gnorm = None
        if goss_mode:
            flat = np.concatenate([np.asarray(scores[i])[:r]
                                   for i, r in enumerate(data.chunk_real)])
            g, _ = obj.grad_hess(jnp.asarray(flat), y_flat_dev, w_flat_dev)
            gnorm = jnp.abs(g)
        sw_vec, bag = _stream_sample_weights(
            cfg, n, key0, t, gnorm, jnp.asarray(in_bag_vec), yj_dev)
        in_bag_vec = np.asarray(bag, np.float32)
        if sw_vec is None:
            return sw_ones
        sw_np = np.asarray(sw_vec, np.float32)
        out = []
        for i, r in enumerate(data.chunk_real):
            v = np.zeros(C, np.float32)
            v[:r] = sw_np[offs[i]:offs[i] + r]
            out.append(_put_vec(v) if resident else v)
        return out

    with measures.span("trainingIterations"):
        for t in range(start_iter, cfg.num_iterations):
            sw_list = _tree_sample_weights(t) if sampling else sw_ones
            if do_feat:
                featm = _sample_features_impl(cfg, F, key0, t)
                featp_t = featp & jnp.zeros(FP, bool).at[:F].set(featm)
            else:
                featp_t = featp
            nk = _node_key_data(key0, t, 0)

            # ---- root histogram pass --------------------------------------
            hist = None
            for i, (bT, y, w, m, sc, nd, sw) in passes(sw_list,
                                                       need_nodes=False):
                hc = progs.root_chunk(bT, y, w, m, sc, sw)
                hist = hc if hist is None else hist + hc
                nodes[i] = zero_nodes_dev
            if progs.reduce_level is not None:
                hist = progs.reduce_level(hist, jnp.int32(0))
            s = progs.root_finish(hist, featp_t, catp, monop, nanp, catb, nk)

            if leafwise:
                # ---- leafwise growth: one split (one stream pass) each ----
                splits = 0
                while splits < L - 1:
                    s, plan, do, l = progs.plan_leaf(s, catp, catb)
                    if not bool(do):
                        break
                    nr = s.num_splits
                    child = None
                    pend = collections.deque()
                    for i, (bT, y, w, m, sc, nd, sw) in passes(sw_list):
                        node2, hc = progs.child_chunk(bT, y, w, m, sc, nd,
                                                      plan, nanp, sw, nr)
                        if resident:
                            nodes[i] = node2
                        else:
                            _park(pend, nodes, i, node2)
                        child = hc if child is None else child + hc
                    _flush(pend, nodes)
                    if progs.reduce_child is not None:
                        child = progs.reduce_child(child)
                    s = progs.commit_leaf(s, child, l, featp_t, catp, monop,
                                          nanp, catb, nk)
                    splits = int(s.num_splits)
            else:
                # ---- level-synchronous depthwise growth -------------------
                progress, num_splits, level = True, 0, 0
                while progress and num_splits < L - 1 and level < max_levels:
                    s, plan, do_any = progs.plan_level(s, catp, catb)
                    hist = None
                    pend = collections.deque()
                    for i, (bT, y, w, m, sc, nd, sw) in passes(sw_list):
                        node2, hc = progs.route_chunk(bT, y, w, m, sc, nd,
                                                      plan, nanp, sw)
                        if resident:
                            nodes[i] = node2
                        else:
                            _park(pend, nodes, i, node2)
                        hist = hc if hist is None else hist + hc
                    _flush(pend, nodes)
                    if progs.reduce_level is not None:
                        hist = progs.reduce_level(hist, s.num_splits)
                    s = progs.commit_level(s, hist, do_any, featp_t, catp,
                                           monop, nanp, catb, nk)
                    progress = bool(s.progress)
                    num_splits = int(s.num_splits)
                    level = int(s.level)

            tree = _tree_to_host(progs.finalize(s))
            trees.append(tree)

            # ---- held-out stream: incremental scoring + early stop --------
            if has_valid:
                lv_np = np.asarray(tree.leaf_value)
                off = 0
                for i, r in enumerate(vd.chunk_real):
                    binned = jnp.asarray(np.ascontiguousarray(
                        vd.chunk_bT(i)[:F, :r].T).astype(np.int32))
                    leaf = np.asarray(_tree_assign_binned(tree, binned,
                                                          nanv))
                    score_v[off:off + r] += lv_np[leaf]
                    off += r
                raw_v = jnp.asarray(score_v, jnp.float32)[:, None]
                pred_v = obj.transform(raw_v[:, 0])
                mval = float(_eval_metric(metric_name, yv_host, pred_v,
                                          raw_v, (None, yv_host), 1, cfg,
                                          wv_eval))
                tol = cfg.improvement_tolerance
                improved = (best_metric is None
                            or (mval > best_metric + tol if higher_better
                                else mval < best_metric - tol))
                if improved:
                    best_metric, best_iter = mval, t
                if (cfg.early_stopping_round > 0
                        and t - best_iter >= cfg.early_stopping_round):
                    trees = trees[:best_iter + 1]
                    stopped_early = True
                    break

            # ---- streamed score update ------------------------------------
            lv = np.asarray(tree.leaf_value)
            pend = collections.deque()
            for i, (bT, y, w, m, sc, nd, sw) in passes(sw_list,
                                                       need_data=False):
                sc2 = progs.update_score(sc, nd, lv, m)
                if resident:
                    scores[i] = sc2
                else:
                    _park(pend, scores, i, sc2)
            _flush(pend, scores)

            if (ckpt_store is not None
                    and (t + 1) % max(checkpoint_every, 1) == 0):
                flat = np.concatenate(
                    [np.asarray(scores[i])[:r]
                     for i, r in enumerate(data.chunk_real)])
                payload = {
                    "iteration": t + 1,
                    "trees": [tuple(np.asarray(a) for a in tr)
                              for tr in trees],
                    "score": flat,
                    "in_bag": np.asarray(in_bag_vec, np.float32)}
                if has_valid:
                    payload["score_v"] = score_v.copy()
                    payload["best_metric"] = np.float64(
                        np.nan if best_metric is None else best_metric)
                    payload["best_iter"] = int(best_iter)
                _ckpt_save_gbdt(ckpt_store, t + 1, payload, fingerprint,
                                ckpt_path, measures)

    meta = _train_metadata(routing_info, autoconfig_info, _fit_t0) or {}
    meta["streamed"] = {
        "chunk_rows": C, "num_chunks": nchunks,
        "rows": int(data.n_rows), "resident": bool(resident),
        "sketch_exact": data.sketch_exact,
        "chunk_boundaries_visited": int(step_base),
        "growth_policy": cfg.growth_policy,
        "workers": W,
        **({"sketch_second_pass": data.second_pass_decision}
           if data.second_pass_decision else {}),
        **({"chunk_decision": data.chunk_decision}
           if getattr(data, "chunk_decision", None) else {}),
    }
    if has_valid:
        meta["streamed"]["stopped_early"] = bool(stopped_early)
    booster = Booster(
        mapper, cfg, trees, [1.0] * len(trees), base,
        feature_names=feature_names,
        best_iteration=(best_iter if has_valid else -1),
        best_score=(best_metric if has_valid else None),
        metadata=meta)
    return booster


def predict_streamed(booster: Booster, batches: Iterable,
                     chunk_rows: Optional[int] = None,
                     depth: Optional[int] = None, **predict_kwargs):
    """Out-of-core scoring: iterate raw ``X`` chunks (dense or scipy sparse)
    through the shared pump and yield one prediction array per chunk. The
    pump's synchronous lookahead dispatches the next chunk's quantize +
    transfer while the consumer holds the previous result — the dl
    ``_prefetch`` overlap shape applied to scoring."""
    def src():
        for chunk in batches:
            X = chunk[0] if isinstance(chunk, tuple) else chunk
            yield np.asarray(X.todense() if _is_sparse(X) else X, np.float32)

    pump = ChunkPump(src(), place=None, depth=stream_depth(depth),
                     threaded=False, name="gbdt-predict")
    for X in pump:
        yield np.asarray(booster.predict(X, **predict_kwargs))
