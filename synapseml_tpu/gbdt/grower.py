"""Leaf-wise histogram tree grower — partitioned rows + MXU histogram kernel.

TPU-native redesign of the LightGBM serial/data-parallel tree learner the
reference drives through LGBM_BoosterUpdateOneIter (reference call stack:
booster/LightGBMBooster.scala:355-392 → C++ ConstructHistograms / FindBestSplit /
Split loop; SURVEY.md §3.1 "the hot loop"). v2 design, shaped by TPU costs:

  * **Row partitioning** (LightGBM's DataPartition): rows live in a position
    array kept sorted by leaf, each leaf owning a contiguous range. A split
    stably partitions only its leaf's range (bucketed static sizes via
    ``lax.switch`` — XLA needs static shapes, so ranges are processed at the
    smallest power-of-two bucket that covers them, masked to the real range).
  * **Histogram subtraction** (LightGBM's parent-minus-sibling): per split,
    only the SMALLER child's histogram is built (ops/hist_kernel.py — two-level
    one-hot matmuls on the MXU); the sibling is parent − child from the
    per-leaf histogram cache. Total histogrammed rows per tree drop from
    O(num_leaves·N) to O(N·log(num_leaves)/2).
  * The ENTIRE growth loop is one ``lax.fori_loop`` with static shapes —
    exactly ``num_leaves - 1`` iterations; when no leaf has a valid split the
    remaining iterations no-op.
  * Leaf numbering matches LightGBM's Tree::Split: splitting leaf ``l`` at step
    ``i`` creates internal node ``i``; the left child keeps leaf id ``l`` and
    the right child becomes leaf ``i + 1``. Child pointers use ``~leaf_index``,
    so the arrays serialize directly into the LightGBM model-string format
    (gbdt/model_io.py).
  * Categorical splits: bins sorted by grad/(hess + cat_smooth), prefix scan,
    chosen prefix encoded as a bitset — LightGBM's many-vs-many algorithm.
  * Monotone constraints ("basic" mode): violating splits masked.
  * **Learned missing direction**: features with NaN carry a dedicated NaN bin
    (ops/quantize.py); every candidate threshold is scored with the NaN bin's
    totals routed left AND right, and the winning direction is recorded as the
    per-split ``default_left`` bit (LightGBM missing_type=NaN semantics).

Distributed data-parallel: run under ``shard_map`` with rows sharded on the
data axis and ``axis_name`` set — each device partitions its own rows, builds
local child histograms, and ONE ``lax.psum`` of the (F, B, 3) histogram per
split replaces LightGBM's socket-ring reduce-scatter (NetworkManager.scala).
Split decisions are taken from the summed histogram, so they are bitwise
identical on every device (uniform control flow by construction).
"""

from __future__ import annotations

import math
import sys
from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..ops.hist_kernel import (child_histogram, default_chunk,
                               features_padded, pad_bins, range_histogram,
                               segmented_histograms_available)
from ..ops.partition_kernel import (partition_kernel_available,
                                    partition_window)

BITS = 32  # bitset word width for categorical splits
def _chunk() -> int:
    """Kernel row chunk; row counts pad to a multiple of this so the Pallas
    grid divides evenly. Resolved lazily at trace time (after backend init)
    so the SYNAPSEML_TPU_HIST_CHUNK env takes effect without re-importing
    the module."""
    return default_chunk()


class GrowerConfig(NamedTuple):
    """Static (compile-time) grower configuration."""

    num_leaves: int = 31
    num_bins: int = 255
    max_depth: int = -1          # <=0: unlimited (bounded by num_leaves anyway)
    lambda_l1: float = 0.0
    lambda_l2: float = 0.0
    min_data_in_leaf: int = 20
    min_sum_hessian_in_leaf: float = 1e-3
    min_gain_to_split: float = 0.0
    learning_rate: float = 0.1
    max_delta_step: float = 0.0
    cat_smooth: float = 10.0
    cat_l2: float = 10.0         # extra L2 applied to categorical split gains
    max_cat_threshold: int = 32
    max_cat_to_onehot: int = 4   # <= this many categories: one-vs-rest splits
    min_data_per_group: int = 100  # thin categorical groups excluded
    feature_fraction_bynode: float = 1.0  # per-NODE feature sampling
    has_categorical: bool = False  # static: traces out the categorical path
    # growth policy: "leafwise" (LightGBM-parity best-first; default) or
    # "depthwise" (level-batched opt-in — ~depth heavy steps per tree via
    # ONE multi-leaf histogram pass per level; trees differ from LightGBM's
    # leaf-wise order, quality gated in tests; grower_depthwise.py)
    growth_policy: str = "leafwise"
    # histogram allreduce wire precision ladder: "f32" (default), "bf16"
    # (2/3 wire bytes), or "int8" (blockwise-quantized allreduce — EQuARX,
    # arXiv:2506.17615 — ~2 bytes/elem effective incl. per-block scales).
    # grad/hess are ALREADY bf16-rounded before histogram accumulation
    # (ops/hist_kernel.py contract), so the lossy rungs only round the
    # SUMS once; the COUNT channel always rides an exact wire (it gates
    # min_data_in_leaf). The int8 result is dequantized ONCE to f32, so
    # the parent-minus-sibling histogram subtraction downstream never
    # compounds quantization error. Multi-host DCN is the payoff regime;
    # f32 by default for bit-parity.
    hist_allreduce_dtype: str = "f32"
    # cross-shard histogram reduction shape: "allreduce" (every device gets
    # the full (FP, B, 3) histogram — LightGBM data_parallel's logical
    # result) or "scatter" (owned-feature reduce-scatter: each of
    # ``feature_shards`` devices keeps only its FP/world slice and the
    # per-leaf best splits are exchanged as tiny (world, 5) candidate
    # rows — LightGBM data_parallel's ACTUAL wire pattern, ~halving
    # collective bytes). "scatter" requires leafwise growth +
    # numeric-only features + FP % feature_shards == 0.
    hist_reduce: str = "allreduce"
    feature_shards: int = 1      # static world size for hist_reduce="scatter"


def resolve_wire_dtype(cfg, mesh, n_rows, nfeat):
    """Resolve ``hist_allreduce_dtype='auto'`` to a concrete ladder rung.

    Routed through ``core.perfmodel``: the analytic prior prices each rung's
    per-tree collective seconds from the cached link-bandwidth probe, but —
    because the lossy rungs trade accuracy, not just time — only a *measured*
    match (recorded ``gbdt_wire_dtype`` rows for a log-nearby workload on
    this platform) may move the choice off the conservative f32 fallback.
    Explicit ``hist_allreduce_dtype="f32"|"bf16"|"int8"`` bypasses all of
    this (the caller never invokes the resolver). Returns
    ``(wire_dtype, perfmodel.Decision)``.
    """
    from ..core import perfmodel

    if mesh is None:
        return "f32", perfmodel.Decision(
            "gbdt_wire_dtype", "f32", "f32", None, 0.0, True, "f32",
            "fallback", [], {"workers": 1.0})
    workers = 1
    try:
        from ..parallel.mesh import DATA_AXIS as _DA
        workers = int(dict(mesh.shape).get(_DA, 1))
    except Exception:  # mesh without a data axis
        pass
    link = perfmodel.link_bandwidth(mesh) if workers > 1 else None
    return perfmodel.suggest_wire_dtype(
        n_rows=float(n_rows), nfeat=float(nfeat), workers=float(workers),
        max_bin=float(cfg.max_bin), num_leaves=float(cfg.num_leaves),
        link_bps=link)


class TreeArrays(NamedTuple):
    """One grown tree in structure-of-arrays form (serializes to the LightGBM
    model-string fields of the same names — gbdt/model_io.py)."""

    split_feature: jnp.ndarray   # (L-1,) i32
    split_bin: jnp.ndarray       # (L-1,) i32 — bin-space threshold (left if bin <= t)
    split_gain: jnp.ndarray      # (L-1,) f32
    split_type: jnp.ndarray      # (L-1,) i32 — 0 numeric, 1 categorical
    default_left: jnp.ndarray    # (L-1,) bool — learned NaN direction
    cat_bitset: jnp.ndarray      # (L-1, ceil(B/32)) u32 — membership → left
    left_child: jnp.ndarray      # (L-1,) i32 — >=0 internal node, ~leaf otherwise
    right_child: jnp.ndarray     # (L-1,) i32
    internal_value: jnp.ndarray  # (L-1,) f32 (shrunk output the node would emit)
    internal_count: jnp.ndarray  # (L-1,) i32
    leaf_value: jnp.ndarray      # (L,) f32 (shrinkage applied, LightGBM-style)
    leaf_weight: jnp.ndarray     # (L,) f32 (sum of hessians)
    leaf_count: jnp.ndarray      # (L,) i32
    num_splits: jnp.ndarray      # () i32


def _threshold_l1(g, l1):
    return jnp.sign(g) * jnp.maximum(jnp.abs(g) - l1, 0.0)


def _leaf_objective(g, h, l1, l2):
    """LightGBM GetLeafSplitGain: ThresholdL1(G)^2 / (H + l2)."""
    gt = _threshold_l1(g, l1)
    return gt * gt / (h + l2)


def _leaf_output(g, h, cfg: GrowerConfig):
    out = -_threshold_l1(g, cfg.lambda_l1) / (h + cfg.lambda_l2)
    if cfg.max_delta_step > 0:
        out = jnp.clip(out, -cfg.max_delta_step, cfg.max_delta_step)
    return out


def _bucket_sizes(np_rows: int) -> list:
    """Static power-of-two bucket sizes (multiples of _chunk()) covering any
    range length up to the padded row count."""
    sizes = []
    s = min(2 * _chunk(), np_rows)
    while s < np_rows:
        sizes.append(s)
        s *= 2
    sizes.append(np_rows)
    return sizes


def _witness_observe(site, tree, expect=None):
    # dtype-witness probe (testing/dtypewitness.py): inert unless the
    # witness module is loaded — sys.modules lookup keeps product imports
    # free of the testing package
    w = sys.modules.get("synapseml_tpu.testing.dtypewitness")
    if w is not None and w.active():
        w.observe(site, tree, expect)


def _maybe_psum(x, axis_name, wire_dtype: str = "f32"):
    """Cross-shard histogram allreduce; ``wire_dtype='bf16'`` ships the
    grad/hess channels at half width (their per-row values are bf16-rounded
    already — ops/hist_kernel.py contract) while the COUNT channel stays
    exact f32: shard count partials are exact integers feeding the
    min_data_in_leaf gates, and bf16 would round them to multiples of 512
    at realistic shard sizes. Net wire bytes: 2/3 of full width."""
    if axis_name is None:
        return x
    if wire_dtype == "bf16":
        gh = lax.psum(x[..., :2].astype(jnp.bfloat16),
                      axis_name).astype(x.dtype)
        # exact f32 totals side wire (1/B of the payload), same as the
        # int8 rung: leaf G/H totals and parent gain terms must not carry
        # bf16 rounding accumulated over the whole grid
        gh = _pin_totals(gh, lax.psum(x[..., :2].sum(axis=-2), axis_name))
        cnt = lax.psum(x[..., 2:], axis_name)
        # contract: pinned totals and the count channel leave on exact f32
        _witness_observe("gbdt.wire.hist", gh, expect="float32")
        _witness_observe("gbdt.wire.count", cnt, expect="float32")
        return jnp.concatenate([gh, cnt], axis=-1)
    if wire_dtype == "int8":
        from ..parallel.collectives import allreduce_sum_quantized

        # channel-major so quantization blocks never mix grad magnitudes
        # with hess magnitudes (per-block max-abs scales stay tight)
        gh = jnp.moveaxis(x[..., :2], -1, 0)
        gh = allreduce_sum_quantized(gh, axis_name).astype(x.dtype)
        gh = jnp.moveaxis(gh, 0, -1)
        gh = _pin_totals(gh, lax.psum(x[..., :2].sum(axis=-2), axis_name))
        cnt = lax.psum(x[..., 2:], axis_name)
        _witness_observe("gbdt.wire.hist", gh, expect="float32")
        _witness_observe("gbdt.wire.count", cnt, expect="float32")
        return jnp.concatenate([gh, cnt], axis=-1)
    return lax.psum(x, axis_name)


def _pin_totals(gh, tot):
    """Pin each feature-channel row of a quantized-wire histogram to its
    exactly-reduced total (a (..., FP, 2) f32 side wire, 1/B of the payload):
    the residual is redistributed across bins proportional to |bin|, so empty
    bins stay exactly zero and the leaf G/H totals the grower reads off the
    histogram (leaf values, parent terms of every gain) carry no quantization
    error — only WITHIN-leaf split placement sees the int8 grid."""
    absg = jnp.abs(gh)
    mass = absg.sum(axis=-2, keepdims=True)
    err = (tot - gh.sum(axis=-2))[..., None, :]
    return gh + err * absg / jnp.where(mass > 0, mass, 1.0)


def _hist_reduce_scatter(x, axis_name, wire_dtype: str = "f32"):
    """Owned-feature histogram reduction: (FP, B, 3) local partials →
    fully-summed (FP/world, B, 3) slice owned by this device (reduce-scatter
    over the leading feature axis — LightGBM data_parallel's actual wire
    pattern, ~half the bytes of a full allreduce). The caller slices every
    per-feature parameter at rank*FPo and exchanges tiny per-leaf best-split
    candidates to keep split decisions uniform across devices."""
    if axis_name is None:
        return x
    scatter = partial(lax.psum_scatter, axis_name=axis_name,
                      scatter_dimension=0, tiled=True)
    if wire_dtype == "bf16":
        gh = scatter(x[..., :2].astype(jnp.bfloat16)).astype(x.dtype)
        # pin owned-slice totals over an exact f32 side wire, mirroring
        # the int8 rung: totals feed leaf values and parent gain terms
        gh = _pin_totals(gh, scatter(x[..., :2].sum(axis=1)))
    elif wire_dtype == "int8":
        from ..parallel.collectives import reduce_scatter_sum_quantized

        B = x.shape[1]
        # (FP, 2, B): channel-major within each feature so quantization
        # blocks never mix grad magnitudes with hess magnitudes
        ghT = jnp.swapaxes(x[..., :2], 1, 2)
        ghT = reduce_scatter_sum_quantized(ghT, axis_name,
                                           block=math.gcd(256, B))
        gh = jnp.swapaxes(ghT, 1, 2).astype(x.dtype)
        gh = _pin_totals(gh, scatter(x[..., :2].sum(axis=1)))
    else:
        gh = scatter(x[..., :2])
    cnt = scatter(x[..., 2:])    # counts stay on an exact wire
    _witness_observe("gbdt.wire.scatter_hist", gh, expect="float32")
    _witness_observe("gbdt.wire.scatter_count", cnt, expect="float32")
    return jnp.concatenate([gh, cnt], axis=-1)


def _chunk_window(start, size: int, np_rows: int, chunk: int):
    """Chunk-aligned static window ``[cs, cs+S)`` covering any range
    ``[start, start+len)`` with ``len <= size``: ``S = min(size+chunk,
    np_rows)`` and ``cs`` rounded down to a chunk boundary, so every
    per-split slice and update is a tile-aligned DMA and the sliced
    histogram groups rows as the segmented kernel does (ops/hist_kernel.py
    ``_range_kernel`` uses this same first-chunk formula). Callers' routing
    keys / masks guard the rows outside [start, start+len)."""
    S = min(size + chunk, np_rows)
    cs0 = jnp.minimum(start, np_rows - S)
    return (cs0 // chunk) * chunk, S


def _partition_bucket(pos, gs, hs, ms, bT, start, length, fsel, route,
                      size: int, chunk: int, num_bins_padded: int,
                      use_kernel: bool):
    """One bucket of the split step: stably partition rows [start,
    start+length) of the sorted arrays by ``route`` (bin values of feature
    ``fsel`` -> goes right) inside the chunk-aligned window that covers any
    range of at most ``size`` rows. ``use_kernel``
    (``partition_kernel_available``: the TPU backend) moves them in one pass
    of ops/partition_kernel.py; elsewhere a stable ``argsort`` of the 4-way
    key and five gathers, the kernel's bit-for-bit reference. Returns the
    five updated arrays and the left child's row count."""
    FP, Np = bT.shape
    cs, S = _chunk_window(start, size, Np, chunk)
    idx = cs + jnp.arange(S, dtype=jnp.int32)
    binrow = lax.dynamic_slice(bT, (fsel, cs), (1, S))[0]
    gr = route(binrow)
    if use_kernel:
        # two classes give the 4-way key's order: rows before the range
        # already precede its left rows, rows past it already follow its
        # right rows
        past = idx >= start + length
        inside = (idx >= start) & ~past
        second = past | (inside & gr)
        nl_loc = jnp.sum(inside & ~gr, dtype=jnp.int32)
        put = lambda a, w: lax.dynamic_update_slice(
            a, w, (0,) * (a.ndim - 1) + (cs,))
        moved = partition_window(second, cs, bT, pos, gs, hs, ms,
                                 num_bins_padded, chunk)
        return tuple(map(put, (pos, gs, hs, ms, bT), moved)) + (nl_loc,)
    key = jnp.where(idx < start, -1,
                    jnp.where(idx >= start + length, 2,
                              gr.astype(jnp.int32)))
    src = jnp.argsort(key, stable=True).astype(jnp.int32)
    nl_loc = jnp.sum(key == 0).astype(jnp.int32)

    def perm1(a):
        sl = lax.dynamic_slice(a, (cs,), (S,))
        return lax.dynamic_update_slice(a, sl[src], (cs,))

    blk = lax.dynamic_slice(bT, (0, cs), (FP, S))
    bT2 = lax.dynamic_update_slice(bT, blk[:, src], (0, cs))
    return perm1(pos), perm1(gs), perm1(hs), perm1(ms), bT2, nl_loc


# ---------------------------------------------------------------------------
# Split finding over one leaf's histogram
# ---------------------------------------------------------------------------

def _best_for_leaf(hist, feature_active, is_categorical, monotone, nan_bins,
                   cfg: GrowerConfig, l1, l2, cat_nbins=None):
    """hist (FP, B, 3) → (gain, feat, bin, default_left, count_left, order).

    ``order`` is the categorical bin ordering (FP, B) used to rebuild the
    winning bitset (None when the config has no categorical features).
    """
    FP, B, _ = hist.shape
    totals = hist[0].sum(axis=0)                       # (3,) — feature 0 spans the leaf
    G, H, C = totals[0], totals[1], totals[2]
    parent_obj = _leaf_objective(G, H, l1, l2)

    def scan_gains(cum, extraG=0.0, extraH=0.0, extraC=0.0, l2_gain=None):
        l2g = l2 if l2_gain is None else l2_gain
        # the parent term uses the SAME regularization as the children
        # (LightGBM's categorical gain_shift also carries lambda_l2 + cat_l2)
        parent = (parent_obj if l2_gain is None
                  else _leaf_objective(G, H, l1, l2g))
        GL = cum[..., 0] + extraG
        HL = cum[..., 1] + extraH
        CL = cum[..., 2] + extraC
        GR, HR, CR = G - GL, H - HL, C - CL
        gain = (_leaf_objective(GL, HL, l1, l2g)
                + _leaf_objective(GR, HR, l1, l2g) - parent)
        valid = ((CL >= cfg.min_data_in_leaf) & (CR >= cfg.min_data_in_leaf)
                 & (HL >= cfg.min_sum_hessian_in_leaf)
                 & (HR >= cfg.min_sum_hessian_in_leaf))
        mc = monotone[:, None]
        vl = -GL / (HL + l2)
        vr = -GR / (HR + l2)
        mono_ok = jnp.where(mc == 0, True,
                            jnp.where(mc > 0, vl <= vr, vl >= vr))
        return jnp.where(valid & mono_ok, gain, -jnp.inf), CL

    cum = jnp.cumsum(hist, axis=1)                     # (FP, B, 3)
    # NaN-bin totals per feature (zero when the feature has no NaN bin)
    nb = jnp.clip(nan_bins, 0, B - 1)
    nan_tot = jnp.take_along_axis(hist, nb[:, None, None].repeat(3, axis=2),
                                  axis=1)[:, 0, :]     # (FP, 3)
    has_nan = (nan_bins < B)[:, None]
    nan_tot = jnp.where(has_nan, nan_tot, 0.0)

    # default-right: NaN bin sits at num_bins-1, so cum[t] for any divider
    # t < nan_bin excludes it naturally (thresholds at/after it yield CR=0 →
    # invalid); default-left adds the NaN totals to the left side.
    gain_r, CL_r = scan_gains(cum)
    gain_l, CL_l = scan_gains(cum, nan_tot[:, None, 0], nan_tot[:, None, 1],
                              nan_tot[:, None, 2])
    use_left = has_nan & (gain_l > gain_r)
    gain_num = jnp.where(use_left, gain_l, gain_r)
    CL_num = jnp.where(use_left, CL_l, CL_r)

    order = None
    if cfg.has_categorical:
        # thin groups (minDataPerGroup) never lead a split: pushed to the end
        # of the ordering and masked out of every candidate position
        order, n_usable = _cat_order_usable(hist, cfg)
        n_usable = n_usable[:, None]
        hist_sorted = jnp.take_along_axis(hist, order[..., None], axis=1)
        cum_cat = jnp.cumsum(hist_sorted, axis=1)
        # LightGBM applies an EXTRA L2 (cat_l2) to categorical split gains
        l2c = l2 + jnp.float32(cfg.cat_l2)
        gain_sorted, CL_sorted = scan_gains(cum_cat, l2_gain=l2c)
        # one-vs-rest (maxCatToOnehot): candidate = a SINGLE sorted category
        # left; scan_gains on the unsummed sorted histogram gives exactly
        # that. The mode is decided by the feature's STATIC category count
        # (LightGBM's use_onehot), not the per-leaf occupancy
        gain_one, CL_one = scan_gains(hist_sorted, l2_gain=l2c)
        kk = jnp.arange(B)[None, :]
        if cat_nbins is None:
            cat_nbins = jnp.full(hist.shape[0], B, jnp.int32)
        onehot = (cat_nbins <= cfg.max_cat_to_onehot)[:, None]
        gain_cat = jnp.where(onehot, gain_one, gain_sorted)
        CL_cat = jnp.where(onehot, CL_one, CL_sorted)
        # max_cat_threshold caps only the many-vs-many prefix size; one-hot
        # mode scans every usable category (LightGBM semantics)
        valid_k = jnp.where(onehot, kk < n_usable,
                            (kk < cfg.max_cat_threshold) & (kk < n_usable))
        gain_cat = jnp.where(valid_k, gain_cat, -jnp.inf)
        gain = jnp.where(is_categorical[:, None], gain_cat, gain_num)
        CLsel = jnp.where(is_categorical[:, None], CL_cat, CL_num)
        use_left = use_left & ~is_categorical[:, None]
    else:
        gain = gain_num
        CLsel = CL_num
    gain = jnp.where(feature_active[:, None], gain, -jnp.inf)

    flat = gain.reshape(FP * B)
    best = jnp.argmax(flat)
    best_gain = flat[best]
    bfeat = (best // B).astype(jnp.int32)
    bbin = (best % B).astype(jnp.int32)
    bdl = use_left.reshape(FP * B)[best]
    bcl = CLsel.reshape(FP * B)[best]
    return best_gain, bfeat, bbin, bdl, bcl, order


def _cat_order_usable(hist_b3, cfg: GrowerConfig):
    """Categorical ordering state from a (..., B, 3) histogram: (order over
    bins by grad/(hess+smooth) with thin groups last, usable count). ONE
    definition shared by the split search and the winning-bitset rebuild —
    they must agree bit for bit."""
    cnt = hist_b3[..., 2]
    usable = (cnt >= cfg.min_data_per_group) & (cnt > 0)
    key = jnp.where(usable,
                    hist_b3[..., 0] / (hist_b3[..., 1] + cfg.cat_smooth),
                    jnp.inf)
    order = jnp.argsort(key, axis=-1)
    return order, usable.sum(axis=-1)


def _node_mask_fn(cfg: GrowerConfig, featp, f: int, node_key):
    """feature_fraction_bynode sampler: node id -> (FP,) bool feature mask.

    LightGBM samples a fresh feature subset for every NODE's split search
    (feature_fraction_bynode, distinct from the per-tree feature_fraction);
    here each node id folds into the tree's key and keeps exactly
    ceil(frac * F) real features."""
    if cfg.feature_fraction_bynode >= 1.0:
        return lambda nid: featp
    if node_key is None:
        raise ValueError("feature_fraction_bynode < 1 requires node_key")
    FP = featp.shape[0]
    # LightGBM ColSampler::GetByNode: the per-node count is a fraction of the
    # CURRENTLY searchable set (the per-tree feature_fraction subset, or the
    # voting winners) — computed dynamically since that mask is traced
    keep = jnp.maximum(
        1, jnp.ceil(cfg.feature_fraction_bynode
                    * jnp.sum(featp).astype(jnp.float32))).astype(jnp.int32)
    base = jax.random.wrap_key_data(node_key)

    def mask(nid):
        u = jax.random.uniform(jax.random.fold_in(base, nid), (FP,))
        u = jnp.where(featp, u, jnp.inf)
        ranks = jnp.zeros(FP, jnp.int32).at[jnp.argsort(u)].set(
            jnp.arange(FP, dtype=jnp.int32))
        return featp & (ranks < keep)

    return mask


# ---------------------------------------------------------------------------
# Tree growth — helpers (shared with grower_depthwise.py and stream.py)
# ---------------------------------------------------------------------------

def _pad_grow_inputs(binned, grad, hess, in_bag, feature_active,
                     is_categorical, monotone, nan_bins, FP, Np):
    """Pad rows to Np (zero mass) / features to FP (inactive), transpose bins."""
    n, f = binned.shape
    in_bag = jnp.asarray(in_bag, jnp.float32)
    g0 = jnp.asarray(grad, jnp.float32) * in_bag
    h0 = jnp.asarray(hess, jnp.float32) * in_bag
    pad_r = Np - n
    bT0 = jnp.zeros((FP, Np), jnp.int32)
    bT0 = bT0.at[:f, :n].set(binned.astype(jnp.int32).T)
    gs0 = jnp.pad(g0, (0, pad_r))
    hs0 = jnp.pad(h0, (0, pad_r))
    ms0 = jnp.pad(in_bag, (0, pad_r))
    featp = jnp.zeros(FP, bool).at[:f].set(feature_active)
    catp = jnp.zeros(FP, bool).at[:f].set(is_categorical)
    monop = jnp.zeros(FP, jnp.int32).at[:f].set(monotone)
    nanp = jnp.full(FP, 0x7FFF, jnp.int32).at[:f].set(nan_bins)
    return bT0, gs0, hs0, ms0, featp, catp, monop, nanp


def _pad_cat_nbins(cat_nbins, f: int, FP: int, B: int):
    """(F,) per-feature category counts → (FP,) padded; None → B (the
    one-hot mode then never triggers, preserving legacy direct-call use)."""
    if cat_nbins is None:
        return jnp.full(FP, B, jnp.int32)
    return jnp.full(FP, B, jnp.int32).at[:f].set(
        jnp.asarray(cat_nbins, jnp.int32))


def _winning_cat_bitset(hist_parent, fsel, bsel, catp, cfg: GrowerConfig,
                        B: int, bw: int, cat_nbins=None):
    """(bitset, cat_split) of the chosen split, rebuilt from the hist cache
    (LightGBM's many-vs-many prefix re-derived from the sorted-bin order —
    the ordering/one-hot decisions share one implementation with the split
    search, _cat_order_usable)."""
    if not cfg.has_categorical:
        return jnp.zeros((bw,), jnp.uint32), jnp.zeros((), bool)
    histf = hist_parent[fsel]                          # (B, 3)
    order_f, _ = _cat_order_usable(histf, cfg)
    nb_f = (jnp.int32(B) if cat_nbins is None else cat_nbins[fsel])
    onehot = nb_f <= cfg.max_cat_to_onehot
    idx = jnp.arange(B)
    # one-vs-rest winners take ONLY the chosen sorted position left
    take = jnp.where(onehot, idx == bsel, idx <= bsel)
    bwords = (order_f >> 5).astype(jnp.int32)
    bvals = jnp.uint32(1) << (order_f & 31).astype(jnp.uint32)
    bitset = jnp.zeros((bw,), jnp.uint32).at[bwords].add(
        jnp.where(take, bvals, jnp.uint32(0)))
    return bitset, catp[fsel]


def _route_right(binrow, bsel, dl, nanbin_f, bitset, cat_split,
                 cfg: GrowerConfig, bw: int):
    """Per-row go-right decision of one split over bin values ``binrow``
    (numeric threshold, learned NaN direction, categorical bitset)."""
    gr = binrow > bsel
    gr = jnp.where(binrow == nanbin_f, ~dl, gr)
    if cfg.has_categorical:
        w = bitset[jnp.clip(binrow >> 5, 0, bw - 1)]
        member = ((w >> (binrow & 31).astype(jnp.uint32)) & 1).astype(bool)
        gr = jnp.where(cat_split, ~member, gr)
    return gr


def _init_split_state(L: int, B: int, bw: int, hist_root, rg, rf, rb, rdl,
                      rcl, FP: int):
    """Initial per-leaf split state + tree-structure arrays (the fields the
    leaf-wise, depth-wise and streamed growers' states share): root occupies
    leaf 0."""
    z1 = lambda dt, fill=0: jnp.full((max(L - 1, 1),), fill, dt)
    return dict(
        hist=jnp.zeros((L, FP, B, 3), jnp.float32).at[0].set(hist_root),
        bgain=jnp.full(L, -jnp.inf, jnp.float32).at[0].set(rg),
        bfeat=jnp.zeros(L, jnp.int32).at[0].set(rf),
        bbin=jnp.zeros(L, jnp.int32).at[0].set(rb),
        bdl=jnp.zeros(L, bool).at[0].set(rdl),
        bcl=jnp.zeros(L, jnp.float32).at[0].set(rcl),
        depth=jnp.zeros(L, jnp.int32),
        leaf_parent=jnp.full(L, -1, jnp.int32),
        leaf_is_right=jnp.zeros(L, bool),
        split_feature=z1(jnp.int32),
        split_bin=z1(jnp.int32, B - 1),
        split_gain=z1(jnp.float32),
        split_type=z1(jnp.int32),
        default_left=jnp.zeros((max(L - 1, 1),), bool),
        cat_bitset=jnp.zeros((max(L - 1, 1), bw), jnp.uint32),
        left_child=z1(jnp.int32, ~0),
        right_child=z1(jnp.int32, ~0),
        internal_value=z1(jnp.float32),
        internal_count=z1(jnp.int32),
        num_splits=jnp.zeros((), jnp.int32),
    )


def _select_split_leaf(s, cfg: GrowerConfig, L: int):
    """(leaf index, do-split flag) for this growth step."""
    active = jnp.arange(L) <= s.num_splits
    if cfg.max_depth > 0:
        active &= s.depth < cfg.max_depth
    masked_gain = jnp.where(active, s.bgain, -jnp.inf)
    l = jnp.argmax(masked_gain).astype(jnp.int32)
    return l, masked_gain[l] > cfg.min_gain_to_split


def _common_split_updates(s, cfg: GrowerConfig, l, fsel, bsel, gain_l, dl,
                          bitset, cat_split, hist_left, hist_right,
                          bg2, bf2, bb2, bdl2, bcl2, G_l, H_l, C_l):
    """``_replace`` kwargs for one split of leaf ``l``:
    hist cache, per-leaf best-split state, and tree-structure bookkeeping
    (leaf numbering per LightGBM Tree::Split — left keeps ``l``, right becomes
    ``num_splits + 1``, child pointers ``~leaf``)."""
    new_right = s.num_splits + 1
    i_node = s.num_splits
    parent_out = _leaf_output(G_l, H_l, cfg) * cfg.learning_rate
    p = s.leaf_parent[l]
    p_idx = jnp.maximum(p, 0)
    lc = s.left_child.at[p_idx].set(
        jnp.where((p >= 0) & ~s.leaf_is_right[l], i_node, s.left_child[p_idx]))
    rc = s.right_child.at[p_idx].set(
        jnp.where((p >= 0) & s.leaf_is_right[l], i_node, s.right_child[p_idx]))
    lc = lc.at[i_node].set(~l)
    rc = rc.at[i_node].set(~new_right)
    return dict(
        hist=s.hist.at[l].set(hist_left).at[new_right].set(hist_right),
        bgain=s.bgain.at[l].set(bg2[0]).at[new_right].set(bg2[1]),
        bfeat=s.bfeat.at[l].set(bf2[0]).at[new_right].set(bf2[1]),
        bbin=s.bbin.at[l].set(bb2[0]).at[new_right].set(bb2[1]),
        bdl=s.bdl.at[l].set(bdl2[0]).at[new_right].set(bdl2[1]),
        bcl=s.bcl.at[l].set(bcl2[0]).at[new_right].set(bcl2[1]),
        depth=s.depth.at[l].add(1).at[new_right].set(s.depth[l] + 1),
        leaf_parent=s.leaf_parent.at[l].set(i_node).at[new_right].set(i_node),
        leaf_is_right=s.leaf_is_right.at[l].set(False)
                                     .at[new_right].set(True),
        split_feature=s.split_feature.at[i_node].set(fsel),
        split_bin=s.split_bin.at[i_node].set(bsel),
        split_gain=s.split_gain.at[i_node].set(gain_l),
        split_type=s.split_type.at[i_node].set(cat_split.astype(jnp.int32)),
        default_left=s.default_left.at[i_node].set(dl),
        cat_bitset=s.cat_bitset.at[i_node].set(bitset),
        left_child=lc,
        right_child=rc,
        internal_value=s.internal_value.at[i_node].set(parent_out),
        internal_count=s.internal_count.at[i_node].set(C_l.astype(jnp.int32)),
        num_splits=s.num_splits + 1,
    )


def _node_of_row_from_ranges(s, L: int, Np: int, n: int) -> jnp.ndarray:
    """Per-row final leaf id in ORIGINAL row order, from the sorted layout's
    (pos, leaf_start, leaf_len): scatter leaf ids at range starts, fill
    forward via cumulative max of marker positions, then undo the sort with
    one scatter through ``pos``. Zero-length local ranges are excluded: they
    share a start position with their sibling and the scatter collision
    would mislabel the sibling's rows. (No Np*L position encoding — that
    would overflow int32 at HIGGS-scale Np.)"""
    exists = jnp.arange(L) <= s.num_splits
    own_rows = exists & (s.leaf_len > 0)
    markers = jnp.full(Np, -1, jnp.int32).at[
        jnp.where(own_rows, s.leaf_start, Np)].set(
            jnp.arange(L, dtype=jnp.int32), mode="drop")
    # lax.cummax, not lax.associative_scan(jnp.maximum, ...): the scan's
    # unrolled tree of slices took the TPU compiler 1,290 s at 3.5 M rows
    last_pos = lax.cummax(
        jnp.where(markers >= 0, jnp.arange(Np, dtype=jnp.int32), -1), axis=0)
    node_sorted = markers[jnp.maximum(last_pos, 0)]
    return jnp.zeros(Np, jnp.int32).at[s.pos].set(node_sorted)[:n]


def _finalize_tree(s, cfg: GrowerConfig, L: int) -> TreeArrays:
    """Leaf stats from the per-leaf histogram cache (per-leaf f32 accumulation
    — a global prefix-sum difference would catastrophically cancel for small
    leaves on large N; the cache is already psum'd across devices)."""
    leaf_tot = s.hist[:, 0].sum(axis=1)                  # (L, 3)
    sumG, sumH, sumC = leaf_tot[:, 0], leaf_tot[:, 1], leaf_tot[:, 2]
    leaf_value = _leaf_output(sumG, sumH, cfg) * cfg.learning_rate
    exists = jnp.arange(L) <= s.num_splits
    leaf_value = jnp.where(exists, leaf_value, 0.0)
    return TreeArrays(
        split_feature=s.split_feature,
        split_bin=s.split_bin,
        split_gain=s.split_gain,
        split_type=s.split_type,
        default_left=s.default_left,
        cat_bitset=s.cat_bitset,
        left_child=s.left_child,
        right_child=s.right_child,
        internal_value=s.internal_value,
        internal_count=s.internal_count,
        leaf_value=leaf_value,
        leaf_weight=sumH,
        leaf_count=sumC.astype(jnp.int32),
        num_splits=s.num_splits,
    )


class _GrowState(NamedTuple):
    pos: jnp.ndarray             # (Np,) i32: sorted position -> original row
    gs: jnp.ndarray              # (Np,) f32 grad, sorted
    hs: jnp.ndarray              # (Np,) f32 hess, sorted
    ms: jnp.ndarray              # (Np,) f32 in-bag mask, sorted
    bT: jnp.ndarray              # (FP, Np) i32 bins, sorted
    leaf_start: jnp.ndarray      # (L,) i32
    leaf_len: jnp.ndarray        # (L,) i32
    hist: jnp.ndarray            # (L, FP, B, 3) f32 cache
    bgain: jnp.ndarray           # (L,) f32 best gain per leaf
    bfeat: jnp.ndarray           # (L,) i32
    bbin: jnp.ndarray            # (L,) i32
    bdl: jnp.ndarray             # (L,) bool
    bcl: jnp.ndarray             # (L,) f32 global count-left of best split
    depth: jnp.ndarray           # (L,) i32
    leaf_parent: jnp.ndarray     # (L,) i32
    leaf_is_right: jnp.ndarray   # (L,) bool
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


def _grow_tree_impl(binned, grad, hess, in_bag, feature_active, is_categorical,
                    monotone, nan_bins, cfg: GrowerConfig,
                    axis_name: Optional[str], node_key=None, cat_nbins=None):
    n, f = binned.shape
    L = cfg.num_leaves
    B = pad_bins(cfg.num_bins)
    FP = features_padded(f)
    # owned-feature mode: each of W devices keeps only FP/W features of the
    # reduced histogram; split decisions are re-unified by a tiny per-leaf
    # candidate exchange (validated + gated in grow_tree/boosting)
    scatter_mode = (cfg.hist_reduce == "scatter" and cfg.feature_shards > 1
                    and axis_name is not None)
    W = cfg.feature_shards if scatter_mode else 1
    if scatter_mode and FP % W:
        raise ValueError(f"hist_reduce='scatter' needs features_padded({f})="
                         f"{FP} divisible by feature_shards={W}")
    FPo = FP // W
    chunk = _chunk()     # resolved ONCE per trace: within-trace consistency
    Np = -(-n // chunk) * chunk
    bw = (B + BITS - 1) // BITS
    l1 = jnp.float32(cfg.lambda_l1)
    l2 = jnp.float32(cfg.lambda_l2)
    sizes = _bucket_sizes(Np)
    sizes_arr = jnp.asarray(sizes, jnp.int32)

    bT0, gs0, hs0, ms0, featp, catp, monop, nanp = _pad_grow_inputs(
        binned, grad, hess, in_bag, feature_active, is_categorical, monotone,
        nan_bins, FP, Np)

    use_seg = segmented_histograms_available(B)

    def build_hist(bT, gs, hs, ms, child_start, child_len):
        """Histogram of sorted rows [child_start, child_start+child_len) via
        the bucketed kernel; psum across the data axis if present. On TPU
        the segmented kernel selects its blocks from the FULL arrays by
        scalar-prefetched offsets — no dynamic_slice copy, no mask multiply."""
        if use_seg:
            # branch i covers lengths <= sizes[i] with ONE extra chunk for
            # window alignment (S = sizes[i] + chunk >= length + chunk) —
            # not the next power of two, which could double the kernel work
            def make_branch(size):
                seg = min(size + chunk, Np)

                def br(args):
                    bT_, gs_, hs_, ms_, cstart, clen = args
                    return range_histogram(bT_, gs_, hs_, ms_, cstart, clen,
                                           B, seg)
                return br
        else:
            def make_branch(size):
                def br(args):
                    bT_, gs_, hs_, ms_, cstart, clen = args
                    cs, S = _chunk_window(cstart, size, Np, chunk)
                    idx = cs + jnp.arange(S, dtype=jnp.int32)
                    mask = ((idx >= cstart)
                            & (idx < cstart + clen)).astype(jnp.float32)
                    gsl = lax.dynamic_slice(gs_, (cs,), (S,)) * mask
                    hsl = lax.dynamic_slice(hs_, (cs,), (S,)) * mask
                    msl = lax.dynamic_slice(ms_, (cs,), (S,)) * mask
                    bsl = lax.dynamic_slice(bT_, (0, cs), (FP, S))
                    return child_histogram(bsl, gsl, hsl, msl, B)
                return br

        bidx = jnp.searchsorted(sizes_arr, child_len, side="left")
        hist = lax.switch(jnp.minimum(bidx, len(sizes) - 1),
                          [make_branch(s) for s in sizes],
                          (bT, gs, hs, ms, child_start, child_len))
        if scatter_mode:
            return _hist_reduce_scatter(hist, axis_name,
                                        cfg.hist_allreduce_dtype)
        return _maybe_psum(hist, axis_name, cfg.hist_allreduce_dtype)

    nmask = _node_mask_fn(cfg, featp, f, node_key)
    catb = _pad_cat_nbins(cat_nbins, f, FP, B)

    if scatter_mode:
        off = lax.axis_index(axis_name).astype(jnp.int32) * FPo
        slice_o = lambda a: lax.dynamic_slice_in_dim(a, off, FPo)
        catp_o, monop_o = slice_o(catp), slice_o(monop)
        nanp_o, catb_o = slice_o(nanp), slice_o(catb)

        def best_of(hist_leaf, fmask):
            # fmask arrives as the full (FP,) node mask; score only the
            # owned slice — the exchange below restores the global argmax
            return _best_for_leaf(hist_leaf, slice_o(fmask), catp_o, monop_o,
                                  nanp_o, cfg, l1, l2, catb_o)

        def exchange_best(g, f_loc, b, dl, cl):
            """All-gather each shard's best owned candidate (5 floats per
            leaf) and take the global winner — every device ends up with the
            SAME (gain, global feature, bin, default_left, left_count), so
            leaf selection and partitioning stay uniform across the mesh."""
            vec = jnp.stack([g, (off + f_loc).astype(jnp.float32),
                             b.astype(jnp.float32), dl.astype(jnp.float32),
                             cl], axis=-1)                    # (..., 5)
            allv = lax.all_gather(vec, axis_name)             # (W, ..., 5)
            win = jnp.argmax(allv[..., 0], axis=0)            # low rank wins ties
            bv = jnp.take_along_axis(
                allv, win[None, ..., None], axis=0)[0]
            return (bv[..., 0], bv[..., 1].astype(jnp.int32),
                    bv[..., 2].astype(jnp.int32), bv[..., 3] > 0.5,
                    bv[..., 4])
    else:
        def best_of(hist_leaf, fmask):
            return _best_for_leaf(hist_leaf, fmask, catp, monop, nanp, cfg,
                                  l1, l2, catb)

        exchange_best = lambda *c: c

    # ---- root ------------------------------------------------------------
    hist_root = build_hist(bT0, gs0, hs0, ms0, jnp.int32(0), jnp.int32(Np))
    rg, rf, rb, rdl, rcl = exchange_best(
        *best_of(hist_root, nmask(jnp.int32(2 * (L - 1))))[:5])

    init = _GrowState(
        pos=jnp.arange(Np, dtype=jnp.int32),
        gs=gs0, hs=hs0, ms=ms0, bT=bT0,
        leaf_start=jnp.zeros(L, jnp.int32),
        leaf_len=jnp.zeros(L, jnp.int32).at[0].set(Np),
        **_init_split_state(L, B, bw, hist_root, rg, rf, rb, rdl, rcl, FPo),
    )

    use_kernel = partition_kernel_available(B, FP)

    def partition(pos, gs, hs, ms, bT, start, length, fsel, bsel, dl, bitset,
                  cat_split, nanbin_f):
        """Stably partition the leaf's range by the split; returns updated
        sorted arrays and the LOCAL left-child row count."""
        route = lambda binrow: _route_right(binrow, bsel, dl, nanbin_f,
                                            bitset, cat_split, cfg, bw)

        def make_branch(size):
            return lambda args: _partition_bucket(
                *args, start, length, fsel, route, size, chunk, B,
                use_kernel)

        bidx = jnp.searchsorted(sizes_arr, length, side="left")
        return lax.switch(jnp.minimum(bidx, len(sizes) - 1),
                          [make_branch(s) for s in sizes],
                          (pos, gs, hs, ms, bT))

    def body(i, s: _GrowState):
        l, do = _select_split_leaf(s, cfg, L)

        def step(s: _GrowState) -> _GrowState:
            gain_l, fsel, bsel, dl = s.bgain[l], s.bfeat[l], s.bbin[l], s.bdl[l]
            start = s.leaf_start[l]
            length = s.leaf_len[l]
            hist_parent = s.hist[l]                     # (FP, B, 3)
            totals = hist_parent[0].sum(axis=0)
            G_l, H_l, C_l = totals[0], totals[1], totals[2]
            bitset, cat_split = _winning_cat_bitset(hist_parent, fsel, bsel,
                                                    catp, cfg, B, bw, catb)

            pos2, gs2, hs2, ms2, bT2, nl_loc = partition(
                s.pos, s.gs, s.hs, s.ms, s.bT, start, length, fsel, bsel, dl,
                bitset, cat_split, nanp[fsel])

            # global child counts decide which side is built (uniform across
            # devices — bcl comes from the summed histogram)
            cl_glob = s.bcl[l]
            left_small = cl_glob * 2.0 <= C_l
            child_start = jnp.where(left_small, start, start + nl_loc)
            child_len = jnp.where(left_small, nl_loc, length - nl_loc)
            hist_small = build_hist(bT2, gs2, hs2, ms2, child_start, child_len)
            hist_left = jnp.where(left_small, hist_small,
                                  hist_parent - hist_small)
            hist_right = hist_parent - hist_left

            # re-evaluate best splits for the two children
            i_node_id = s.num_splits
            masks2 = jnp.stack([nmask(i_node_id * 2),
                                nmask(i_node_id * 2 + 1)])
            bg2, bf2, bb2, bdl2, bcl2, _ = jax.vmap(best_of)(
                jnp.stack([hist_left, hist_right]), masks2)
            bg2, bf2, bb2, bdl2, bcl2 = exchange_best(bg2, bf2, bb2, bdl2,
                                                      bcl2)

            new_right = s.num_splits + 1                # leaf id of right child
            return s._replace(
                pos=pos2, gs=gs2, hs=hs2, ms=ms2, bT=bT2,
                leaf_start=s.leaf_start.at[l].set(start)
                                       .at[new_right].set(start + nl_loc),
                leaf_len=s.leaf_len.at[l].set(nl_loc)
                                    .at[new_right].set(length - nl_loc),
                **_common_split_updates(s, cfg, l, fsel, bsel, gain_l, dl,
                                        bitset, cat_split, hist_left,
                                        hist_right, bg2, bf2, bb2, bdl2, bcl2,
                                        G_l, H_l, C_l),
            )

        return lax.cond(do, step, lambda s: s, s)

    s = lax.fori_loop(0, L - 1, body, init) if L > 1 else init
    return _finalize_tree(s, cfg, L), _node_of_row_from_ranges(s, L, Np, n)


@partial(jax.jit, static_argnames=("cfg", "axis_name"))
def grow_tree(
    binned: jnp.ndarray,         # (N, F) uint8/uint16 bin ids
    grad: jnp.ndarray,           # (N,) f32 — pre-weighted (instance weight / GOSS amp)
    hess: jnp.ndarray,           # (N,) f32
    in_bag: jnp.ndarray,         # (N,) f32 — 1 participating, 0 bagged-out/padding
    feature_active: jnp.ndarray, # (F,) bool — feature_fraction mask
    is_categorical: jnp.ndarray, # (F,) bool
    monotone: jnp.ndarray,       # (F,) i32 in {-1, 0, +1}
    cfg: GrowerConfig,
    nan_bins: Optional[jnp.ndarray] = None,  # (F,) i32 NaN bin per feature
    axis_name: Optional[str] = None,         # shard_map data axis for psum
    node_key=None,                           # raw key data (feature_fraction_bynode)
    cat_nbins=None,                          # (F,) static per-feature category counts
) -> tuple:
    """Grow one tree; returns (TreeArrays, node_of_row) where node_of_row is
    each row's final leaf index (used for the O(1) training-score update)."""
    n, f = binned.shape
    if nan_bins is None:
        nan_bins = jnp.full(f, 0x7FFF, jnp.int32)
    if cfg.hist_reduce not in ("allreduce", "scatter"):
        raise ValueError("hist_reduce must be 'allreduce' or 'scatter', "
                         f"got {cfg.hist_reduce!r}")
    if cfg.hist_reduce == "scatter" and cfg.feature_shards > 1:
        if cfg.growth_policy != "leafwise":
            raise ValueError(
                "hist_reduce='scatter' (feature-parallel) supports only "
                "leafwise growth")
        if cfg.has_categorical:
            raise ValueError("hist_reduce='scatter' does not support "
                             "categorical features (the winning split's "
                             "bitset needs the owner's histogram slice)")
        if axis_name is None:
            raise ValueError("hist_reduce='scatter' requires a mesh axis")
    if cfg.growth_policy == "depthwise":
        from .grower_depthwise import _grow_tree_impl_depthwise

        return _grow_tree_impl_depthwise(binned, grad, hess, in_bag,
                                         feature_active, is_categorical,
                                         monotone, nan_bins, cfg, axis_name,
                                         node_key, cat_nbins)
    if cfg.growth_policy != "leafwise":
        raise ValueError("growth_policy must be 'leafwise' or 'depthwise', "
                         f"got {cfg.growth_policy!r}")
    return _grow_tree_impl(binned, grad, hess, in_bag, feature_active,
                           is_categorical, monotone, nan_bins, cfg, axis_name,
                           node_key, cat_nbins)


def split_counter(cfg: GrowerConfig, nfeat: int) -> Optional[str]:
    """Name of the ``trainingMeasures`` counter for the splits of trees grown
    under ``cfg``: which path moves a leaf's rows — the partition kernel or
    ``argsort`` and five gathers. None for depth-wise growth, which has no
    leaf ranges to partition."""
    if cfg.growth_policy != "leafwise":
        return None
    use_kernel = partition_kernel_available(pad_bins(cfg.num_bins),
                                            features_padded(nfeat))
    return "splitsPartitionKernel" if use_kernel else "splitsPartitionSort"


# ---------------------------------------------------------------------------
# Stacked-forest prediction
# ---------------------------------------------------------------------------

class Forest(NamedTuple):
    """All trees stacked on a leading tree axis; ``threshold`` is in raw feature
    space (bin upper bounds), ``split_bin`` in bin space (for binned traversal).
    Inference is a ``lax.scan`` over trees of a vectorized pointer-chase, batched
    over rows — the reference instead does row-at-a-time JNI predict
    (LightGBMBooster.scala:520-560), which SURVEY §3.2 flags as unbatched."""

    split_feature: jnp.ndarray   # (T, L-1)
    threshold: jnp.ndarray       # (T, L-1) f32
    split_bin: jnp.ndarray       # (T, L-1) i32
    split_type: jnp.ndarray      # (T, L-1) i32
    default_left: jnp.ndarray    # (T, L-1) bool
    cat_bitset: jnp.ndarray      # (T, L-1, BW) u32
    left_child: jnp.ndarray      # (T, L-1)
    right_child: jnp.ndarray     # (T, L-1)
    leaf_value: jnp.ndarray      # (T, L)
    # per-split missing handling (LightGBM decision_type bits 2-3):
    # 0 none, 1 zero (|x|<=1e-35 routes default), 2 nan. Raw-value traversal
    # only; binned traversal routes via nan_bins.
    missing_type: jnp.ndarray = None  # (T, L-1) i32

    @property
    def num_trees(self) -> int:
        return self.split_feature.shape[0]

    @property
    def num_leaves(self) -> int:
        return self.leaf_value.shape[1]


def _descend(X, sf, thr, sbin, stype, dleft, bits, lc, rc, binned: bool,
             depth: int, nan_bins=None, mtypes=None):
    """Vectorized pointer-chase for one tree; returns leaf index per row."""
    n = X.shape[0]
    node = jnp.zeros((n,), jnp.int32)

    def step(_, node):
        nd = jnp.maximum(node, 0)
        f = sf[nd]
        x = jnp.take_along_axis(X, f[:, None].astype(jnp.int32), axis=1)[:, 0]
        dl = dleft[nd]
        if binned:
            xb = x.astype(jnp.int32)
            num_right = xb > sbin[nd]
            if nan_bins is not None:
                is_missing = xb == nan_bins[f.astype(jnp.int32)]
                num_right = jnp.where(is_missing, ~dl, num_right)
            c = xb
        else:
            # LightGBM Tree::NumericalDecision: NaN coerces to 0.0 unless
            # missing_type is nan; zero missing routes |x| <= 1e-35 to the
            # default side (kZeroThreshold)
            t = thr[nd]
            isnan_x = jnp.isnan(x)
            if mtypes is None:
                is_missing = isnan_x
                x0 = x
            else:
                mt = mtypes[nd]
                x0 = jnp.where(isnan_x & (mt != 2), 0.0, x)
                is_missing = jnp.where(mt == 1, jnp.abs(x0) <= 1e-35,
                                       (mt == 2) & isnan_x)
            num_right = jnp.where(is_missing, ~dl, ~(x0 <= t))
            # categorical NaN: member test on category 0 unless missing_type
            # is nan, where NaN is never a member (LightGBM
            # Tree::CategoricalDecision coerces int_fval to 0 for non-nan
            # missing types)
            if mtypes is None:
                cat_nan = -1.0
            else:
                cat_nan = jnp.where(mtypes[nd] == 2, -1.0, 0.0)
            c = jnp.clip(jnp.where(isnan_x, cat_nan, x), -1,
                         bits.shape[1] * BITS - 1).astype(jnp.int32)
        cw = jnp.maximum(c, 0)
        word = bits[nd, cw >> 5]
        member = ((word >> (cw & 31).astype(jnp.uint32)) & 1).astype(bool) & (c >= 0)
        is_cat = stype[nd] == 1
        go_right = jnp.where(is_cat, ~member, num_right)
        nxt = jnp.where(go_right, rc[nd], lc[nd])
        return jnp.where(node < 0, node, nxt)

    node = jax.lax.fori_loop(0, depth, step, node)
    return ~node  # leaf index


@partial(jax.jit, static_argnames=("binned", "output", "depth"))
def forest_predict(forest: Forest, X: jnp.ndarray, binned: bool = False,
                   output: str = "sum", nan_bins=None,
                   depth: Optional[int] = None) -> jnp.ndarray:
    """Sum of tree outputs (raw score) per row. ``output='leaf'`` returns the
    (N, T) leaf indices (predictLeaf parity — LightGBMBooster.scala:408-419);
    ``output='per_tree'`` returns (N, T) leaf values (for DART drop handling).
    ``nan_bins`` (F,) routes missing-bin values by each split's default_left
    when traversing binned data. ``depth`` bounds the pointer-chase steps —
    pass the forest's true max depth (see ``forest_max_depth``) to skip the
    dead iterations of the worst-case ``num_leaves - 1`` walk."""
    X = jnp.asarray(X, jnp.float32 if not binned else X.dtype)
    L = forest.leaf_value.shape[1]
    depth = max(depth if depth is not None else L - 1, 1)

    mts = forest.missing_type

    def unpack(t):
        if mts is None:
            return t + (None,)
        return t

    xs = (forest.split_feature, forest.threshold, forest.split_bin,
          forest.split_type, forest.default_left, forest.cat_bitset,
          forest.left_child, forest.right_child, forest.leaf_value)
    if mts is not None:
        xs = xs + (mts,)

    if output == "sum":
        # accumulate in the scan CARRY: the stacked (T, N) per-tree buffer
        # is ~4 GB at 11M rows x 100 trees and plain scoring never needs it
        def one_tree_sum(carry, t):
            sf, thr, sbin, stype, dl, bits, lc, rc, lv, mt = unpack(t)
            leaf = _descend(X, sf, thr, sbin, stype, dl, bits, lc, rc,
                            binned, depth, nan_bins, mt)
            return carry + lv[leaf], None

        total, _ = jax.lax.scan(
            one_tree_sum, jnp.zeros(X.shape[0], forest.leaf_value.dtype), xs)
        return total                 # (N,)

    def one_tree(carry, t):
        sf, thr, sbin, stype, dl, bits, lc, rc, lv, mt = unpack(t)
        leaf = _descend(X, sf, thr, sbin, stype, dl, bits, lc, rc, binned,
                        depth, nan_bins, mt)
        val = lv[leaf]
        return carry, (leaf, val)

    _, (leaves, vals) = jax.lax.scan(one_tree, 0, xs)
    if output == "leaf":
        return leaves.T          # (N, T)
    return vals.T                # (N, T)  ("per_tree")


def forest_max_depth(trees: list) -> int:
    """Max internal-node depth across trees (host-side): the exact number of
    pointer-chase steps any row needs. Children are created after their
    parent, so a single forward pass suffices."""
    maxd = 1
    for t in trees:
        ns = int(t.num_splits)
        if ns <= 0:
            continue
        lc = np.asarray(t.left_child)[:ns]
        rc = np.asarray(t.right_child)[:ns]
        # BFS from the root: exact for ANY node ordering (loaded third-party
        # model strings need not create children after parents)
        depth = np.ones(ns, np.int64)
        stack = [0]
        while stack:
            i = stack.pop()
            for c in (lc[i], rc[i]):
                if 0 <= c < ns:
                    depth[c] = depth[i] + 1
                    stack.append(int(c))
        maxd = max(maxd, int(depth.max()))
    return maxd


def stack_trees(trees: list, thresholds: list,
                missing_types: Optional[list] = None) -> Forest:
    """Host-side: stack per-tree TreeArrays (+ real-valued thresholds resolved
    from the BinMapper) into a Forest. ``missing_types`` is a per-tree list of
    (L-1,) arrays of LightGBM missing-type codes (0 none / 1 zero / 2 nan)."""
    def cat(field):
        return jnp.stack([np.asarray(getattr(t, field)) for t in trees])

    return Forest(
        split_feature=cat("split_feature"),
        threshold=jnp.stack([np.asarray(t, np.float32) for t in thresholds]),
        split_bin=cat("split_bin"),
        split_type=cat("split_type"),
        default_left=cat("default_left"),
        cat_bitset=cat("cat_bitset"),
        left_child=cat("left_child"),
        right_child=cat("right_child"),
        leaf_value=cat("leaf_value"),
        missing_type=(None if missing_types is None else jnp.stack(
            [np.asarray(m, np.int32) for m in missing_types])),
    )
