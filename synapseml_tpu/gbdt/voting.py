"""Voting-parallel feature selection (PV-Tree).

Reference: LightGBM's ``voting_parallel`` tree learner, surfaced through
``parallelism``/``topK`` (lightgbm/.../params/LightGBMParams.scala:25-27,
LightGBMConstants.scala:22-24 DefaultTopK=20, LightGBMBase.scala:252). In
data-parallel mode every split synchronizes histograms for ALL features;
voting-parallel cuts that to O(top_k): each worker votes its local top-k
features by split gain, the global top-2k by votes (gain-sum tie-break) are
selected, and only those features' histograms are aggregated.

TPU adaptation: selection runs once per tree at the root (one shard_map with a
``psum`` of per-feature gains + votes — cheap, (F,)-sized); the tree then grows
on the SLICED (N, 2k) bin matrix, so every per-leaf histogram allreduce inside
the growth loop moves 2k features instead of F. Split feature indices are
remapped to the full feature space afterwards.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..core.compat import shard_map
from ..parallel.mesh import DATA_AXIS

# per-shard row budget for the root-selection pass: the PV-Tree vote is a
# rank statistic over 2k-of-F features, robust under row subsampling, and an
# unsampled selection pass at large shards costs a visible fraction of the
# tree it elects features for (r05: 11 s/tree eager+unsampled). Strided
# sampling (not a prefix — label-sorted inputs stay representative) with
# contributions scaled back by the stride keeps G/H/count magnitudes
# unbiased for the min_data validity filter.
DEFAULT_SELECTION_SAMPLE_ROWS = 4096


def _per_feature_root_gain(binned, g, h, in_bag, num_bins: int,
                           lambda_l2: float, min_data: int):
    """(F,) best numeric-split gain per feature over the root node, from this
    shard's rows only. Counts use ``in_bag`` so padding/bagged-out rows do not
    inflate the min_data validity filter."""
    n, f = binned.shape
    # histogram per feature: scatter (grad, hess, in_bag) into (F*B, 3)
    flat = binned.astype(jnp.int32) + jnp.arange(f)[None, :] * num_bins
    contrib = jnp.stack([g, h, in_bag], axis=1)              # (N, 3)
    tot = jnp.zeros((f * num_bins, 3), jnp.float32)
    tot = tot.at[flat].add(contrib[:, None, :])              # (N,F) idx rows
    hist = tot.reshape(f, num_bins, 3)
    cum = jnp.cumsum(hist, axis=1)                          # (F, B, 3)
    G, H = cum[:, -1, 0:1], cum[:, -1, 1:2]
    GL, HL, CL = cum[..., 0], cum[..., 1], cum[..., 2]
    GR, HR, CR = G - GL, H - HL, cum[:, -1, 2:3] - CL
    lam = jnp.float32(lambda_l2)
    gain = (GL ** 2 / (HL + lam) + GR ** 2 / (HR + lam)
            - G ** 2 / (H + lam))
    valid = (CL >= min_data) & (CR >= min_data)
    return jnp.max(jnp.where(valid, gain, -jnp.inf), axis=1)  # (F,)


#: compiled selection programs keyed by (mesh, shapes, knobs) — the r05 A/B
#: measured the EAGER per-call shard_map rebuild at ~11 s/tree; the cached
#: jit brings steady-state selection to one device dispatch per tree.
_SELECT_CACHE: dict = {}
_SELECT_CACHE_MAX = 16


def _select_fn(mesh, n: int, f: int, k: int, out_k: int, num_bins: int,
               lambda_l2: float, min_data: int, stride: int):
    key = (mesh, n, f, k, out_k, num_bins, float(lambda_l2), int(min_data),
           stride)
    fn = _SELECT_CACHE.get(key)
    if fn is not None:
        return fn

    def _select(b_shard, g_shard, h_shard, bag_shard, act):
        if stride > 1:
            # strided per-shard subsample (static shapes, no collectives);
            # scaling contributions by the stride keeps G/H/counts unbiased
            b_shard, g_shard = b_shard[::stride], g_shard[::stride]
            h_shard, bag_shard = h_shard[::stride], bag_shard[::stride]
            g_shard = g_shard * float(stride)
            h_shard = h_shard * float(stride)
            bag_shard = bag_shard * float(stride)
        local_gain = _per_feature_root_gain(b_shard, g_shard, h_shard,
                                            bag_shard, num_bins, lambda_l2,
                                            min_data)
        local_gain = jnp.where(act, local_gain, -jnp.inf)
        # local top-k vote (PV-Tree step 1)
        _, top_idx = jax.lax.top_k(local_gain, k)
        votes = jnp.zeros((f,), jnp.float32).at[top_idx].add(1.0)
        votes = jax.lax.psum(votes, DATA_AXIS)
        gain_sum = jax.lax.psum(jnp.where(jnp.isfinite(local_gain),
                                          local_gain, 0.0), DATA_AXIS)
        # global selection: votes dominate, gain-sum breaks ties (step 2)
        norm_gain = gain_sum / (jnp.max(jnp.abs(gain_sum)) + 1e-12)
        score = votes * 2.0 + norm_gain
        score = jnp.where(act, score, -jnp.inf)
        _, sel = jax.lax.top_k(score, out_k)
        return jnp.sort(sel)

    fn = jax.jit(shard_map(
        _select, mesh=mesh,
        in_specs=(P(DATA_AXIS, None), P(DATA_AXIS), P(DATA_AXIS),
                  P(DATA_AXIS), P()),
        out_specs=P(), check_vma=False))
    if len(_SELECT_CACHE) >= _SELECT_CACHE_MAX:
        _SELECT_CACHE.pop(next(iter(_SELECT_CACHE)))
    _SELECT_CACHE[key] = fn
    return fn


def _selection_stride(n: int, mesh, sample_rows) -> int:
    """Static per-shard subsample stride for the selection pass."""
    if sample_rows is None:
        sample_rows = DEFAULT_SELECTION_SAMPLE_ROWS
    if sample_rows <= 0:
        return 1
    shard_rows = max(n // int(dict(mesh.shape).get(DATA_AXIS, 1)), 1)
    return max(-(-shard_rows // int(sample_rows)), 1)


def voting_select(binned, g, h, in_bag, mesh, top_k: int, num_bins: int,
                  lambda_l2: float = 0.0, min_data: int = 1,
                  feature_active=None, sample_rows=None) -> np.ndarray:
    """Global top-2k feature indices by per-shard votes (gain-sum tie-break).
    Returns a sorted int array of 2k (or fewer) feature indices, replicated.
    ``feature_active`` (F,) bool restricts voting to the feature_fraction
    sample so selection never wastes slots on masked-out features.
    ``sample_rows`` caps the per-shard rows the vote scans (default
    DEFAULT_SELECTION_SAMPLE_ROWS; <=0 disables sampling)."""
    n, f = binned.shape
    k = min(top_k, f)
    out_k = min(2 * k, f)
    active = (jnp.ones((f,), bool) if feature_active is None
              else jnp.asarray(feature_active))
    stride = _selection_stride(n, mesh, sample_rows)
    fn = _select_fn(mesh, n, f, k, out_k, num_bins, lambda_l2, min_data,
                    stride)
    return np.asarray(fn(binned, g, h, in_bag, active))


def time_selection(binned, mesh, top_k: int, num_bins: int,
                   lambda_l2: float = 0.0, min_data: int = 1,
                   sample_rows=None) -> tuple:
    """Measured (seconds_per_selection, fraction_of_shard_rows_scanned) of
    the jitted selection pass on this dataset — synthetic unit gradients,
    compile excluded (the compiled program lands in _SELECT_CACHE, so the
    training loop reuses it). Feeds ``route_parallelism``'s measured
    ``selection_s_per_tree``."""
    import time

    n, _ = binned.shape
    ones = jnp.ones((n,), jnp.float32)
    jax.block_until_ready(
        voting_select(binned, ones, ones, ones, mesh, top_k, num_bins,
                      lambda_l2, min_data, sample_rows=sample_rows))
    t0 = time.perf_counter()
    jax.block_until_ready(
        voting_select(binned, ones, ones, ones, mesh, top_k, num_bins,
                      lambda_l2, min_data, sample_rows=sample_rows))
    dt = time.perf_counter() - t0
    return dt, 1.0 / _selection_stride(n, mesh, sample_rows)


def remap_tree_features(tree, sel_idx: np.ndarray):
    """Split features of a tree grown on sliced columns → full feature space."""
    sel = jnp.asarray(sel_idx, jnp.int32)
    return tree._replace(split_feature=sel[tree.split_feature])


# ---------------------------------------------------------------------------
# Collective cost model — when does voting-parallel actually pay?
# ---------------------------------------------------------------------------
#
# The A/B on a single-host mesh (docs/measurements.json
# gbdt_voting_vs_data_parallel_speedup) shows voting as a pure cost there:
# allreduce over a host-local mesh is a memcpy, so the smaller histogram
# payload buys nothing while the root-selection pass still runs. The model
# below prices the tradeoff explicitly — logical collective bytes per split
# for both modes, the per-tree saving, and the link bandwidth below which
# that saving outweighs the measured selection overhead (PV-Tree's regime:
# many hosts on a thin DCN link). LightGBM ships the same knob pair
# (parallelism/topK, params/LightGBMParams.scala:25-27,
# LightGBMConstants.scala:22-24) but leaves the choice entirely manual.

# per-link full-duplex bandwidth, bytes/s — public figures (the scaling-book
# mental model): ICI ~1e11 B/s per link on v4/v5p-class chips; DCN per-host
# is NIC-bound, ~1.25e10 B/s (100 Gb/s) in common fleet configs.
DEFAULT_LINK_BYTES_PER_S = {"ici": 1.0e11, "dcn": 1.25e10}

# the selection pass's compute is ONE extra root-histogram build over all
# features (voting_select literally builds one); relative to a whole tree
# (whose histogram work revisits each row roughly tree-depth times) that is
# a FRACTION of per-tree compute. 0.3 is deliberately conservative (against
# voting); bench_voting_ab records the measured per-tree overhead alongside
# the model so the estimate is auditable against data.
DEFAULT_SELECTION_FRACTION = 0.3
# fallback engine throughput anchor (row-iters/sec/chip) when
# docs/measurements.json is unreadable — its 2026-07-31 on-chip entry.
# Conservative: a faster engine shrinks selection cost and favors voting.
DEFAULT_ENGINE_ROW_ITERS_PER_S = 1.69e6

#: effective wire bytes per histogram element for each
#: BoosterConfig.hist_allreduce_dtype rung: bf16 ships grad/hess at 2 bytes
#: with counts still f32 (→ 8/3 average); int8 is the blockwise-quantized
#: allreduce (int16 grid values on the wire + f32 scales per 256-block
#: ≈ 2 bytes effective, with counts exact — parallel/collectives.py).
WIRE_DTYPE_BYTES = {"f32": 4.0, "bf16": 8.0 / 3.0, "int8": 2.0}

#: fraction of a full-width histogram pass spent scanning (feature, bin)
#: cells for split gains rather than building bins from rows. Scatter-mode
#: feature-parallel scans only its owned 1/W of the features, so its
#: per-pass compute shrinks by ``scan_fraction * (1 - 1/W)``. Calibrated on
#: the 8-device CPU-mesh bench (bench_distributed_gbdt_auto): wide, narrow
#: and tall shapes all measure feature-parallel at 0.90-0.93x data-parallel
#: seconds/tree, which a pure wire model cannot explain on a host-local
#: mesh where collective bytes are ~free.
FEATURE_SCAN_FRACTION = 0.10


def default_engine_row_iters_per_s() -> float:
    """Engine throughput anchor for the selection-cost estimate: the live
    measured ``gbdt_train_row_iters_per_sec_per_chip`` record in
    docs/measurements.json when readable (the cost model then tracks the
    engine as it gets faster), else DEFAULT_ENGINE_ROW_ITERS_PER_S."""
    import json
    import os

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))), "docs",
        "measurements.json")
    try:
        with open(path) as fh:
            records = json.load(fh)
        for rec in records:
            if rec.get("metric") == "gbdt_train_row_iters_per_sec_per_chip":
                v = float(rec["value"])
                return v if v > 0 else DEFAULT_ENGINE_ROW_ITERS_PER_S
    except (OSError, ValueError, TypeError, KeyError, AttributeError):
        pass
    return DEFAULT_ENGINE_ROW_ITERS_PER_S


def collective_bytes_per_split(num_features: int, max_bin: int,
                               top_k=None, dtype_bytes: int = 4) -> int:
    """Logical allreduce payload of ONE split's histogram aggregation:
    (F_aggregated, max_bin, 3 channels) × dtype_bytes. Data-parallel
    aggregates every feature; voting-parallel only the elected 2k columns.
    ``dtype_bytes=8/3`` prices the bf16 wire option
    (BoosterConfig.hist_allreduce_dtype: grad/hess at 2 bytes, counts at
    4) — an independent 1.5x on the same comm term."""
    f_agg = (num_features if top_k is None
             else min(2 * int(top_k), num_features))
    return int(round(f_agg * int(max_bin) * 3 * dtype_bytes))


def selection_bytes_per_tree(num_features: int, dtype_bytes: int = 4) -> int:
    """The root-selection pass psums (F,) votes + (F,) gain sums once per
    tree (voting_select above)."""
    return int(num_features) * 2 * dtype_bytes


def voting_cost_model(num_features: int, max_bin: int, top_k: int,
                      num_leaves: int,
                      selection_s_per_tree: float = 1e-3,
                      dtype_bytes: float = 4) -> dict:
    """Per-tree collective accounting for both modes and the CROSSOVER link
    bandwidth: below it, the bytes voting saves per tree take longer on the
    wire than its selection pass costs — voting wins. ``dtype_bytes``
    follows the configured histogram wire precision (8/3 under bf16)."""
    splits = max(int(num_leaves) - 1, 1)
    dp = splits * collective_bytes_per_split(num_features, max_bin,
                                             dtype_bytes=dtype_bytes)
    vp = (splits * collective_bytes_per_split(num_features, max_bin, top_k,
                                              dtype_bytes=dtype_bytes)
          + selection_bytes_per_tree(num_features))
    saved = max(dp - vp, 0)
    crossover = (saved / selection_s_per_tree
                 if selection_s_per_tree > 0 else float("inf"))
    return {
        "bytes_per_split_data_parallel":
            collective_bytes_per_split(num_features, max_bin,
                                       dtype_bytes=dtype_bytes),
        "bytes_per_split_voting":
            collective_bytes_per_split(num_features, max_bin, top_k,
                                       dtype_bytes=dtype_bytes),
        "selection_bytes_per_tree": selection_bytes_per_tree(num_features),
        "bytes_per_tree_data_parallel": dp,
        "bytes_per_tree_voting": vp,
        "bytes_saved_per_tree": saved,
        "crossover_link_bytes_per_s": crossover,
    }


def recommend_tree_learner(num_features: int, max_bin: int, top_k: int,
                           num_leaves: int, n_hosts: int,
                           rows_per_host: int = None,
                           link_bytes_per_s: float = None,
                           engine_row_iters_per_s: float = None,
                           selection_fraction: float =
                           DEFAULT_SELECTION_FRACTION,
                           selection_s_per_tree: float = None,
                           dtype_bytes: float = 4) -> str:
    """The documented selection rule (VERDICT r4 #7):

    * single host — "data": every collective is intra-host (ICI/memcpy);
      the selection pass can never pay for itself.
    * narrow feature space (F <= 2k) — "data": voting would aggregate
      everything anyway.
    * multi-host — "voting" iff the per-tree wire-time saving
      ``bytes_saved_per_tree / link_bytes_per_s`` exceeds the selection
      cost. Selection cost defaults to
      ``selection_fraction * rows_per_host / engine_row_iters_per_s``
      (one extra root-histogram build, scaled by the measured engine
      throughput); pass ``selection_s_per_tree`` to override with a
      measured value (bench_voting_ab records one). With the DCN default
      this picks voting exactly for wide feature spaces on NIC-bound
      fabrics — PV-Tree's regime — and data-parallel on ICI-connected
      slices, matching the single-host A/B measurement.
    """
    if n_hosts <= 1 or num_features <= 2 * top_k:
        return "data"
    if link_bytes_per_s is None:
        link_bytes_per_s = DEFAULT_LINK_BYTES_PER_S["dcn"]
    if engine_row_iters_per_s is None:
        engine_row_iters_per_s = default_engine_row_iters_per_s()
    if selection_s_per_tree is None:
        if rows_per_host is None:
            rows_per_host = 1_000_000        # HIGGS-class shard, conservative
        selection_s_per_tree = (selection_fraction * rows_per_host
                                / engine_row_iters_per_s)
    m = voting_cost_model(num_features, max_bin, top_k, num_leaves,
                          selection_s_per_tree, dtype_bytes=dtype_bytes)
    saved_wire_s = m["bytes_saved_per_tree"] / link_bytes_per_s
    return "voting" if saved_wire_s > selection_s_per_tree else "data"


def route_parallelism(num_features: int, max_bin: int, top_k: int,
                      num_leaves: int, *, n_workers: int,
                      rows_per_worker: int, link_bytes_per_s: float,
                      selection_s_per_tree: float = None,
                      selection_fraction_of_rows: float = 1.0,
                      wire_dtype: str = "f32",
                      feature_parallel_ok: bool = False,
                      hist_passes_per_tree: float = None,
                      scan_fraction_of_pass: float = None,
                      engine_row_iters_per_s: float = None) -> tuple:
    """Measured-input router across the three distributed learners. Unlike
    :func:`recommend_tree_learner` (the byte-only rule it generalizes — kept
    for its documented behavior), this prices per-tree COMPUTE as well as
    wire time, anchored on a measured selection pass, so it can prefer
    voting even on a host-local mesh where wire bytes are ~free but the
    in-loop histogram width still dominates.

    Returns ``(choice, info)`` where info records every model input, the
    per-mode predicted s/tree, and the byte accounting — audited into
    ``Booster.metadata["routing"]`` by ``train_booster``.

    Terms, per tree (``splits = num_leaves - 1``):

    * wire: ``voting_cost_model`` bytes at the configured wire dtype
      (``WIRE_DTYPE_BYTES`` — int8 halves data-parallel bytes, shifting the
      voting crossover ~2x) divided by the measured link bandwidth.
      Feature-parallel moves ~half the allreduce bytes (reduce-scatter
      only) plus a tiny per-split (n_workers, 5)-float candidate exchange.
    * compute: one full-width root pass costs
      ``selection_s_per_tree / selection_fraction_of_rows`` (the probe may
      subsample rows); smaller-child subtraction makes a tree cost about
      ``1 + log2(L)/2`` such passes. Voting's in-loop passes run at the
      elected ``2k``-of-``F`` width (padded, as the kernel sees it); its
      selection pass is a flat per-tree add. Feature-parallel builds
      full-width histograms but split-scans only its owned ``1/W`` of the
      features, so its pass shrinks by the scan share of a pass
      (``FEATURE_SCAN_FRACTION``, calibrated on the CPU-mesh bench).

    A 5% hysteresis favors data-parallel: the probe's error bars must not
    route a marginal predicted win onto a slower mode (the bench guard
    asserts auto stays within 5% of the best manual flag, so a choice the
    hysteresis keeps on data is within guard tolerance by construction
    whenever the model is right to within its own margin).
    """
    from .grower import features_padded

    db = WIRE_DTYPE_BYTES.get(wire_dtype, 4.0)
    splits = max(int(num_leaves) - 1, 1)
    if hist_passes_per_tree is None:
        hist_passes_per_tree = 1.0 + 0.5 * math.log2(max(num_leaves, 2))
    if selection_s_per_tree is None or selection_s_per_tree <= 0:
        if engine_row_iters_per_s is None:
            engine_row_iters_per_s = default_engine_row_iters_per_s()
        selection_s_per_tree = (DEFAULT_SELECTION_FRACTION * rows_per_worker
                                / engine_row_iters_per_s)
        selection_fraction_of_rows = DEFAULT_SELECTION_FRACTION
    t_root_full = selection_s_per_tree / max(selection_fraction_of_rows,
                                             1e-9)
    t_hist_full = hist_passes_per_tree * t_root_full
    m = voting_cost_model(num_features, max_bin, top_k, num_leaves,
                          selection_s_per_tree, dtype_bytes=db)

    def wire(nbytes):
        return nbytes / max(link_bytes_per_s, 1.0)

    fp_ratio = (features_padded(min(2 * top_k, num_features))
                / max(features_padded(num_features), 1))
    if scan_fraction_of_pass is None:
        scan_fraction_of_pass = FEATURE_SCAN_FRACTION
    scatter_compute = 1.0 - scan_fraction_of_pass * (1.0
                                                     - 1.0 / max(n_workers, 1))
    exchange_bytes = splits * n_workers * 5 * 4
    predicted = {
        "data": t_hist_full + wire(m["bytes_per_tree_data_parallel"]),
        "voting": (selection_s_per_tree + t_hist_full * fp_ratio
                   + wire(m["bytes_per_tree_voting"])),
        "feature": (t_hist_full * scatter_compute
                    + wire(0.5 * m["bytes_per_tree_data_parallel"]
                           + exchange_bytes)),
    }
    candidates = {"data": predicted["data"]}
    if num_features > 2 * top_k and n_workers > 1:
        candidates["voting"] = predicted["voting"]
    if feature_parallel_ok and n_workers > 1:
        candidates["feature"] = predicted["feature"]
    choice = min(candidates, key=candidates.get)
    if choice != "data" and candidates[choice] > 0.95 * candidates["data"]:
        choice = "data"
    info = {
        "tree_learner": choice,
        "predicted_s_per_tree": predicted,
        "considered": sorted(candidates),
        "inputs": {
            "num_features": int(num_features), "max_bin": int(max_bin),
            "top_k": int(top_k), "num_leaves": int(num_leaves),
            "n_workers": int(n_workers),
            "rows_per_worker": int(rows_per_worker),
            "link_bytes_per_s": float(link_bytes_per_s),
            "selection_s_per_tree": float(selection_s_per_tree),
            "selection_fraction_of_rows": float(selection_fraction_of_rows),
            "wire_dtype": wire_dtype, "wire_dtype_bytes": db,
            "hist_passes_per_tree": float(hist_passes_per_tree),
            "scan_fraction_of_pass": float(scan_fraction_of_pass),
        },
        "cost_model": m,
    }
    return choice, info
