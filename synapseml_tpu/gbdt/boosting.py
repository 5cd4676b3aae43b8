"""Boosting driver: the training-iteration loop and the Booster model.

The analog of the reference's TrainUtils.scala (booster creation :16-29, iteration
loop with early stopping + custom fobj :77-135, eval-metric extraction :137-151)
plus the serializable model of booster/LightGBMBooster.scala. The per-iteration
work (gradients → tree growth → score update) is jitted XLA; the loop itself is
host Python (one dispatch per tree), matching the reference's structure where the
JVM loop calls LGBM_BoosterUpdateOneIter per iteration.

Boosting modes (SURVEY §2.1 N1): gbdt, rf (bagged trees, averaged output), dart
(tree dropout with 1/(k+1) normalization), goss (top-|g| keep + amplified random
sample of the rest). GOSS/bagging/instance weights all funnel into the same
(grad, hess, in_bag) triple consumed by the grower.
"""

from __future__ import annotations

import dataclasses
import functools
import math
import time as _time
from typing import Callable, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..ops.quantize import (BinMapper, apply_bins, bin_threshold_to_value,
                            bins_by_compare, compute_bin_mapper)

from .dataset import Dataset, _is_sparse
from .grower import (Forest, GrowerConfig, TreeArrays, forest_max_depth,
                     forest_predict, grow_tree, split_counter, stack_trees)
from .objectives import (METRICS, HIGHER_IS_BETTER, Objective, get_objective,
                         lambdarank_objective, make_grouped,
                         map_at_k, metric_kwargs, ndcg_at_k)
from ..parallel.elastic import current_watchdog


@dataclasses.dataclass
class BoosterConfig:
    """Training configuration — the native-param surface the reference renders
    through ParamsStringBuilder (LightGBMBase.scala:374-386). Field names follow
    LightGBM's canonical param names."""

    objective: str = "regression"
    boosting_type: str = "gbdt"          # gbdt | rf | dart | goss
    num_iterations: int = 100
    learning_rate: float = 0.1
    num_leaves: int = 31
    max_bin: int = 255
    max_depth: int = -1
    lambda_l1: float = 0.0
    lambda_l2: float = 0.0
    min_data_in_leaf: int = 20
    min_sum_hessian_in_leaf: float = 1e-3
    min_gain_to_split: float = 0.0
    bagging_fraction: float = 1.0
    bagging_freq: int = 0
    pos_bagging_fraction: float = 1.0
    neg_bagging_fraction: float = 1.0
    feature_fraction: float = 1.0
    feature_fraction_bynode: float = 1.0
    top_rate: float = 0.2                # goss
    other_rate: float = 0.1              # goss
    drop_rate: float = 0.1               # dart
    max_drop: int = 50
    skip_drop: float = 0.5
    uniform_drop: bool = False
    num_class: int = 1
    sigmoid: float = 1.0
    alpha: float = 0.9                   # huber / quantile
    fair_c: float = 1.0
    poisson_max_delta_step: float = 0.7
    tweedie_variance_power: float = 1.5
    max_delta_step: float = 0.0
    cat_smooth: float = 10.0
    max_cat_threshold: int = 32
    max_cat_to_onehot: int = 4
    min_data_per_group: int = 100
    xgboost_dart_mode: bool = False
    monotone_constraints: Optional[Sequence[int]] = None
    early_stopping_round: int = 0
    metric: Optional[str] = None
    seed: int = 0
    boost_from_average: bool = True
    bin_sample_count: int = 200_000
    min_data_in_bin: int = 3              # merge under-filled bins (minDataPerBin)
    max_bin_by_feature: Optional[Sequence[int]] = None
    cat_l2: float = 10.0                  # categorical split L2 (catl2)
    # derived sampling seeds (LightGBM exposes independent seeds; 0 = derive
    # purely from `seed`)
    drop_seed: int = 0
    feature_fraction_seed: int = 0
    extra_seed: int = 0
    start_iteration: int = 0              # prediction start (predict window)
    # distributed tree learner: "auto" (default) routes per dataset through
    # the measured cost model in gbdt/voting.py at fit time (falls back to
    # "serial" off-mesh; the decision + model inputs land in
    # Booster.metadata["routing"]); "serial"/"data" aggregate all features'
    # histograms; "voting" selects top-2k features per tree by shard votes
    # (PV-Tree; LightGBM voting_parallel + topK — LightGBMParams.scala:25-27);
    # "feature" is the owned-feature reduce-scatter grower (each device keeps
    # 1/world of the reduced histogram and per-leaf winners are exchanged —
    # LightGBM data_parallel's actual wire pattern). Explicit values force.
    tree_learner: str = "auto"
    top_k: int = 20
    # growth policy: "leafwise" (LightGBM parity) | "depthwise"
    # (level-batched opt-in; see grower_depthwise.py)
    growth_policy: str = "leafwise"
    # histogram allreduce wire precision ladder ("f32" | "bf16" | "int8") —
    # grad/hess ride the wire at reduced width (counts stay exact), cutting
    # per-split collective bytes to 2/3 (bf16) or ~1/2 (int8 blockwise-
    # quantized allreduce, EQuARX-style incl. per-block scales) on
    # multi-host fabrics; see GrowerConfig.hist_allreduce_dtype. "auto"
    # resolves at fit time through core/perfmodel (grower.resolve_wire_dtype):
    # the learned model picks the ladder rung only on measured evidence for a
    # matching workload, else the conservative f32 wire; the decision lands
    # in Booster.metadata["autoconfig"]["wire_dtype"]
    hist_allreduce_dtype: str = "f32"
    # lambdarank
    lambdarank_truncation_level: int = 30
    max_position: int = 30
    # relevance gain per label value (LightGBMRankerParams labelGain; empty
    # = the default 2^label - 1 table)
    label_gain: tuple = ()
    # bagging stream seed (LightGBM bagging_seed, default 3)
    bagging_seed: int = 3
    # minimum metric improvement for early stopping (improvementTolerance)
    improvement_tolerance: float = 0.0
    # bin-boundary sampling seed override (LightGBM data_random_seed);
    # None = use `seed` (legacy behavior)
    data_random_seed: object = None
    # features' missing code becomes zero (zeroAsMissing): the estimator
    # layer maps 0 -> NaN before binning and traversal routes |x|<=1e-35
    # (and coerced NaN) to the default side
    zero_as_missing: bool = False
    # NDCG eval positions (LightGBMRankerParams evalAt, default 1-5 at the
    # estimator layer): when set, the FIRST position drives validation/early
    # stopping, matching the reference (maxPosition truncates the lambdarank
    # objective via lambdarank_truncation_level, not the eval metric). Empty
    # = legacy engine-level behavior: evaluate at max_position.
    eval_at: tuple = ()

    def __post_init__(self):
        if self.growth_policy not in ("leafwise", "depthwise"):
            raise ValueError(
                f"BoosterConfig.growth_policy={self.growth_policy!r} is not "
                "one of ('leafwise', 'depthwise')")
        if self.hist_allreduce_dtype not in ("auto", "f32", "bf16", "int8"):
            raise ValueError(
                f"BoosterConfig.hist_allreduce_dtype="
                f"{self.hist_allreduce_dtype!r} is not one of "
                "('auto', 'f32', 'bf16', 'int8')")
        if self.tree_learner not in ("auto", "serial", "data", "voting",
                                     "feature"):
            raise ValueError(
                f"BoosterConfig.tree_learner={self.tree_learner!r} is not "
                "one of ('auto', 'serial', 'data', 'voting', 'feature')")

    def grower(self, has_categorical: bool = False,
               feature_shards: int = 1) -> GrowerConfig:
        lr = 1.0 if self.boosting_type == "rf" else self.learning_rate
        feature_mode = self.tree_learner == "feature" and feature_shards > 1
        return GrowerConfig(
            hist_reduce="scatter" if feature_mode else "allreduce",
            feature_shards=feature_shards if feature_mode else 1,
            has_categorical=has_categorical,
            num_leaves=self.num_leaves,
            num_bins=self.max_bin,
            max_depth=self.max_depth,
            lambda_l1=self.lambda_l1,
            lambda_l2=self.lambda_l2,
            min_data_in_leaf=self.min_data_in_leaf,
            min_sum_hessian_in_leaf=self.min_sum_hessian_in_leaf,
            min_gain_to_split=self.min_gain_to_split,
            feature_fraction_bynode=self.feature_fraction_bynode,
            learning_rate=lr,
            max_delta_step=self.max_delta_step,
            cat_smooth=self.cat_smooth,
            cat_l2=self.cat_l2,
            max_cat_threshold=self.max_cat_threshold,
            max_cat_to_onehot=self.max_cat_to_onehot,
            min_data_per_group=self.min_data_per_group,
            growth_policy=self.growth_policy,
            hist_allreduce_dtype=self.hist_allreduce_dtype,
        )


class Booster:
    """A trained forest + binning metadata; the LightGBMBooster analog
    (booster/LightGBMBooster.scala): scoring, leaf prediction, SHAP, model-string
    save/load, feature importances."""

    def __init__(self, mapper: BinMapper, config: BoosterConfig,
                 trees: List[TreeArrays], tree_weights: List[float],
                 base_score: np.ndarray, feature_names: Optional[List[str]] = None,
                 best_iteration: int = -1,
                 thresholds: Optional[List[np.ndarray]] = None,
                 missing_types: Optional[List[np.ndarray]] = None,
                 best_score: Optional[float] = None,
                 metadata: Optional[dict] = None):
        self.mapper = mapper
        # training provenance (e.g. the parallelism router's decision and the
        # measured inputs it saw); empty for loaded native models
        self.metadata: dict = dict(metadata) if metadata else {}
        self.config = config
        self.trees = trees
        self.tree_weights = list(tree_weights)
        self.base_score = np.atleast_1d(np.asarray(base_score, np.float64))
        self.feature_names = feature_names or [f"Column_{i}" for i in range(mapper.num_features)]
        self.best_iteration = best_iteration
        # the best validation metric value (LightGBM Booster.best_score)
        self.best_score = best_score
        # real-valued thresholds per tree; None → resolve from the bin mapper.
        # Loaded native models carry raw thresholds directly (no mapper).
        self.thresholds = thresholds
        # per-split LightGBM missing-type codes (0 none / 1 zero / 2 nan);
        # loaded native models parse them from decision_type, trained models
        # derive them from the mapper's NaN mask (_missing_types)
        self.missing_types = missing_types
        self._forest_cache: Optional[Forest] = None
        self._depth_cache: Optional[int] = None
        # bucketed serving runners keyed by max_batch_size (serving_fn /
        # batched predict share the same compiled bucket ladder)
        self._serving_cache: dict = {}

    # --- structure ------------------------------------------------------
    @property
    def num_class(self) -> int:
        return max(self.config.num_class, 1)

    @property
    def models_per_iter(self) -> int:
        return self.num_class if self.config.objective in ("multiclass", "softmax", "multiclassova") else 1

    @property
    def num_trees(self) -> int:
        return len(self.trees)

    @property
    def average_output(self) -> bool:
        return self.config.boosting_type == "rf"

    @property
    def trees_per_class(self) -> int:
        """Full-model rf averaging divisor (forest()); SHAP uses the
        start_iteration-windowed count to match raw_score's rescale."""
        return max(len(self.trees) // self.models_per_iter, 1)

    def _thresholds(self, index: int) -> np.ndarray:
        # per-entry None = resolve from the mapper (warm starts merge loaded
        # trees' parsed thresholds with None slots for newly grown trees)
        if self.thresholds is not None:
            t = (self.thresholds[index]
                 if index < len(self.thresholds) else None)
            if t is not None:
                return np.asarray(t, np.float32)
        tree = self.trees[index]
        sf = np.asarray(tree.split_feature)
        sb = np.asarray(tree.split_bin)
        vals = np.array([bin_threshold_to_value(self.mapper, int(f), int(b))
                         for f, b in zip(sf, sb)], np.float64)
        # top-bin sentinel is 1e308 (finite in f64 model strings); map it to an
        # INTENTIONAL f32 inf (not a clamp to f32max: +inf feature values must
        # still satisfy x <= threshold and go left, matching the binned path
        # where apply_bins clamps inf into the last real-value bin)
        f32max = np.float64(np.finfo(np.float32).max)
        return np.where(vals >= f32max, np.inf,
                        np.clip(vals, -f32max, f32max)).astype(np.float32)

    def _missing_types(self, index: int) -> np.ndarray:
        """(L-1,) missing-type codes for one tree: parsed values for loaded
        models, else nan (2) for features with a NaN bin AND for categorical
        splits (NaN categories are never set members) / 0 otherwise — the
        codes the model-string writer emits in decision_type, so in-memory
        traversal and a save/load round trip route missing rows identically."""
        if self.missing_types is not None:
            m = (self.missing_types[index]
                 if index < len(self.missing_types) else None)
            if m is not None:
                return np.asarray(m, np.int32)
        tree = self.trees[index]
        sf = np.asarray(tree.split_feature).astype(np.int64)
        stype = np.asarray(tree.split_type)
        has_nan = np.asarray(self.mapper.nan_mask)
        sf_safe = np.clip(sf, 0, len(has_nan) - 1)
        # zeroAsMissing trains with zeros mapped to NaN; traversal and the
        # serialized decision_type must route zeros (code 1), not just NaN
        nan_code = 1 if getattr(self.config, "zero_as_missing", False) else 2
        return np.where(stype[: len(sf)] == 1, 2,
                        np.where(has_nan[sf_safe], nan_code,
                                 0)).astype(np.int32)

    def unweighted(self) -> "Booster":
        """Copy with unit tree weights and zero base — used to recover raw
        per-tree contributions (dart drop candidates / rf validation).
        Thresholds/missing codes ride along: a from_model_string booster has
        a synthetic all-inf mapper, so dropping its parsed thresholds would
        send every row left."""
        return Booster(self.mapper, self.config, self.trees,
                       [1.0] * len(self.trees),
                       np.zeros_like(self.base_score),
                       thresholds=self.thresholds,
                       missing_types=self.missing_types)

    def forest(self) -> Forest:
        if self._forest_cache is None or self._forest_cache.num_trees != len(self.trees):
            trees = self.trees
            weights = np.asarray(self.tree_weights, np.float32)
            if self.average_output:
                weights = weights / self.trees_per_class
            weighted = [t._replace(leaf_value=jnp.asarray(t.leaf_value) * w)
                        for t, w in zip(trees, weights)]
            self._forest_cache = stack_trees(
                weighted, [self._thresholds(i) for i in range(len(trees))],
                [self._missing_types(i) for i in range(len(trees))])
            self._depth_cache = forest_max_depth(trees)
        return self._forest_cache

    # --- inference ------------------------------------------------------
    def serving_fn(self, max_batch_size: int = 64, bucketed: bool = True):
        """Callable ``X (N, F) -> prediction`` for low-latency serving:
        forest traversal, base score, and the objective's output transform
        compiled into a single XLA program — one device dispatch per request
        batch instead of predict()'s traversal + transform round trips. This
        is the handler-side analog of the reference's served fitted models
        (README Spark Serving cell; HTTPSourceV2.scala:485-713 transport +
        a model transform).

        By default the fused program runs through a shape-bucketed runner
        (core/inference.py, docs/serving-perf.md): batches pad up to a
        geometric ladder of bucket sizes so XLA compiles once per bucket —
        not once per observed batch size — with padded rows masked out of
        the result. The returned callable carries ``.runner`` (per-bucket
        compile/hit counters) and ``.warmup()`` (AOT-compile every bucket;
        ServingServer.start() calls it before accepting traffic).
        ``bucketed=False`` returns the raw fused jit for callers that manage
        their own shapes."""
        import jax

        forest = self.forest()
        obj = self._objective_for_transform()
        depth = self._depth_cache
        k = self.models_per_iter
        base = jnp.asarray(self.base_score[:max(k, 1)], jnp.float32)
        # the config's prediction window applies to serving too (raw_score
        # parity — code-review r5: a windowed booster must not serve
        # different probabilities than predict())
        start = max(int(getattr(self.config, "start_iteration", 0)), 0)

        def fn(X):
            if k == 1 and not start and not self.average_output:
                raw = forest_predict(forest, X, output="sum",
                                     depth=depth) + base[0]
            else:
                per_tree = forest_predict(forest, X, output="per_tree",
                                          depth=depth)
                n, t = per_tree.shape
                per_iter = per_tree.reshape(n, t // k, k)
                if start:
                    per_iter = per_iter[:, start:]
                if self.average_output and per_iter.shape[1] != t // k:
                    # rf leaves were pre-divided by the FULL tree count
                    per_iter = per_iter * ((t // k)
                                           / max(per_iter.shape[1], 1))
                raw = per_iter.sum(axis=1) + base[None]
                if k == 1:
                    raw = raw[:, 0]
            return obj.transform(raw)

        if not bucketed:
            return jax.jit(fn)

        from ..core.inference import BucketedRunner

        # fn is deliberately NOT pre-jitted here: the runner owns the jit
        # boundary (one AOT-compiled executable per bucket)
        runner = BucketedRunner(fn, max_batch_size=max_batch_size,
                                name="gbdt.serving_fn")
        num_features = self.mapper.num_features

        def serve(X):
            return runner(np.asarray(X))

        def warmup(dtype=np.float32):
            return runner.warmup(np.zeros((1, num_features), dtype))

        serve.runner = runner
        serve.warmup = warmup
        return serve

    def raw_score(self, X, binned: bool = False, num_iteration: int = -1,
                  start_iteration: Optional[int] = None) -> np.ndarray:
        """(N,) or (N, K) raw margin. ``num_iteration`` > 0 scores with only
        that many boosting rounds; ``start_iteration`` (default: the config's
        predict-time window) skips leading rounds. Training-side margin
        rebuilds pass start_iteration=0 explicitly — the window is a
        prediction feature and must not leak into warm starts."""
        X = _densify(X)
        nb = jnp.asarray(self.mapper.nan_bins) if binned else None
        forest = self.forest()
        k = self.models_per_iter
        if start_iteration is None:
            start_iteration = max(
                int(getattr(self.config, "start_iteration", 0)), 0)
        if (k == 1 and not start_iteration
                and (not num_iteration or num_iteration < 0)
                and not self.average_output):
            # no prediction window active: sum inside the traversal scan —
            # the (N, T) per-tree matrix is 4 GB at 11M rows x 100 trees and
            # exists only to support windowing/rf rescale
            out = forest_predict(forest, jnp.asarray(X), binned=binned,
                                 output="sum", nan_bins=nb,
                                 depth=self._depth_cache)
            return np.asarray(out + self.base_score[0])
        per_tree = forest_predict(forest, jnp.asarray(X), binned=binned,
                                  output="per_tree", nan_bins=nb,
                                  depth=self._depth_cache)  # (N, T)
        n, t = per_tree.shape
        per_iter = per_tree.reshape(n, t // k, k)
        if start_iteration:
            per_iter = per_iter[:, start_iteration:]
        if num_iteration and num_iteration > 0:
            per_iter = per_iter[:, :num_iteration]
        if self.average_output and per_iter.shape[1] != t // k:
            # rf leaves were pre-divided by the FULL tree count; rescale so
            # the windowed average stays an average of the summed trees
            per_iter = per_iter * ((t // k) / max(per_iter.shape[1], 1))
        out = per_iter.sum(axis=1) + self.base_score[None, :k]
        return np.asarray(out[:, 0] if k == 1 else out)

    def predict(self, X, binned: bool = False, num_iteration: int = -1,
                batch_size: Optional[int] = None) -> np.ndarray:
        """Probability / response-space prediction.

        ``batch_size`` routes batch predict through the shared bucketed
        serving runner (core/inference.py): rows are processed in
        ``batch_size`` chunks with a bucket-padded tail, so repeated calls
        with varying N reuse one compiled ladder instead of compiling a
        fresh XLA program per observed shape. The runner is cached per
        ``batch_size``, shared with ``serving_fn(max_batch_size=...)``."""
        if batch_size is not None:
            if binned or (num_iteration and num_iteration > 0):
                raise ValueError(
                    "predict(batch_size=...) serves the full raw-value "
                    "model; binned inputs or an iteration window need the "
                    "unbatched path")
            serve = self._serving_cache.get(batch_size)
            if serve is None:
                serve = self.serving_fn(max_batch_size=batch_size)
                self._serving_cache[batch_size] = serve
            return serve(_densify(X))
        raw = self.raw_score(X, binned=binned, num_iteration=num_iteration)
        obj = self._objective_for_transform()
        return np.asarray(obj.transform(jnp.asarray(raw)))

    def predict_leaf(self, X) -> np.ndarray:
        """(N, T) leaf indices (predictLeaf parity, LightGBMBooster.scala:408)."""
        forest = self.forest()
        leaves = np.asarray(forest_predict(forest, jnp.asarray(_densify(X)),
                                           output="leaf",
                                           depth=self._depth_cache))
        start = max(int(getattr(self.config, "start_iteration", 0)), 0)
        return leaves[:, start * self.models_per_iter:] if start else leaves

    def feature_importances(self, importance_type: str = "split") -> np.ndarray:
        """split count or total gain per feature (getFeatureImportances parity,
        LightGBMBooster.scala:490-505)."""
        imp = np.zeros(self.mapper.num_features)
        for t in self.trees:
            ns = int(t.num_splits)
            sf = np.asarray(t.split_feature)[:ns]
            if importance_type == "gain":
                np.add.at(imp, sf, np.asarray(t.split_gain)[:ns])
            else:
                np.add.at(imp, sf, 1.0)
        return imp

    def feature_shap(self, X) -> np.ndarray:
        from .shap import forest_shap
        return forest_shap(self, np.asarray(_densify(X), np.float32))

    def _objective_for_transform(self) -> Objective:
        cfg = self.config
        name = cfg.objective
        if name == "lambdarank":
            from .objectives import regression_objective
            return regression_objective()
        return get_objective(name, num_class=self.num_class, sigmoid=cfg.sigmoid,
                             alpha=cfg.alpha, fair_c=cfg.fair_c,
                             poisson_max_delta_step=cfg.poisson_max_delta_step,
                             tweedie_variance_power=cfg.tweedie_variance_power)

    # --- persistence ----------------------------------------------------
    def dump_model(self, num_iteration: int = -1) -> str:
        """LightGBM-format JSON dump (dumpModel parity,
        LightGBMBooster.scala:458-516)."""
        from .model_io import booster_dump_json

        return booster_dump_json(self, num_iteration)

    def model_string(self) -> str:
        from .model_io import booster_to_string
        return booster_to_string(self)

    @staticmethod
    def from_model_string(s: str) -> "Booster":
        from .model_io import booster_from_string
        return booster_from_string(s)

    def save_native(self, path: str) -> None:
        """saveNativeModel parity (LightGBMBooster.scala:458-470)."""
        with open(path, "w") as f:
            f.write(self.model_string())

    def to_onnx(self, input_name: str = "input", num_iteration: int = -1):
        """ONNX TreeEnsemble export — the native analog of the reference's
        documented onnxmltools.convert_lightgbm workflow (website Quickstart
        - ONNX Model Inference.md); serve the result through ONNXModel."""
        from ..onnx.treeensemble import booster_to_onnx

        return booster_to_onnx(self, input_name, num_iteration)


# ---------------------------------------------------------------------------
# Training
# ---------------------------------------------------------------------------

def _densify(X):
    """scipy sparse -> dense float32 (predict/valid inputs accept CSR the same
    as training); pass-through for anything else."""
    if _is_sparse(X):
        return np.asarray(X.tocsr().todense(), np.float32)
    return X


@jax.jit
def _leaf_gather(leaf_value, node_of_row):
    return leaf_value[node_of_row]


# ---------------------------------------------------------------------------
# Shared per-iteration sampling (device-side; used by the fused scan and the
# host loop so both paths sample identically from fold_in(seed, it))
# ---------------------------------------------------------------------------

def _sample_rows_impl(cfg, n, key0, valid_mask, it, g, h, in_bag_cur, yj=None):
    goss_mode = cfg.boosting_type == "goss"
    stratified = (cfg.pos_bagging_fraction < 1.0
                  or cfg.neg_bagging_fraction < 1.0)
    do_bag = ((cfg.boosting_type == "rf" or cfg.bagging_freq > 0)
              and (cfg.bagging_fraction < 1.0 or stratified))
    if goss_mode:
        gnorm = jnp.abs(g).sum(axis=1)
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
        wmask = (jnp.where(ranks < top_n, 1.0,
                           jnp.where(pick, amp, 0.0)) * valid_mask)
        return (wmask > 0).astype(jnp.float32), g * wmask[:, None], \
            h * wmask[:, None], in_bag_cur
    if do_bag:
        kb = (jax.random.fold_in(key0, cfg.bagging_seed)
              if cfg.bagging_seed != 3 else key0)  # default keeps the stream
        u = jax.random.uniform(
            jax.random.fold_in(kb, 20_000_000 + it), (n,))
        if stratified and yj is not None:
            # posBaggingFraction / negBaggingFraction (binary objectives):
            # per-class keep probability, refreshed every bagging_freq rounds
            frac = jnp.where(yj > 0, cfg.pos_bagging_fraction,
                             cfg.neg_bagging_fraction)
        else:
            frac = cfg.bagging_fraction
        fresh = ((u < frac).astype(jnp.float32) * valid_mask)
        bag = jnp.where(it % max(cfg.bagging_freq, 1) == 0, fresh, in_bag_cur)
        return bag, g, h, bag
    return valid_mask, g, h, in_bag_cur


def _sample_features_impl(cfg, nfeat, key0, it):
    if cfg.feature_fraction >= 1.0:
        return jnp.ones(nfeat, bool)
    nf_keep = max(1, int(math.ceil(cfg.feature_fraction * nfeat)))
    kf = (jax.random.fold_in(key0, cfg.feature_fraction_seed)
          if cfg.feature_fraction_seed else key0)  # 0 keeps the default stream
    perm = jax.random.permutation(
        jax.random.fold_in(kf, 10_000_000 + it), nfeat)
    return jnp.zeros(nfeat, bool).at[perm[:nf_keep]].set(True)


def _node_key_data(key0, it, cls):
    """Per-tree raw key for feature_fraction_bynode: shared derivation so the
    fused scan and the host loop sample identical per-node feature subsets."""
    return jax.random.key_data(
        jax.random.fold_in(jax.random.fold_in(key0, 30_000_000 + cls), it))


def _make_grow_fn(grower_cfg, mesh):
    """The per-tree grower, shard_map'd over the data axis when distributed
    (one histogram psum per split — the socket-ring allreduce analog)."""
    if mesh is not None:
        from jax.sharding import PartitionSpec as P
        from ..parallel.collectives import shard_apply
        from ..parallel.mesh import DATA_AXIS as _DA

        def _grow_sharded(binned_s, g_s, h_s, bag_s, fa, ic, mo, nb, nk, cb):
            return grow_tree(binned_s, g_s, h_s, bag_s, fa, ic, mo,
                             grower_cfg, nan_bins=nb, axis_name=_DA,
                             node_key=nk, cat_nbins=cb)

        return shard_apply(
            mesh, _grow_sharded,
            in_specs=(P(_DA, None), P(_DA), P(_DA), P(_DA),
                      P(None), P(None), P(None), P(None), P(None), P(None)),
            out_specs=(P(), P(_DA)))

    def grow_fn(binned_s, g_s, h_s, bag_s, fa, ic, mo, nb, nk, cb):
        return grow_tree(binned_s, g_s, h_s, bag_s, fa, ic, mo,
                         grower_cfg, nan_bins=nb, node_key=nk, cat_nbins=cb)

    return grow_fn


def _route_features(cfg, n_rows, nfeat, n_workers):
    """The tree-learner featurization shared by the router, bench.py's
    training-row writer, and the ci.sh auto-config guard — one schema, so
    rows recorded by a bench arm are matchable by the live router."""
    from ..core import perfmodel

    return perfmodel.featurize(
        wire_dtype=cfg.hist_allreduce_dtype, rows=n_rows, nfeat=nfeat,
        workers=n_workers, max_bin=cfg.max_bin, top_k=cfg.top_k,
        num_leaves=cfg.num_leaves)


def _perfmodel_route(cfg, n_rows, nfeat, n_workers, choice, info,
                     feature_ok):
    """Layer the learned perf model over ``route_parallelism``'s analytic
    choice: the analytic per-tree predictions become priors, and recorded
    training rows for a matching workload (kind ``gbdt_tree_learner``) can
    confidently override the hand-tuned cost model. Low confidence — the
    usual case on shapes never benched — keeps the analytic choice, so
    this layer strictly adds measured evidence. Provenance lands in
    ``info["perfmodel"]`` either way."""
    from ..core import perfmodel

    feats = _route_features(cfg, n_rows, nfeat, n_workers)
    pred = info.get("predicted_s_per_tree") or {}
    arms = ["data", "voting"] + (["feature"] if feature_ok else [])
    cands = [perfmodel.Candidate("gbdt_tree_learner", arm, feats,
                                 analytic_s=pred.get(arm), config=arm)
             for arm in arms]
    try:
        dec = perfmodel.choose(cands, fallback_arm=choice)
    except Exception:  # model failure keeps router choice
        return choice
    info["perfmodel"] = dec.provenance()
    if not dec.used_fallback and dec.arm != choice:
        info["tree_learner"] = dec.arm
        info["router"] = "measured+perfmodel"
        return dec.arm
    return choice


def _train_metadata(routing_info, autoconfig_info, fit_t0):
    """Assemble Booster.metadata: the router's decision plus every
    auto-configuration decision's provenance, stamped with the observed fit
    wall time so predicted-vs-observed runtime is auditable per model."""
    meta = {}
    if routing_info:
        meta["routing"] = routing_info
    if autoconfig_info:
        autoconfig_info["observed_fit_s"] = round(
            _time.perf_counter() - fit_t0, 6)
        meta["autoconfig"] = autoconfig_info
    return meta or None


def _auto_route(cfg, mesh, binned, nfeat, n_rows, multiproc,
                has_categorical):
    """Resolve ``tree_learner='auto'`` into a concrete learner.

    Single-process mesh: measure the link (one timed ~1MB allreduce) and —
    when voting is even a candidate (F > 2k) — the selection pass, both
    cached per mesh in ``core.tuned``'s measurement store, then let
    ``voting.route_parallelism`` pick data / voting / feature from the
    quantization-aware cost model. Multi-process training skips the probes
    (a timed collective would need every process in lockstep before shapes
    are agreed) and falls back to the static ``recommend_tree_learner``
    model, as before. Returns ``(choice, info)``; ``info`` lands in
    ``Booster.metadata['routing']`` so the decision is auditable.
    """
    from .voting import recommend_tree_learner, route_parallelism

    if mesh is None:
        return "data", {"tree_learner": "data", "router": "static",
                        "reason": "no mesh: serial == data-parallel-of-1"}
    from ..parallel.mesh import DATA_AXIS as _DA

    n_workers = int(dict(mesh.shape).get(_DA, 1))
    if multiproc or n_workers <= 1:
        choice = recommend_tree_learner(
            nfeat, cfg.max_bin, cfg.top_k, cfg.num_leaves,
            n_hosts=jax.process_count(), rows_per_host=n_rows,
            dtype_bytes=(8 / 3 if cfg.hist_allreduce_dtype == "bf16" else 4))
        reason = "multi-process: static model (no probes)" \
            if multiproc else "single worker"
        if choice == "voting" and multiproc:
            import warnings

            warnings.warn(
                "tree_learner='auto': the collective cost model prefers "
                "voting-parallel at this shape, but multi-process training "
                "does not support the voting learner yet — falling back to "
                "data-parallel. Set tree_learner='voting' on a "
                "single-process mesh to use it.")
            choice = "data"
        return choice, {"tree_learner": choice, "router": "static",
                        "reason": reason}

    from ..core import tuned
    from ..parallel.collectives import probe_link_bandwidth

    try:
        fp = tuned.mesh_fingerprint(mesh)
        link = tuned.measured_or(("link_bytes_per_s", fp),
                                 lambda: probe_link_bandwidth(mesh))
        sel_s, sel_frac = None, 1.0
        if nfeat > 2 * cfg.top_k:
            from .voting import time_selection

            sel_s, sel_frac = tuned.measured_or(
                ("selection_s_per_tree", fp, int(binned.shape[0]), nfeat,
                 cfg.max_bin, cfg.top_k),
                lambda: time_selection(
                    binned, mesh, cfg.top_k, cfg.max_bin,
                    lambda_l2=cfg.lambda_l2,
                    min_data=max(cfg.min_data_in_leaf, 1)))
        from ..ops.hist_kernel import features_padded as _fpad

        feature_ok = (not has_categorical
                      and cfg.growth_policy == "leafwise"
                      and _fpad(nfeat) % n_workers == 0)
        choice, info = route_parallelism(
            nfeat, cfg.max_bin, cfg.top_k, cfg.num_leaves,
            n_workers=n_workers,
            rows_per_worker=max(n_rows // n_workers, 1),
            link_bytes_per_s=link,
            selection_s_per_tree=sel_s,
            selection_fraction_of_rows=sel_frac,
            wire_dtype=cfg.hist_allreduce_dtype,
            feature_parallel_ok=feature_ok)
        info["router"] = "measured"
        choice = _perfmodel_route(cfg, n_rows, nfeat, n_workers, choice,
                                  info, feature_ok)
        return choice, info
    except Exception as e:                   # pragma: no cover - probe escape
        import warnings

        warnings.warn(f"tree_learner='auto': probe failed ({e!r}); "
                      "using the static cost model")
        choice = recommend_tree_learner(
            nfeat, cfg.max_bin, cfg.top_k, cfg.num_leaves,
            n_hosts=jax.process_count(), rows_per_host=n_rows,
            dtype_bytes=(8 / 3 if cfg.hist_allreduce_dtype == "bf16" else 4))
        return choice, {"tree_learner": choice, "router": "static",
                        "reason": f"probe failed: {e!r}"}


# ---------------------------------------------------------------------------
# Fused-scan runner cache: the jitted whole-training program is cached ACROSS
# train_booster calls (keyed by the static config + shapes), so a warmup call
# with identical config compiles the exact executable the timed/production
# call reuses. Without this, every fit would recompile the scan — 40-110 s
# for the chip at HIGGS width.
# ---------------------------------------------------------------------------

_FUSED_RUNNERS: dict = {}


def _fused_static_key(cfg, grower_cfg, n, nfeat, k, nv, metric_name, mesh):
    mono = tuple(cfg.monotone_constraints or ())
    return (cfg.objective, cfg.boosting_type, cfg.learning_rate, cfg.num_class,
            cfg.sigmoid, cfg.alpha, cfg.fair_c, cfg.poisson_max_delta_step,
            cfg.tweedie_variance_power, cfg.top_rate, cfg.other_rate,
            cfg.bagging_fraction, cfg.bagging_freq, cfg.feature_fraction,
            cfg.pos_bagging_fraction, cfg.neg_bagging_fraction,
            cfg.lambdarank_truncation_level, mono, grower_cfg,
            # seeds are folded into the traced program as Python ints
            # (_sample_rows_impl/_sample_features_impl): two configs that
            # differ only here must NOT share an executable
            cfg.extra_seed, cfg.feature_fraction_seed, cfg.bagging_seed,
            tuple(cfg.label_gain or ()),
            n, nfeat, k, nv, metric_name, mesh)


def _get_fused_runner(cfg, grower_cfg, n, nfeat, k, nv, metric_name, mesh):
    """Jitted fn(binned, yj, wj, valid_mask, key0, is_cat, mono, nan_bins,
    base_k, gidx, binned_v, yv_j, wv_j, gidx_v, score0, bag0, sv0, start,
    count[static]) → (carry, (stacked_trees, mvals)). ``nv`` is the
    validation row count (0 = no validation)."""
    key = _fused_static_key(cfg, grower_cfg, n, nfeat, k, nv, metric_name,
                            mesh)
    if key in _FUSED_RUNNERS:
        return _FUSED_RUNNERS[key]

    has_valid = nv > 0
    rf_mode = cfg.boosting_type == "rf"
    is_ranking = cfg.objective == "lambdarank"
    grow_fn = _make_grow_fn(grower_cfg, mesh)
    if not is_ranking:
        obj = get_objective(cfg.objective, num_class=max(k, 1),
                            sigmoid=cfg.sigmoid, alpha=cfg.alpha,
                            fair_c=cfg.fair_c,
                            poisson_max_delta_step=cfg.poisson_max_delta_step,
                            tweedie_variance_power=cfg.tweedie_variance_power)

    def body_for(args):
        (binned, yj, wj, valid_mask, key0, is_cat, mono, nan_bins, cat_nbins,
         base_k, gidx, binned_v, yv_j, wv_j, gidx_v) = args
        if not jnp.issubdtype(key0.dtype, jax.dtypes.prng_key):
            key0 = jax.random.wrap_key_data(key0)   # multi-process raw key
        if is_ranking:
            obj_l = lambdarank_objective(gidx, cfg.sigmoid,
                                         cfg.lambdarank_truncation_level,
                                         cfg.label_gain)
            gh_fn, transform = obj_l.grad_hess, (lambda sc: sc)
        else:
            gh_fn, transform = obj.grad_hess, obj.transform

        def body(carry, it):
            score_c, in_bag_c, score_v_c = carry
            g, h = gh_fn(score_c[:, 0] if k == 1 else score_c, yj, wj)
            g = jnp.reshape(g, (n, k))
            h = jnp.reshape(h, (n, k))
            in_bag, g, h, in_bag_c = _sample_rows_impl(
                cfg, n, key0, valid_mask, it, g, h, in_bag_c, yj)
            feat_mask = _sample_features_impl(cfg, nfeat, key0, it)
            cls_trees = []
            for cls in range(k):
                tree, node = grow_fn(binned, g[:, cls], h[:, cls], in_bag,
                                     feat_mask, is_cat, mono, nan_bins,
                                     _node_key_data(key0, it, cls), cat_nbins)
                cls_trees.append(tree)
                if not rf_mode:
                    score_c = score_c.at[:, cls].add(
                        _leaf_gather(tree.leaf_value, node))
                if has_valid:
                    leaf_v = _tree_assign_binned(tree, binned_v, nan_bins)
                    score_v_c = score_v_c.at[:, cls].add(
                        jnp.asarray(tree.leaf_value)[leaf_v])
            stacked = jax.tree.map(lambda *x: jnp.stack(x), *cls_trees)
            if has_valid:
                # rf averages the trees grown so far
                raw_v = (score_v_c if not rf_mode else
                         base_k[None, :]
                         + (score_v_c - base_k[None, :])
                         / (it + 1).astype(jnp.float32))
                pred_v = transform(raw_v[:, 0] if k == 1 else raw_v)
                if _is_rank_metric(metric_name):
                    at = (int(metric_name.split("@")[1])
                          if "@" in metric_name else 5)
                    if metric_name.startswith("map"):
                        mval = map_at_k(yv_j, raw_v[:, 0], gidx_v, at)
                    else:
                        mval = ndcg_at_k(yv_j, raw_v[:, 0], gidx_v, at,
                                         cfg.label_gain)
                else:
                    mval = METRICS[metric_name](yv_j, pred_v, weight=wv_j,
                                                **metric_kwargs(cfg))
            else:
                mval = jnp.float32(0)
            return (score_c, in_bag_c, score_v_c), (stacked, mval)

        return body

    @functools.partial(jax.jit, static_argnames=("count",))
    def run_scan(binned, yj, wj, valid_mask, key0, is_cat, mono, nan_bins,
                 cat_nbins, base_k, gidx, binned_v, yv_j, wv_j, gidx_v,
                 score0,
                 bag0, sv0, start, count):
        body = body_for((binned, yj, wj, valid_mask, key0, is_cat, mono,
                         nan_bins, cat_nbins, base_k, gidx, binned_v, yv_j,
                         wv_j, gidx_v))
        return lax.scan(body, (score0, bag0, sv0),
                        start + jnp.arange(count, dtype=jnp.int32))

    if len(_FUSED_RUNNERS) > 16:
        # LRU-ish: evict the oldest entry, keep hot executables (a full clear
        # would force minute-scale recompiles under config churn)
        _FUSED_RUNNERS.pop(next(iter(_FUSED_RUNNERS)))
    _FUSED_RUNNERS[key] = run_scan
    return run_scan


def _tree_assign_binned(tree: TreeArrays, binned, nan_bins=None) -> jnp.ndarray:
    """Leaf assignment of (already-binned) rows for one tree — used for
    validation-score streaming updates."""
    f = Forest(split_feature=tree.split_feature[None], threshold=jnp.zeros_like(
        tree.split_gain)[None], split_bin=tree.split_bin[None],
        split_type=tree.split_type[None], default_left=tree.default_left[None],
        cat_bitset=tree.cat_bitset[None],
        left_child=tree.left_child[None], right_child=tree.right_child[None],
        leaf_value=tree.leaf_value[None])
    return forest_predict(f, binned, binned=True, output="leaf",
                          nan_bins=nan_bins)[:, 0]


def train_booster(
    X: np.ndarray,
    y: np.ndarray,
    config: BoosterConfig,
    sample_weight: Optional[np.ndarray] = None,
    init_score: Optional[np.ndarray] = None,
    categorical_features: Optional[Sequence[int]] = None,
    group_sizes: Optional[np.ndarray] = None,
    valid: Optional[tuple] = None,            # (Xv, yv) or (Xv, yv, wv, group_sizes_v) for ranking
    fobj: Optional[Callable] = None,          # custom objective (FObjTrait analog)
    feature_names: Optional[List[str]] = None,
    init_model: Optional[Booster] = None,     # warm start (modelString param analog)
    callbacks: Optional[List[Callable]] = None,
    mapper: Optional[BinMapper] = None,       # pre-computed reference dataset analog
    mesh=None,                                # jax.sharding.Mesh: shard rows over DATA_AXIS
    measures=None,                            # InstrumentationMeasures (§5.1)
    checkpoint_store=None,                    # CheckpointStore or directory path
    checkpoint_every: int = 0,                # snapshot every K iterations (0 = default 10)
    resume: bool = True,                      # continue from the newest matching snapshot
) -> Booster:
    from ..core.logging import InstrumentationMeasures

    if measures is None:
        measures = InstrumentationMeasures()
    cfg = config
    # out-of-core route: a StreamedDataset carries its own labels/weights and
    # trains through the chunk-streamed level-synchronous grower
    # (gbdt/stream.py — local import: stream imports this module)
    from .stream import StreamedDataset, train_booster_streamed

    if isinstance(X, StreamedDataset):
        unsupported = [name for name, v in [
            ("y", y), ("sample_weight", sample_weight),
            ("init_score", init_score), ("group_sizes", group_sizes),
            ("fobj", fobj), ("init_model", init_model),
            ("callbacks", callbacks or None)]
            if v is not None]
        if unsupported:
            raise NotImplementedError(
                f"train_booster(StreamedDataset) does not take {unsupported}"
                " — labels/weights ride the stream; the other features are "
                "resident-path only (see gbdt/stream.py)")
        if mapper is not None and X.mapper is None:
            X.mapper = mapper
            X._user_mapper = True
        if categorical_features is not None and X.categorical_features is None:
            X.categorical_features = list(categorical_features)
        return train_booster_streamed(
            X, config, mesh=mesh, valid_data=valid, measures=measures,
            checkpoint_store=checkpoint_store,
            checkpoint_every=checkpoint_every, resume=resume,
            feature_names=feature_names)
    # --- crash-safe snapshots (core/checkpoint.py): periodic forest + loop
    # state, resumable bit-for-bit because all per-iteration sampling is
    # stateless fold_in(seed, it) and the carried score is saved exactly
    ckpt_store = checkpoint_store
    if isinstance(ckpt_store, str):
        from ..core.checkpoint import CheckpointStore

        ckpt_store = CheckpointStore(ckpt_store)
    if ckpt_store is not None and checkpoint_every <= 0:
        checkpoint_every = 10
    # multi-process snapshots: the carry is gathered to host on every rank
    # (_pack_gbdt_carry is collective) and committed by rank 0 through a
    # shared checkpoint directory; snapshots are trimmed to the original
    # unpadded global rows so a shrunken/regrown mesh can resume them
    # (parallel/elastic.py consensus restart path)
    if _is_sparse(X):
        if mesh is not None or init_model is not None:
            # these paths need raw dense rows anyway (padding / rescoring) and
            # would discard a pre-binned matrix — densify once, skip the wrap
            X = _densify(X)
        else:
            # scipy CSR/CSC rows: bin chunk-wise through the sparse Dataset
            # path (the reference's isSparse election, BulkPartitionTask CSR)
            X = Dataset(X, mapper=mapper, max_bin=cfg.max_bin,
                        bin_sample_count=cfg.bin_sample_count,
                        categorical_features=categorical_features,
                        seed=cfg.seed, min_data_in_bin=cfg.min_data_in_bin,
                        max_bin_by_feature=cfg.max_bin_by_feature)
    # LightGBM Dataset analog: pre-binned device-resident data skips the
    # quantization pass and the raw-float host→device transfer entirely
    dataset = X if isinstance(X, Dataset) else None
    prebinned = None
    if dataset is not None:
        if y is None:
            y = dataset.label
        if y is None:
            raise ValueError("no label: pass y explicitly or build the "
                             "Dataset with label=...")
        if sample_weight is None:
            sample_weight = dataset.weight
        if init_score is None:
            init_score = dataset.init_score
        if group_sizes is None:
            group_sizes = dataset.group_sizes
        if categorical_features is None:
            categorical_features = dataset.categorical_features
        ds_binning = (getattr(dataset, "min_data_in_bin", 3),
                      tuple(dataset.max_bin_by_feature)
                      if getattr(dataset, "max_bin_by_feature", None) else None)
        cfg_binning = (cfg.min_data_in_bin,
                       tuple(cfg.max_bin_by_feature)
                       if cfg.max_bin_by_feature else None)
        if (ds_binning != cfg_binning and mapper is None
                and not getattr(dataset, "_user_mapper", False)):
            # (an explicit user mapper defines the binning outright — the
            # Dataset's unused binning knobs cannot conflict with anything)
            raise ValueError(
                f"Dataset was binned with (min_data_in_bin, max_bin_by_feature)"
                f"={ds_binning} but the config asks for {cfg_binning}; rebuild "
                "the Dataset with matching binning params")
        if mapper is not None and mapper is not dataset.mapper:
            # explicit conflicting mapper (reference-dataset warm-start style):
            # the pre-binned ids were assigned under dataset.mapper's
            # boundaries, so fall back to re-binning the raw rows under the
            # user's mapper rather than decoding splits against the wrong one
            pass
        else:
            mapper = dataset.mapper
            if init_model is None and (mesh is None
                                       or jax.process_count() == 1):
                # fast path: reuse the binned matrix. Warm start still needs
                # raw rows (init-model rescoring); single-process mesh pads
                # the BINNED rows below, so streamed datasets (from_batches:
                # raw floats never kept) shard across a mesh too. Multi-
                # process keeps the raw path (global ingest re-stages rows).
                prebinned = dataset.binned
        if prebinned is not None:
            # shape-only placeholder when no dense raw rows are held (sparse
            # or keep_raw=False): broadcast view, zero memory, never read
            X = (dataset.X if dataset.X is not None
                 else np.broadcast_to(np.float32(0.0), dataset.shape))
        else:
            X = dataset.raw_dense()
            if X is None:
                raise ValueError("Dataset was built with keep_raw=False; this "
                                 "training path (mesh / warm start) needs raw "
                                 "rows")
    X = np.asarray(X, np.float32)
    y = np.asarray(y, np.float32)
    if X.ndim != 2 or X.shape[0] == 0:
        raise ValueError(f"training data must be a non-empty 2-D matrix, got shape {X.shape}")
    if len(y) != X.shape[0]:
        raise ValueError(f"label length {len(y)} != row count {X.shape[0]}")
    n_orig, nfeat = X.shape
    w = (np.ones(n_orig, np.float32) if sample_weight is None
         else np.asarray(sample_weight, np.float32))
    rng = np.random.default_rng(cfg.seed)

    multiproc = mesh is not None and jax.process_count() > 1
    if mapper is None and not multiproc:
        # sampling + bin-boundary phase (reference: samplingParameters /
        # columnStatistics spans in LightGBMPerformance.scala); the multiproc
        # path instead samples across ALL processes below
        with measures.span("referenceDataset"):
            mapper = compute_bin_mapper(
                X, cfg.max_bin, cfg.bin_sample_count, categorical_features,
                (cfg.seed if cfg.data_random_seed is None
                 else int(cfg.data_random_seed)),
                min_data_in_bin=cfg.min_data_in_bin,
                max_bin_by_feature=cfg.max_bin_by_feature)
    if mapper is not None and mapper.max_bin != cfg.max_bin:
        # every mapper source (Dataset, explicit mapper=, warm start) funnels
        # through here: bin ids outside the grower's num_bins range would
        # silently drop from histograms, so a mismatch is an error
        raise ValueError(
            f"bin mapper has max_bin={mapper.max_bin} but config.max_bin="
            f"{cfg.max_bin}; rebuild the Dataset/mapper with the matching "
            "max_bin")

    # Multi-PROCESS (multi-host) mode: X/y are THIS process's row shard of one
    # global mesh; bin boundaries broadcast from process 0 so every host bins
    # identically, and all row arrays are assembled into global sharded arrays
    # (the reference's distributed mode instead rendezvouses a socket ring).
    if multiproc:
        unsupported = [name for name, v in [
            ("fobj", fobj), ("callbacks", callbacks or None),
            ("init_model", init_model), ("valid", valid),
            ("init_score", init_score), ("group_sizes", group_sizes)]
            if v is not None]
        if unsupported or cfg.boosting_type == "dart" \
                or cfg.tree_learner in ("voting", "feature"):
            raise NotImplementedError(
                "multi-process training currently supports the fused path "
                f"only (gbdt/goss/rf, serial learner); got {unsupported or cfg}")
        from jax.experimental import multihost_utils

        from ..parallel.mesh import (assert_equal_across_processes,
                                     local_mesh_devices)

        local_mesh_devices(mesh)        # mesh must span every process evenly
        assert_equal_across_processes((n_orig, nfeat),
                                      "local row count / feature count")
        if mapper is None:
            # bin boundaries from a sample gathered across ALL processes (the
            # reference samples across all partitions on the driver,
            # LightGBMBase.getSampledRows); deterministic on the gathered
            # union, so every process computes the identical mapper
            per = max(1, min(n_orig,
                             -(-cfg.bin_sample_count // jax.process_count())))
            sub = np.random.default_rng(cfg.seed).choice(
                n_orig, size=per, replace=False)
            gathered = np.asarray(multihost_utils.process_allgather(
                np.ascontiguousarray(X[np.sort(sub)])))
            X_samp = gathered.reshape(-1, X.shape[1])
            # NaN election over the FULL global matrix, not just the sample
            local_nan = np.ascontiguousarray(np.isnan(X).any(axis=0)[None])
            has_nan_g = np.asarray(multihost_utils.process_allgather(
                local_nan)).reshape(-1, X.shape[1]).any(axis=0)
            # categorical bin occupancy over the FULL global matrix: local
            # presence bitmaps OR-reduced across processes (maxCatToOnehot
            # must not depend on which rows the boundary sample drew)
            cat_presence_g = None
            if categorical_features:
                from ..ops.quantize import cat_presence_bitmap

                pres_l = np.zeros((X.shape[1], cfg.max_bin), np.uint8)
                for cj in categorical_features:
                    pres_l[cj] = cat_presence_bitmap(X[:, cj], cfg.max_bin)
                cat_presence_g = np.asarray(multihost_utils.process_allgather(
                    pres_l[None])).reshape(-1, X.shape[1], cfg.max_bin).any(0)
            mapper = compute_bin_mapper(
                X_samp, cfg.max_bin, cfg.bin_sample_count,
                categorical_features, cfg.seed, has_nan=has_nan_g,
                min_data_in_bin=cfg.min_data_in_bin,
                max_bin_by_feature=cfg.max_bin_by_feature,
                cat_presence=cat_presence_g)
        else:
            bnd, nb_, cat_, hn_ = multihost_utils.broadcast_one_to_all(
                (mapper.boundaries, np.asarray(mapper.num_bins),
                 np.asarray(mapper.is_categorical),
                 np.asarray(mapper.nan_mask)))
            # NaNs on ANY process must have a dedicated bin in the broadcast
            # mapper — a local mapper that never saw them would silently route
            # those NaNs into the last real-value bin
            any_nan = np.asarray(multihost_utils.process_allgather(
                np.ascontiguousarray(np.isnan(X).any(axis=0)[None]))
                ).reshape(-1, X.shape[1]).any(axis=0)
            if (any_nan & ~np.asarray(hn_)).any():
                raise ValueError(
                    "explicit mapper lacks NaN bins for features with missing "
                    "values on some process; pass mapper=None so boundaries "
                    "are sampled across all processes")
            mapper = BinMapper(boundaries=np.asarray(bnd),
                               num_bins=np.asarray(nb_),
                               is_categorical=np.asarray(cat_),
                               max_bin=mapper.max_bin,
                               has_nan=np.asarray(hn_))


    # Multi-chip: pad rows to the data-axis size and shard. The padding rows get
    # in_bag = 0, so they contribute nothing to histograms or leaf stats; GSPMD
    # then turns the histogram scatter into per-shard partials + one psum over
    # ICI — the entire replacement for LightGBM's socket-ring allreduce.
    valid_mask_np = np.ones(n_orig, np.float32)
    if mesh is not None:
        from ..parallel.mesh import DATA_AXIS as _DA
        ndata = mesh.shape[_DA]
        if multiproc:
            # local rows pad to the per-process shard multiple; every process
            # must contribute equally-sized shards
            nproc = jax.process_count()
            if ndata % nproc:
                raise ValueError(f"data axis ({ndata}) must divide evenly "
                                 f"across {nproc} processes")
            ndata = ndata // nproc
        rem = (-n_orig) % ndata
        if rem:
            if prebinned is not None:
                # pad the BINNED rows directly (in_bag=0 keeps padding out
                # of every histogram); the raw-X placeholder stays a
                # zero-memory broadcast view at the new length
                pb = np.asarray(prebinned)
                prebinned = np.concatenate(
                    [pb, np.repeat(pb[-1:], rem, axis=0)])
                X = np.broadcast_to(np.float32(0.0),
                                    (n_orig + rem, X.shape[1]))
            else:
                X = np.concatenate([X, np.repeat(X[-1:], rem, axis=0)])
            y = np.concatenate([y, np.zeros(rem, np.float32)])
            w = np.concatenate([w, np.zeros(rem, np.float32)])
            valid_mask_np = np.concatenate([valid_mask_np, np.zeros(rem, np.float32)])
            if init_score is not None:
                init_score = np.concatenate(
                    [np.asarray(init_score), np.zeros(rem, np.float32)])
    n = X.shape[0]
    # the span closes when the binned matrix is ready on the device(s), not
    # when its binning is dispatched
    with measures.span("dataPreparation"):
        binned = (prebinned if prebinned is not None
                  else _bin_on_device(mapper, X, measures))
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P
            from ..parallel.mesh import DATA_AXIS as _DA
            row2 = NamedSharding(mesh, P(_DA, None))
            row1 = NamedSharding(mesh, P(_DA))
            with measures.span("shardRows"):
                if multiproc:
                    from ..parallel.mesh import to_global_rows
                    binned = to_global_rows(mesh, P(_DA, None),
                                            np.asarray(binned))
                    n = n * jax.process_count()   # n is GLOBAL from here on
                else:
                    binned = jax.device_put(binned, row2)
                jax.block_until_ready(binned)

    # objective, and the per-row state on the device; the base score's
    # np.asarray waits for the device, so the span ends with the work done
    with measures.span("objectiveSetup"):
        k = cfg.num_class if cfg.objective in ("multiclass", "softmax", "multiclassova") else 1
        # lambdarank group index; 1-length dummy otherwise (it would replicate at
        # GLOBAL length onto every device in multi-process mode)
        gidx_arr = (np.zeros(1, np.int32) if multiproc else jnp.zeros(1, jnp.int32))
        if cfg.objective == "lambdarank":
            if group_sizes is None:
                raise ValueError("lambdarank requires group_sizes")
            if cfg.label_gain:
                max_label = int(np.max(y)) if len(y) else 0
                if max_label >= len(cfg.label_gain):
                    # LightGBM fails fast here too ("Label ... is not less than
                    # the number of label gains") — silent clipping would
                    # optimize the wrong objective
                    raise ValueError(
                        f"label {max_label} needs a label_gain table of at "
                        f"least {max_label + 1} entries, got "
                        f"{len(cfg.label_gain)}")
            gidx = make_grouped(y, group_sizes)
            gidx_arr = jnp.asarray(gidx)
            obj = lambdarank_objective(gidx_arr, cfg.sigmoid,
                                       cfg.lambdarank_truncation_level,
                                       cfg.label_gain)
        else:
            obj = get_objective(cfg.objective, num_class=k, sigmoid=cfg.sigmoid,
                                alpha=cfg.alpha, fair_c=cfg.fair_c,
                                poisson_max_delta_step=cfg.poisson_max_delta_step,
                                tweedie_variance_power=cfg.tweedie_variance_power)

        if ((cfg.pos_bagging_fraction < 1.0 or cfg.neg_bagging_fraction < 1.0)
                and cfg.objective not in ("binary",)):
            # native LightGBM rejects stratified bagging for non-binary objectives
            raise ValueError("pos_bagging_fraction / neg_bagging_fraction require "
                             f"objective='binary' (got {cfg.objective!r})")
        if cfg.boosting_type == "rf" and not (cfg.bagging_freq > 0 and cfg.bagging_fraction < 1.0
                                              or cfg.feature_fraction < 1.0):
            # native LightGBM rejects the same degenerate config (identical trees)
            raise ValueError("boosting_type='rf' requires bagging (bagging_freq > 0 and "
                             "bagging_fraction < 1) and/or feature_fraction < 1")

        if multiproc:
            from jax.sharding import PartitionSpec as P

            from ..parallel.mesh import to_global_rows

            yj = to_global_rows(mesh, P(_DA), y)
            wj = to_global_rows(mesh, P(_DA), w)
            valid_mask = to_global_rows(mesh, P(_DA), valid_mask_np)
            if cfg.boost_from_average:
                # base score from GLOBAL label stats: jit over the sharded labels
                # inserts the cross-process reductions (one-shot per fit, so the
                # throwaway jit wrapper is deliberate)
                base_g = jax.jit(obj.init_score,  # lint-ok: recompile
                                 out_shardings=NamedSharding(mesh, P()))(yj, wj)
                base = np.atleast_1d(np.asarray(jax.device_get(base_g), np.float64))
            else:
                base = np.zeros(max(k, 1))
            local_margin = (np.zeros((len(y), k), np.float32)
                            + base[None, :k].astype(np.float32))
            score = to_global_rows(mesh, P(_DA, None), local_margin)
        else:
            yj, wj = jnp.asarray(y), jnp.asarray(w)
            valid_mask = jnp.asarray(valid_mask_np)
            base = (np.atleast_1d(np.asarray(obj.init_score(yj, wj), np.float64))
                    if cfg.boost_from_average else np.zeros(max(k, 1)))
            # the fixed margin every iteration starts from: base score + init_score
            init_margin = jnp.zeros((n, k)) + jnp.asarray(base[None, :k], jnp.float32)
            if init_score is not None:
                init_margin = init_margin + jnp.asarray(
                    np.asarray(init_score).reshape(n, -1), jnp.float32)
            score = init_margin
            if mesh is not None:
                score = jax.device_put(score, row2)
                yj = jax.device_put(yj, row1)
                wj = jax.device_put(wj, row1)
                valid_mask = jax.device_put(valid_mask, row1)

    trees: List[TreeArrays] = []
    tree_weights: List[float] = []
    # dart only: per-tree train contribution, stored as (class, (N,) values)
    tree_contribs: List[tuple] = []
    # warm start: the continued model bins against a NEW mapper, so the init
    # trees' real-valued thresholds / missing codes must be resolved against
    # the INIT model's own mapper (or its parsed values) and carried verbatim;
    # newly grown trees get None slots (= resolve from the training mapper)
    init_thresholds: Optional[List] = None
    init_mtypes: Optional[List] = None
    if init_model is not None:
        trees = list(init_model.trees)
        tree_weights = list(init_model.tree_weights)
        base = init_model.base_score
        prior_k = init_model.models_per_iter
        init_thresholds = [init_model._thresholds(i)
                           for i in range(len(trees))]
        init_mtypes = [init_model._missing_types(i)
                       for i in range(len(trees))]
        score = jnp.asarray(
            init_model.raw_score(X, start_iteration=0).reshape(n, k),
            jnp.float32)
        init_margin = jnp.zeros((n, k)) + jnp.asarray(
            init_model.base_score[None, :k], jnp.float32)
        if init_score is not None:
            extra = jnp.asarray(np.asarray(init_score).reshape(n, -1), jnp.float32)
            score = score + extra
            init_margin = init_margin + extra
        if cfg.boosting_type == "dart":
            # warm-started DART needs per-tree contributions of the PRIOR trees
            # too (they are drop candidates); recover them by raw traversal with
            # weights divided back out
            from .grower import forest_predict as _fp

            unweighted = init_model.unweighted()
            uf = unweighted.forest()
            per_tree = np.asarray(_fp(uf, jnp.asarray(X), output="per_tree",
                                      depth=unweighted._depth_cache))  # (N, T)
            for ti in range(per_tree.shape[1]):
                tree_contribs.append((ti % prior_k, per_tree[:, ti].astype(np.float32)))
    n_init_trees = len(trees)

    # tree_learner routing happens BEFORE the grower config is derived: the
    # resolved learner decides the grower's reduction strategy (feature-
    # parallel = owned-feature reduce-scatter). The resolved value lands on
    # cfg for provenance (as the old cost-model block did) and the router's
    # inputs/decision land in Booster.metadata["routing"].
    has_cat = bool(mapper.is_categorical.any())
    # decision provenance for the learned auto-configuration layer
    # (core/perfmodel): every model-made choice — and every fallback — is
    # auditable from Booster.metadata["autoconfig"]
    autoconfig_info = {}
    _fit_t0 = _time.perf_counter()
    if cfg.hist_allreduce_dtype == "auto":
        from .grower import resolve_wire_dtype

        wd, wdec = resolve_wire_dtype(cfg, mesh, n, nfeat)
        cfg.hist_allreduce_dtype = wd
        autoconfig_info["wire_dtype"] = wdec.provenance()
    routing_info = None
    if cfg.tree_learner == "auto":
        choice, routing_info = _auto_route(cfg, mesh, binned, nfeat, n,
                                           multiproc, has_cat)
        cfg.tree_learner = choice
    feature_shards = 1
    if cfg.tree_learner == "feature" and mesh is not None:
        from ..parallel.mesh import DATA_AXIS as _DAf

        feature_shards = int(dict(mesh.shape).get(_DAf, 1))
        from ..ops.hist_kernel import features_padded as _fpad

        if feature_shards > 1 and _fpad(nfeat) % feature_shards:
            # an elastic shrink/regrow can change the data-axis size after a
            # previous call routed this cfg to feature-parallel; the owned-
            # feature scatter needs the padded feature count to divide evenly,
            # so degrade to data-parallel histograms rather than raising at
            # trace time mid-restart
            import warnings

            warnings.warn(
                f"tree_learner='feature': features_padded({nfeat})="
                f"{_fpad(nfeat)} is not divisible by the {feature_shards}-way "
                f"data axis of this mesh; falling back to data-parallel "
                f"histograms")
            cfg.tree_learner = "data"
            feature_shards = 1
            if routing_info is not None:
                routing_info = dict(routing_info, tree_learner="data",
                                    fallback="feature_shards_indivisible")
    grower_cfg = cfg.grower(has_categorical=has_cat,
                            feature_shards=feature_shards)
    _wrap = np.asarray if multiproc else jnp.asarray
    is_cat = _wrap(mapper.is_categorical)
    nan_bins = _wrap(np.asarray(mapper.nan_bins, np.int32))
    # static per-feature DISTINCT category counts drive the one-vs-rest
    # decision (sparse id encodings make num_bins an overcount; fall back to
    # it for mappers predating cat_counts)
    _cc = (np.asarray(mapper.cat_counts, np.int32)
           if getattr(mapper, "cat_counts", None) is not None
           else np.asarray(mapper.num_bins, np.int32) - 1)
    cat_nbins = _wrap(np.where(np.asarray(mapper.is_categorical), _cc,
                               np.int32(0x7FFF)))
    mono = np.zeros(nfeat, np.int32)
    if cfg.monotone_constraints is not None:
        mc = np.asarray(cfg.monotone_constraints, np.int32)
        mono[: len(mc)] = mc
    mono = _wrap(mono)

    grow_fn = _make_grow_fn(grower_cfg, mesh)

    # validation state
    has_valid = valid is not None
    if has_valid:
        Xv = np.asarray(_densify(valid[0]), np.float32)
        yv = np.asarray(valid[1], np.float32)
        binned_v = apply_bins(mapper, Xv)
        score_v = jnp.zeros((Xv.shape[0], k)) + jnp.asarray(base[None, :k], jnp.float32)
        if init_model is not None:
            score_v = jnp.asarray(
                init_model.raw_score(Xv, start_iteration=0).reshape(
                    Xv.shape[0], k), jnp.float32)
        metric_name = cfg.metric or _default_metric(cfg.objective)
        if metric_name in ("ndcg", "map") or (
                cfg.metric is None and metric_name.startswith("ndcg")):
            # evalAt (LightGBMRankerParams, default 1-5) sets the ndcg/map
            # eval positions; early stopping tracks the FIRST position,
            # matching the reference. Engine-level configs that never set
            # eval_at keep the max_position behavior.
            first_at = (cfg.eval_at[0] if cfg.eval_at else cfg.max_position)
            metric_name = f"{metric_name.split('@')[0]}@{int(first_at)}"
        best_metric, best_iter = None, -1
        higher_better = metric_name.split("@")[0] in HIGHER_IS_BETTER
        # dart/rf: per-tree validation contributions (weights change later)
        valid_contribs: List[tuple] = []
        if init_model is not None and cfg.boosting_type in ("dart", "rf"):
            unw = init_model.unweighted()
            uf_v = unw.forest()
            pt_v = forest_predict(uf_v, jnp.asarray(Xv), output="per_tree",
                                  depth=unw._depth_cache)   # (Nv, T)
            pk = init_model.models_per_iter
            for ti in range(pt_v.shape[1]):
                valid_contribs.append((ti % pk, pt_v[:, ti]))

    gh_fn = fobj if fobj is not None else obj.grad_hess
    rf_mode, dart_mode, goss_mode = (cfg.boosting_type == "rf", cfg.boosting_type == "dart",
                                     cfg.boosting_type == "goss")
    if multiproc:
        from jax.sharding import PartitionSpec as P
        from ..parallel.mesh import DATA_AXIS as _DA2

        from ..parallel.mesh import to_global_rows as _tgr

        in_bag_cur = _tgr(
            mesh, P(_DA2), np.ones(n // jax.process_count(), np.float32))
    else:
        in_bag_cur = jnp.ones(n, jnp.float32)

    # ------------------------------------------------------------------
    # Fused fast path: the WHOLE boosting loop is one lax.scan under one
    # jit — a single device dispatch for all iterations. The reference's
    # loop is one LGBM_BoosterUpdateOneIter native call per iteration
    # (TrainUtils.scala:98-135); the fused program leaves the host out of
    # the loop entirely.
    # dart / custom fobj / callbacks / warm start keep the host loop.
    # ------------------------------------------------------------------
    fused = (fobj is None and not callbacks and init_model is None
             and cfg.boosting_type in ("gbdt", "goss", "rf")
             and cfg.tree_learner != "voting")

    # per-iteration sampling — ONE device-side implementation shared by the
    # fused scan and the host loop (GOSS top-|g| + amplified rest; bagging;
    # feature_fraction), all keyed off fold_in(seed, it) so both paths sample
    # identically
    key0 = jax.random.PRNGKey(cfg.seed)
    if multiproc:
        # raw key data (identical host value on every process -> replicated);
        # run_scan re-wraps it into a typed key
        key0 = np.asarray(jax.random.key_data(key0))

    def sample_rows(it, g, h, in_bag_cur):
        return _sample_rows_impl(cfg, n, key0, valid_mask, it, g, h,
                                 in_bag_cur, yj)

    def sample_features(it):
        return _sample_features_impl(cfg, nfeat, key0, it)

    if fused:
        T = cfg.num_iterations
        nv = Xv.shape[0] if has_valid else 0
        run_scan = _get_fused_runner(cfg, grower_cfg, n, nfeat, k, nv,
                                     metric_name if has_valid else "", mesh)
        base_k = _wrap(np.asarray(base[:k], np.float32))
        if has_valid:
            yv_j = jnp.asarray(yv)
            if _is_rank_metric(metric_name):
                if len(valid) < 4:
                    raise ValueError("ranking validation requires "
                                     "valid=(Xv, yv, wv_or_None, group_sizes_v)")
                gidx_v = jnp.asarray(make_grouped(yv, valid[3]))
            else:
                gidx_v = jnp.zeros(nv, jnp.int32)
            # validation sample weights (valid[2]) weight the POINTWISE
            # eval metrics, as in LightGBM (ndcg/map stay per-query
            # unweighted here); absent -> uniform
            wv_raw = valid[2] if len(valid) > 2 else None
            wv_j = (jnp.asarray(np.asarray(wv_raw, np.float32))
                    if wv_raw is not None else jnp.ones(nv, jnp.float32))
            bv_arg = binned_v
        else:
            zeros = np.zeros if multiproc else jnp.zeros
            yv_j = zeros(1, np.float32)
            wv_j = zeros(1, np.float32)
            gidx_v = zeros(1, np.int32)
            bv_arg = zeros((1, nfeat), binned.dtype)

        score_v0 = (score_v if has_valid
                    else (np.zeros((1, k), np.float32) if multiproc
                          else jnp.zeros((1, k))))

        # With early stopping the scan runs in chunks with a host-side stop
        # check between them, so a run that converges at iteration 40 does
        # not burn the full num_iterations on device.
        chunk = T
        if has_valid and cfg.early_stopping_round > 0:
            chunk = min(T, max(2 * cfg.early_stopping_round, 16))
        carry = (score, in_bag_cur, score_v0)
        mvals_list = []
        done = 0
        if ckpt_store is not None:
            from ..core.checkpoint import preemption_point

            # snapshot boundaries must fall on chunk boundaries (the carry is
            # only exact between scan invocations)
            chunk = min(chunk, max(1, checkpoint_every))
            n_fp, y_fp = _elastic_label_identity(y, n_orig, multiproc)
            fingerprint = _train_fingerprint(cfg, n_fp, nfeat, y_fp,
                                             n_init_trees)
            state = _ckpt_load_gbdt(ckpt_store, fingerprint, "fused") \
                if resume else None
            if state is not None:
                done = int(state["iteration"])
                trees = list(state["trees"])
                tree_weights = list(state["tree_weights"])
                mvals_list = [np.asarray(m) for m in state["mvals"]]
                carry = _place_gbdt_carry(
                    state["carry"], n, n_orig, mesh, multiproc,
                    row2 if mesh is not None else None,
                    row1 if mesh is not None else None, score_v0)
        moved_by = split_counter(grower_cfg, nfeat)
        with measures.span("trainingIterations"):
            wd = current_watchdog()
            while done < T:
                if ckpt_store is not None:
                    preemption_point("gbdt.chunk", done)
                if wd is not None:
                    wd.beat("gbdt.chunk", done)
                c = min(chunk, T - done)

                def _run_chunk(_d=done, _c=c):
                    # the wait INSIDE the guard: it is the host sync point
                    # where a hung peer's psum would stall forever
                    with measures.span("scanRun"):
                        return jax.block_until_ready(run_scan(
                            binned, yj, wj, valid_mask, key0, is_cat, mono,
                            nan_bins, cat_nbins, base_k, gidx_arr, bv_arg,
                            yv_j, wv_j, gidx_v, *carry, _d, _c))

                if wd is not None:
                    carry, (stacked_trees, mv) = wd.run(
                        _run_chunk, op="gbdt.chunk")
                else:
                    carry, (stacked_trees, mv) = _run_chunk()
                with measures.span("treesReadback"):
                    stacked_trees = jax.device_get(stacked_trees)
                    for ti in range(c):
                        for cls in range(k):
                            trees.append(jax.tree.map(lambda a: a[ti, cls],
                                                      stacked_trees))
                            tree_weights.append(1.0)
                if moved_by:
                    measures.count(moved_by,
                                   int(stacked_trees.num_splits.sum()))
                done += c
                stop = False
                if has_valid:
                    mvals_list.append(np.asarray(mv))
                    if cfg.early_stopping_round > 0:
                        series = np.concatenate(mvals_list)
                        series = series if higher_better else -series
                        b = _best_so_far(series, cfg.improvement_tolerance)
                        stop = (done - 1 - int(b[-1])
                                >= cfg.early_stopping_round)
                if ckpt_store is not None and (done >= T or not stop):
                    # pack is collective (all ranks); only rank 0 commits to
                    # the (shared) store — one writer, no torn races
                    carry_h = _pack_gbdt_carry(carry, n, n_orig, multiproc)
                    if not multiproc or jax.process_index() == 0:
                        _ckpt_save_gbdt(
                            ckpt_store, done,
                            {"iteration": done, "trees": trees,
                             "tree_weights": tree_weights,
                             "mvals": mvals_list, "carry": carry_h,
                             "n_orig": n_fp},
                            fingerprint, "fused", measures)
                if stop:
                    break
        score = carry[0]
        measures.count("iterations", done)
        with measures.span("modelAssembly"):
            best_iter = -1
            if has_valid:
                mvals = np.concatenate(mvals_list)
                tdone = len(mvals)
                series = mvals if higher_better else -mvals
                # earliest best index (LightGBM keeps the first best)
                bests = _best_so_far(series, cfg.improvement_tolerance)
                stop = tdone - 1
                if cfg.early_stopping_round > 0:
                    waited = np.arange(tdone) - bests
                    hit = np.nonzero(waited >= cfg.early_stopping_round)[0]
                    if len(hit):
                        stop = int(hit[0])
                best_iter = int(bests[stop])
                best_metric = float(mvals[best_iter])
                if cfg.early_stopping_round > 0:
                    cut = (best_iter + 1) * k
                    trees = trees[:cut]
                    tree_weights = tree_weights[:cut]

            return Booster(mapper, cfg, trees, tree_weights, base, feature_names,
                           best_iteration=(best_iter if has_valid else -1),
                           best_score=(best_metric if has_valid else None),
                           metadata=_train_metadata(routing_info,
                                                    autoconfig_info, _fit_t0))

    # validation weights converted to device ONCE (per-iteration eval would
    # otherwise redo the H2D transfer every round)
    wv_dev = None
    if has_valid and len(valid) > 2 and valid[2] is not None:
        wv_dev = jnp.asarray(np.asarray(valid[2], np.float32))
    start_it = 0
    if ckpt_store is not None:
        from ..core.checkpoint import CheckpointError, preemption_point

        # host path is single-process only; fingerprint + snapshots use the
        # original unpadded rows so a resume survives a mesh-shape change
        n_fp, y_fp = _elastic_label_identity(y, n_orig, False)
        fingerprint = _train_fingerprint(cfg, n_fp, nfeat, y_fp, n_init_trees)
        state = _ckpt_load_gbdt(ckpt_store, fingerprint, "host") \
            if resume else None
        if state is not None:
            start_it = int(state["iteration"])
            trees = list(state["trees"])
            tree_weights = list(state["tree_weights"])
            tree_contribs = [(c, jnp.asarray(_repad_rows(v, n)))
                             for c, v in state["tree_contribs"]]
            score = jnp.asarray(_repad_rows(state["score"], n))
            in_bag_cur = jnp.asarray(_repad_rows(state["in_bag_cur"], n))
            if mesh is not None:
                score = jax.device_put(score, row2)
                in_bag_cur = jax.device_put(in_bag_cur, row1)
            # dart's drop decisions come from this stateful host Generator;
            # restoring it is what makes the resumed drop sequence identical
            rng = state["rng"]
            if has_valid:
                sv = np.asarray(state["score_v"], np.float32)
                if sv.shape != tuple(np.shape(score_v)):
                    raise CheckpointError(
                        f"validation score shape changed {sv.shape} -> "
                        f"{tuple(np.shape(score_v))}; resume with the "
                        "original validation set (or pass resume=False)")
                score_v = jnp.asarray(sv)
                valid_contribs = list(state["valid_contribs"])
                best_metric = state["best_metric"]
                best_iter = int(state["best_iter"])
    wd = current_watchdog()
    for it in range(start_it, cfg.num_iterations):
        if ckpt_store is not None:
            preemption_point("gbdt.iteration", it)
        if wd is not None:
            wd.beat("gbdt.iteration", it)
        # ---- dart: drop trees and de-weight the score -------------------
        if dart_mode and trees:
            nt = len(trees)
            # sequence seeding gives independent streams per (drop_seed, it)
            drop_rng = (np.random.default_rng([cfg.drop_seed, it])
                        if cfg.drop_seed else rng)
            if drop_rng.random() >= cfg.skip_drop:
                if cfg.uniform_drop:
                    p = np.full(nt, cfg.drop_rate)
                else:
                    # weighted drop (LightGBM default): drop probability
                    # proportional to each tree's current weight, normalized
                    # so the expected drop count stays drop_rate * nt
                    w = np.asarray(tree_weights[:nt], np.float64)
                    p = np.minimum(cfg.drop_rate * w * nt / max(w.sum(), 1e-12),
                                   1.0)
                drop = np.nonzero(drop_rng.random(nt) < p)[0][: cfg.max_drop]
            else:
                drop = np.array([], np.int64)
            kdrop = len(drop)
            if kdrop:
                # device-side: sum the dropped trees' weighted contributions
                dropped = jnp.zeros((n, k), jnp.float32)
                for j in drop:
                    cls_j, vec = tree_contribs[j]
                    dropped = dropped.at[:, cls_j].add(tree_weights[j] * vec)
                score_it = score - dropped
            else:
                score_it = score
        else:
            score_it, drop, kdrop = score, None, 0

        g, h = gh_fn(score_it[:, 0] if k == 1 else score_it, yj, wj)
        g = jnp.reshape(g, (n, k))
        h = jnp.reshape(h, (n, k))

        # ---- row + feature sampling (shared device-side implementation) --
        in_bag, g, h, in_bag_cur = sample_rows(it, g, h, in_bag_cur)
        feat_mask = sample_features(it)

        # ---- grow K trees ----------------------------------------------
        new_weight = 1.0
        if dart_mode and kdrop:
            if cfg.xgboost_dart_mode:
                # leaf values already carry the learning rate (grower), so
                # the extra multiplier is 1/(k+lr): effective lr/(k+lr), the
                # DART-paper / LightGBM xgboost-mode weight
                new_weight = 1.0 / (kdrop + cfg.learning_rate)
            else:
                new_weight = 1.0 / (kdrop + 1.0)
        # voting-parallel: pick top-2k features per tree by shard votes, grow
        # on the sliced columns so in-loop histogram allreduce is O(top_k)
        # ("auto" resolved to a concrete learner before the fused-path
        # decision above)
        voting = (cfg.tree_learner == "voting" and mesh is not None
                  and nfeat > 2 * cfg.top_k)
        for cls in range(k):
            if voting:
                from .voting import remap_tree_features, voting_select

                sel_idx = voting_select(
                    binned, g[:, cls] * in_bag, h[:, cls] * in_bag, in_bag,
                    mesh, cfg.top_k, cfg.max_bin, cfg.lambda_l2,
                    max(cfg.min_data_in_leaf, 1), feature_active=feat_mask)
                sel_j = jnp.asarray(sel_idx)
                # bynode sampling applies WITHIN the vote winners (the
                # searchable subset — LightGBM ColSampler semantics)
                tree, node = grow_fn(
                    binned[:, sel_j], g[:, cls], h[:, cls], in_bag,
                    feat_mask[sel_j], is_cat[sel_j], mono[sel_j],
                    nan_bins[sel_j], _node_key_data(key0, it, cls),
                    cat_nbins[sel_j])
                tree = remap_tree_features(tree, sel_idx)
            else:
                tree, node = grow_fn(binned, g[:, cls], h[:, cls], in_bag,
                                     feat_mask, is_cat, mono, nan_bins,
                                     _node_key_data(key0, it, cls), cat_nbins)
            contrib = _leaf_gather(tree.leaf_value, node)          # (N,)
            if dart_mode:
                tree_contribs.append((cls, contrib))               # device-side
                if kdrop and cls == k - 1:
                    # dropped trees scaled by kdrop/(kdrop+1), then rebuild the
                    # score from the fixed init margin + all weighted per-tree
                    # contributions — one stacked matvec on device instead of a
                    # host numpy loop (VERDICT weak #7)
                    factor = (kdrop / (kdrop + cfg.learning_rate)
                              if cfg.xgboost_dart_mode
                              else kdrop / (kdrop + 1.0))
                    for j in drop:
                        tree_weights[j] *= factor
                    stack = jnp.stack([v for _, v in tree_contribs])  # (T, N)
                    # THIS iteration's k trees are appended below, after the
                    # rebuild: extend explicitly or the newest contributions
                    # gather stale (clamped) weights
                    wts_now = (tree_weights
                               + [new_weight] * (len(tree_contribs)
                                                 - len(tree_weights)))
                    wts = jnp.asarray(wts_now, jnp.float32)
                    cls_ids = np.asarray([c for c, _ in tree_contribs])
                    total = jnp.zeros((n, k))
                    for cj in range(k):
                        sel = np.nonzero(cls_ids == cj)[0]
                        if len(sel):
                            total = total.at[:, cj].set(
                                jnp.einsum("tn,t->n", stack[sel], wts[sel]))
                    score = init_margin + total
                elif not kdrop:
                    score = score.at[:, cls].add(contrib * new_weight)
            elif rf_mode:
                pass  # rf: gradients always from the base score; trees averaged at predict
            else:
                score = score.at[:, cls].add(contrib)
            # trees stay device-resident until fit ends (one host pull at the
            # end instead of one per iteration — VERDICT weak #7)
            trees.append(tree)
            tree_weights.append(new_weight)

            if has_valid:
                # streaming validation contribution for every mode; dart/rf
                # re-weight the stacked per-tree contributions below instead
                # of re-scoring the whole forest per iteration (the former
                # O(T^2) full rebuild — VERDICT weak #7)
                leaf_v = _tree_assign_binned(trees[-1], binned_v, nan_bins)
                contrib_v = jnp.asarray(trees[-1].leaf_value)[leaf_v]
                if rf_mode or dart_mode:
                    valid_contribs.append((cls, contrib_v))
                else:
                    score_v = score_v.at[:, cls].add(contrib_v * new_weight)

        # ---- validation metric / early stopping ------------------------
        if has_valid:
            if rf_mode or dart_mode:
                stack_v = jnp.stack([v for _, v in valid_contribs])  # (T, Nv)
                wts_v = jnp.asarray(tree_weights, jnp.float32)
                if rf_mode:
                    wts_v = wts_v / max(len(trees) // k, 1)
                cls_v = np.asarray([c for c, _ in valid_contribs])
                raw_v = jnp.zeros((stack_v.shape[1], k)) + jnp.asarray(
                    base[None, :k], jnp.float32)
                for cj in range(k):
                    sel = np.nonzero(cls_v == cj)[0]
                    if len(sel):
                        raw_v = raw_v.at[:, cj].add(
                            jnp.einsum("tn,t->n", stack_v[sel], wts_v[sel]))
            else:
                raw_v = score_v
            pred_v = obj.transform(raw_v[:, 0] if k == 1 else raw_v)
            mval = float(_eval_metric(metric_name, yv, pred_v, raw_v,
                                      valid, k, cfg, wv_dev))
            tol = cfg.improvement_tolerance
            improved = (best_metric is None
                        or (mval > best_metric + tol if higher_better
                            else mval < best_metric - tol))
            if improved:
                best_metric, best_iter = mval, it
            if cfg.early_stopping_round > 0 and it - best_iter >= cfg.early_stopping_round:
                # best_iter counts NEW iterations: keep every warm-start tree
                cut = n_init_trees + (best_iter + 1) * k
                trees = trees[:cut]
                tree_weights = tree_weights[:cut]
                break

        if callbacks:
            for cb in callbacks:
                cb(it, trees)

        if ckpt_store is not None and (it + 1) % checkpoint_every == 0:
            # per-row state trimmed to the original rows (mesh-independent;
            # see _pack_gbdt_carry for why dropping padding rows is exact)
            payload = {
                "iteration": it + 1,
                "trees": jax.device_get(trees),
                "tree_weights": list(tree_weights),
                "tree_contribs": [(c, np.asarray(jax.device_get(v))[:n_orig])
                                  for c, v in tree_contribs],
                "score": np.asarray(jax.device_get(score))[:n_orig],
                "in_bag_cur": np.asarray(jax.device_get(in_bag_cur))[:n_orig],
                "rng": rng,
                "n_orig": n_orig,
            }
            if has_valid:
                payload["score_v"] = np.asarray(jax.device_get(score_v))
                payload["valid_contribs"] = [
                    (c, np.asarray(jax.device_get(v)))
                    for c, v in valid_contribs]
                payload["best_metric"] = best_metric
                payload["best_iter"] = best_iter
            _ckpt_save_gbdt(ckpt_store, it + 1, payload, fingerprint, "host",
                            measures)

    # single batched device→host transfer of the whole forest (the per-tree
    # pulls were VERDICT weak #7)
    trees = jax.device_get(trees)
    merged_thr = merged_mt = None
    if init_thresholds is not None:
        # warm-start trees keep their origin-resolved thresholds/missing
        # codes; new trees (None slots) resolve from this training's mapper
        merged_thr = (init_thresholds
                      + [None] * (len(trees) - len(init_thresholds)))[
                          : len(trees)]
        merged_mt = (init_mtypes
                     + [None] * (len(trees) - len(init_mtypes)))[: len(trees)]
    # best_iter counts NEW iterations; best_iteration addresses the full
    # returned forest, so warm-start iterations offset it
    return Booster(mapper, cfg, trees, tree_weights, base, feature_names,
                   best_iteration=(n_init_trees // max(k, 1) + best_iter
                                   if has_valid else -1),
                   thresholds=merged_thr, missing_types=merged_mt,
                   best_score=(best_metric if has_valid else None),
                   metadata=_train_metadata(routing_info,
                                            autoconfig_info, _fit_t0))


def _bin_on_device(mapper, X, measures):
    """Host float rows -> bin ids on the default device, each half timed
    until it is done. The float32 device copy lives only in here."""
    with measures.span("copyToDevice"):
        Xd = jax.block_until_ready(jnp.asarray(X, jnp.float32))
    with measures.span("binning"):
        binned = jax.block_until_ready(apply_bins(mapper, Xd))
    measures.count("binnedValuesCompare" if bins_by_compare(mapper)
                   else "binnedValuesSearch", X.size)
    return binned


def _train_fingerprint(cfg, n, nfeat, y, n_init_trees) -> str:
    """Identity of a training run for resume-compatibility: config + data
    shape + label digest + warm-start length. A snapshot whose fingerprint
    differs belongs to a DIFFERENT run and must not be resumed from."""
    import hashlib
    import zlib

    h = hashlib.sha256()
    h.update(repr(sorted(dataclasses.asdict(cfg).items())).encode())
    h.update(repr((int(n), int(nfeat), int(n_init_trees),
                   zlib.crc32(np.ascontiguousarray(
                       np.asarray(y, np.float32)).tobytes()))).encode())
    return h.hexdigest()


def _elastic_label_identity(y, n_orig, multiproc):
    """(global original row count, global original labels) for the resume
    fingerprint. Padded counts/labels are MESH-DEPENDENT (padding varies
    with the data-axis size), so hashing them would pin a snapshot to one
    mesh shape and block the elastic shrink/regrow resume path
    (parallel/elastic.py); the original rows identify the run for any mesh.
    Collective in multi-process mode (label allgather — every rank calls)."""
    y_loc = np.ascontiguousarray(np.asarray(y, np.float32)[:n_orig])
    if not multiproc:
        return int(n_orig), y_loc
    from jax.experimental import multihost_utils

    # stacked (nproc, n_orig) -> rank-order concat == global row order,
    # because to_global_rows lays process blocks contiguously
    g = np.asarray(multihost_utils.process_allgather(y_loc))
    return int(n_orig) * jax.process_count(), g.reshape(-1)


def _repad_rows(a, n):
    """Zero-pad trimmed per-row snapshot state back to THIS run's padded row
    count. Exact, not approximate: padding rows carry in_bag=0 / weight 0,
    so their (discarded) evolved values never touched a histogram or leaf
    stat and zeros are indistinguishable going forward."""
    from ..core.checkpoint import CheckpointError

    a = np.asarray(a, np.float32)
    if a.shape[0] > n:
        raise CheckpointError(
            f"snapshot has {a.shape[0]} rows but this run has {n}; the "
            "snapshot belongs to different data")
    if a.shape[0] == n:
        return a
    pad = np.zeros((n - a.shape[0],) + a.shape[1:], np.float32)
    return np.concatenate([a, pad])


def _pack_gbdt_carry(carry, n, n_orig, multiproc):
    """Host snapshot of the fused-scan carry trimmed to the ORIGINAL rows in
    global row order — mesh-independent, so a shrunken/regrown mesh can
    restore it (_place_gbdt_carry re-pads for the new layout). Collective in
    multi-process mode: the host_copy allgather runs on EVERY rank even
    though only rank 0 commits the resulting checkpoint."""
    score, in_bag, score_v = carry
    if multiproc:
        from ..parallel.mesh import host_copy

        nproc = jax.process_count()
        blk = n // nproc                    # padded rows per process block
        keep = np.concatenate([np.arange(p * blk, p * blk + n_orig)
                               for p in range(nproc)])
        score = np.asarray(host_copy(score))[keep]
        in_bag = np.asarray(host_copy(in_bag))[keep]
        if isinstance(score_v, jax.Array) and not (
                score_v.is_fully_addressable or score_v.is_fully_replicated):
            score_v = host_copy(score_v)
    else:
        score = np.asarray(jax.device_get(score))[:n_orig]
        in_bag = np.asarray(jax.device_get(in_bag))[:n_orig]
    return score, in_bag, np.asarray(jax.device_get(score_v))


def _place_gbdt_carry(saved, n, n_orig, mesh, multiproc, row2, row1,
                      score_v_like):
    """Inverse of _pack_gbdt_carry: zero-pad the trimmed carry back to THIS
    run's padded row count and place it on THIS run's mesh. A resume across
    a different mesh shape therefore converges to the same model as the
    uninterrupted run, and a same-shape resume stays bit-for-bit (trees
    never read padded-row state)."""
    from ..core.checkpoint import CheckpointError

    sc = np.asarray(saved[0], np.float32)
    ib = np.asarray(saved[1], np.float32)
    sv = np.asarray(saved[2], np.float32)
    if sv.shape != tuple(np.shape(score_v_like)):
        raise CheckpointError(
            f"validation score shape changed {sv.shape} -> "
            f"{tuple(np.shape(score_v_like))}; resume with the original "
            "validation set (or pass resume=False)")
    if multiproc:
        from jax.sharding import PartitionSpec as P

        from ..parallel.mesh import DATA_AXIS as _DA
        from ..parallel.mesh import to_global_rows

        nproc, rank = jax.process_count(), jax.process_index()
        if sc.shape[0] != n_orig * nproc:
            raise CheckpointError(
                f"snapshot has {sc.shape[0]} rows but this run has "
                f"{n_orig * nproc} original rows; different data")
        blk = n // nproc

        def _mine(a):
            loc = a[rank * n_orig:(rank + 1) * n_orig]
            pad = np.zeros((blk - n_orig,) + a.shape[1:], np.float32)
            return np.concatenate([loc, pad])

        score = to_global_rows(mesh, P(_DA, None), _mine(sc))
        in_bag = to_global_rows(mesh, P(_DA), _mine(ib))
        return score, in_bag, sv        # multiproc keeps host-side score_v
    sc, ib = _repad_rows(sc, n), _repad_rows(ib, n)
    score, in_bag = jnp.asarray(sc), jnp.asarray(ib)
    if mesh is not None:
        score = jax.device_put(score, row2)
        in_bag = jax.device_put(in_bag, row1)
    return score, in_bag, jnp.asarray(sv)


def _ckpt_save_gbdt(store, iteration, payload, fingerprint, path, measures):
    import pickle

    with measures.span("checkpointSave"):
        store.save(int(iteration),
                   {"state.pkl": pickle.dumps(payload, protocol=4)},
                   meta={"kind": "gbdt", "path": path,
                         "fingerprint": fingerprint})


def _ckpt_load_gbdt(store, fingerprint, path):
    """Newest verified snapshot matching this run, or None (fresh start)."""
    import pickle

    from ..core.logging import record_failure

    ckpt = store.load_latest()
    if ckpt is None:
        return None
    if (ckpt.meta.get("kind") != "gbdt" or ckpt.meta.get("path") != path
            or ckpt.meta.get("fingerprint") != fingerprint):
        record_failure("checkpoint.fingerprint_mismatch", base=ckpt.base,
                       ckpt_kind=ckpt.meta.get("kind"))
        return None
    return pickle.loads(ckpt.artifacts["state.pkl"])


def _best_so_far(series: np.ndarray, tol: float = 0.0) -> np.ndarray:
    """bests[i] = index of the best value within series[:i+1], where a new
    best must beat the incumbent by MORE than ``tol`` (improvementTolerance;
    series is pre-negated for lower-is-better metrics). LightGBM keeps the
    FIRST best on exact ties."""
    bests = np.zeros(len(series), np.int64)
    best, bi = -np.inf, 0
    for i, v in enumerate(series):
        if v > best + tol:
            best, bi = float(v), i
        bests[i] = bi
    return bests


def _is_rank_metric(name: str) -> bool:
    """ndcg/ndcg@k/map/map@k — NOT mape (startswith would match it)."""
    return name.split("@")[0] in ("ndcg", "map")


def _default_metric(objective: str) -> str:
    return {
        "binary": "auc",
        "multiclass": "multi_logloss",
        "softmax": "multi_logloss",
        "multiclassova": "multi_logloss",
        "regression_l1": "mae",
        "lambdarank": "ndcg@5",
        # exp-family / robust objectives early-stop on their OWN loss
        # (LightGBM's default metric = the objective)
        "poisson": "poisson",
        "gamma": "gamma",
        "tweedie": "tweedie",
        "quantile": "quantile",
        "huber": "huber",
        "fair": "fair",
        "mape": "mape",
        "cross_entropy": "cross_entropy",
        "xentropy": "cross_entropy",
    }.get(objective, "rmse")


def _eval_metric(name, yv, pred_v, raw_v, valid, k, cfg=None, wv=None):
    if _is_rank_metric(name):
        at = int(name.split("@")[1]) if "@" in name else 5
        if len(valid) < 4:
            raise ValueError(
                "ranking validation requires valid=(Xv, yv, wv_or_None, group_sizes_v)")
        gidx = make_grouped(yv, valid[3])
        if name.startswith("map"):
            return map_at_k(jnp.asarray(yv), raw_v[:, 0], jnp.asarray(gidx),
                            at)
        return ndcg_at_k(jnp.asarray(yv), raw_v[:, 0], jnp.asarray(gidx), at,
                         cfg.label_gain if cfg is not None else ())
    fn = METRICS[name]
    return fn(jnp.asarray(yv), pred_v, weight=wv, **metric_kwargs(cfg))
