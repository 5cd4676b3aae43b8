"""LightGBM-capability estimators: Classifier / Regressor / Ranker.

The estimator surface of the reference's lightgbm module (SURVEY.md §2.3):
LightGBMClassifier.scala, LightGBMRegressor.scala, LightGBMRanker.scala and the
~90-param surface of params/LightGBMParams.scala + BaseTrainParams.scala, on top
of this framework's TPU GBDT engine (synapseml_tpu.gbdt) instead of SWIG/JNI
calls into lightgbmlib.

Param-parity notes:
  * camelCase param names match the reference so code ports 1:1.
  * Cluster-plumbing params that exist only because of Spark/JNI mechanics
    (useBarrierExecutionMode, driverListenPort, timeout, numTasks, chunkSize,
    matrixType, executionMode, dataTransferMode, useSingleDatasetMode,
    maxStreamingOMPThreads, ...) are accepted for API compatibility but are
    no-ops on TPU: pods are gang-scheduled SPMD, there is no rendezvous ring to
    configure (SURVEY §5.8).
  * ``numBatches`` batching with warm start reproduces LightGBMBase.scala:39-64.
  * ``passThroughArgs`` accepts raw LightGBM-style "key=value" text overriding
    structured params — the reference's escape hatch (LightGBMParams.scala).
  * Accepted-but-inert by design beyond the Spark-plumbing set:
    ``objectiveSeed`` (our objectives draw no randomness), ``deterministic``
    (training is deterministic by construction), ``verbosity`` /
    ``isProvideTrainingMetric`` (use core.logging spans), ``isEnableSparse``
    (sparse input auto-detects), ``repartitionByGroupingColumn`` (the ranker
    always sorts group-contiguously — the param's true behavior), and the
    advanced monotone modes ``monotoneConstraintsMethod`` /
    ``monotonePenalty`` (the basic method is enforced; the advanced
    relaxations are an accuracy/speed trade the basic mode upper-bounds).
"""

from __future__ import annotations

from typing import List, Optional

import numpy as np

from ..core import (Estimator, HasFeaturesCol, HasGroupCol, HasInitScoreCol,
                    HasLabelCol, HasPredictionCol, HasProbabilityCol,
                    HasRawPredictionCol, HasValidationIndicatorCol, HasWeightCol,
                    Model, Param, Table, feature_matrix)
from ..core.logging import InstrumentationMeasures
from ..gbdt.boosting import Booster, BoosterConfig, train_booster


class _LightGBMParams(HasFeaturesCol, HasLabelCol, HasWeightCol,
                      HasValidationIndicatorCol, HasInitScoreCol, HasPredictionCol):
    # core boosting params (defaults = LightGBM defaults, as in the reference)
    numIterations = Param("numIterations", "Number of boosting iterations", int, 100)
    learningRate = Param("learningRate", "Shrinkage rate", float, 0.1)
    numLeaves = Param("numLeaves", "Max leaves per tree", int, 31)
    maxBin = Param("maxBin", "Max number of feature bins", int, 255)
    maxDepth = Param("maxDepth", "Max tree depth (-1 = unlimited)", int, -1)
    boostingType = Param("boostingType", "gbdt, rf, dart or goss", str, "gbdt")
    lambdaL1 = Param("lambdaL1", "L1 regularization", float, 0.0)
    lambdaL2 = Param("lambdaL2", "L2 regularization", float, 0.0)
    minDataInLeaf = Param("minDataInLeaf", "Min rows per leaf", int, 20)
    minSumHessianInLeaf = Param("minSumHessianInLeaf", "Min hessian sum per leaf", float, 1e-3)
    minGainToSplit = Param("minGainToSplit", "Min gain to perform a split", float, 0.0)
    baggingFraction = Param("baggingFraction", "Row subsample fraction", float, 1.0)
    baggingFreq = Param("baggingFreq", "Resample bagging every k iterations (0=off)", int, 0)
    baggingSeed = Param("baggingSeed", "Bagging seed", int, 3)
    featureFraction = Param("featureFraction", "Feature subsample fraction per tree", float, 1.0)
    featureFractionByNode = Param("featureFractionByNode", "Feature subsample fraction per node", float, 1.0)
    posBaggingFraction = Param("posBaggingFraction", "Positive-class bagging fraction", float, 1.0)
    negBaggingFraction = Param("negBaggingFraction", "Negative-class bagging fraction", float, 1.0)
    maxDeltaStep = Param("maxDeltaStep", "Max absolute leaf output", float, 0.0)
    earlyStoppingRound = Param("earlyStoppingRound", "Early stopping patience (0=off)", int, 0)
    improvementTolerance = Param("improvementTolerance", "Min metric improvement", float, 0.0)
    metric = Param("metric", "Eval metric for validation", str)
    dropRate = Param("dropRate", "DART tree drop probability", float, 0.1)
    maxDrop = Param("maxDrop", "DART max trees dropped per iteration", int, 50)
    skipDrop = Param("skipDrop", "DART probability of skipping dropout", float, 0.5)
    uniformDrop = Param("uniformDrop", "DART uniform drop", bool, False)
    topRate = Param("topRate", "GOSS large-gradient keep fraction", float, 0.2)
    otherRate = Param("otherRate", "GOSS small-gradient sample fraction", float, 0.1)
    monotoneConstraints = Param("monotoneConstraints", "Per-feature -1/0/+1 constraints", list)
    monotoneConstraintsMethod = Param("monotoneConstraintsMethod", "basic/intermediate/advanced", str, "basic")
    monotonePenalty = Param("monotonePenalty", "Monotone split penalty", float, 0.0)
    categoricalSlotIndexes = Param("categoricalSlotIndexes", "Categorical feature indices", list)
    categoricalSlotNames = Param("categoricalSlotNames", "Categorical feature names", list)
    slotNames = Param("slotNames", "Feature names", list)
    seed = Param("seed", "Main random seed", int, 0)
    objectiveSeed = Param("objectiveSeed", "Objective seed", int, 5)
    dataRandomSeed = Param("dataRandomSeed", "Data random seed", int, 1)
    boostFromAverage = Param("boostFromAverage", "Initialize score to label average", bool, True)
    numBatches = Param("numBatches", "Split training into N sequential warm-started batches", int, 0)
    modelString = Param("modelString", "Initial model string to continue training from", str)
    binSampleCount = Param("binSampleCount", "Rows sampled for bin boundaries", int, 200000)
    catSmooth = Param("catSmooth", "Categorical smoothing", float, 10.0)
    maxCatThreshold = Param("maxCatThreshold", "Max categories on one split side", int, 32)
    verbosity = Param("verbosity", "Verbosity", int, -1)
    leafPredictionCol = Param("leafPredictionCol", "Output column for leaf indices", str)
    featuresShapCol = Param("featuresShapCol", "Output column for SHAP values", str)
    predictDisableShapeCheck = Param("predictDisableShapeCheck", "Disable shape check at predict", bool, False)
    passThroughArgs = Param("passThroughArgs", "Raw LightGBM-style 'key=value' args overriding params", str)
    # Spark/JNI-plumbing compat no-ops (see module docstring)
    useBarrierExecutionMode = Param("useBarrierExecutionMode", "no-op on TPU (gang-scheduled)", bool, False)
    useSingleDatasetMode = Param("useSingleDatasetMode", "no-op on TPU (one process per host)", bool, True)
    executionMode = Param("executionMode", "no-op on TPU", str, "streaming")
    dataTransferMode = Param("dataTransferMode", "no-op on TPU", str, "streaming")
    numTasks = Param("numTasks", "no-op on TPU", int, 0)
    numThreads = Param("numThreads", "no-op (XLA manages threads)", int, 0)
    chunkSize = Param("chunkSize", "no-op on TPU", int, 10000)
    matrixType = Param("matrixType", "no-op on TPU (auto)", str, "auto")
    defaultListenPort = Param("defaultListenPort", "no-op on TPU", int, 12400)
    driverListenPort = Param("driverListenPort", "no-op on TPU", int, 0)
    timeout = Param("timeout", "no-op on TPU", float, 1200.0)
    maxStreamingOMPThreads = Param("maxStreamingOMPThreads", "no-op on TPU", int, 16)
    microBatchSize = Param("microBatchSize", "no-op on TPU", int, 100)
    topK = Param("topK", "Voting-parallel top-K (distributed histogram vote)", int, 20)
    parallelism = Param("parallelism", "data_parallel or voting_parallel "
                        "(LightGBMParams.scala:25-29)", str, "data_parallel")
    isProvideTrainingMetric = Param("isProvideTrainingMetric", "Log training metrics", bool, False)
    deterministic = Param("deterministic", "Deterministic training", bool, False)
    isEnableSparse = Param("isEnableSparse", "Enable sparse optimization", bool, True)
    minDataPerBin = Param("minDataPerBin", "Minimum sample rows per bin "
                          "(under-filled bins merge)", int, 3)
    maxBinByFeature = Param("maxBinByFeature", "Per-feature max bin counts",
                            list, None)
    catl2 = Param("catl2", "Extra L2 applied to categorical split gains",
                  float, 10.0)
    dropSeed = Param("dropSeed", "DART drop-selection seed (0 = derive from "
                     "seed)", int, 0)
    featureFractionSeed = Param("featureFractionSeed", "Feature-sampling seed "
                                "(0 = derive from seed)", int, 0)
    extraSeed = Param("extraSeed", "Extra sampling seed (0 = derive from "
                      "seed)", int, 0)
    startIteration = Param("startIteration", "First boosting round used at "
                           "prediction time", int, 0)
    maxCatToOnehot = Param("maxCatToOnehot", "One-vs-rest categorical splits "
                           "at or below this many categories", int, 4)
    minDataPerGroup = Param("minDataPerGroup", "Minimum rows per categorical "
                            "group considered for splitting", int, 100)
    xGBoostDartMode = Param("xGBoostDartMode", "XGBoost-style DART "
                            "normalization (learning-rate weighted)", bool,
                            False)
    fobj = Param("fobj", "Custom objective: fn(score, label, weight) -> "
                 "(grad, hess) arrays (the reference's FObjTrait/FObjParam)",
                 is_complex=True)
    samplingSubsetSize = Param("samplingSubsetSize", "Boundary-sample size "
                               "when subset sampling; 0 defers to "
                               "binSampleCount", int, 0)
    repartitionByGroupingColumn = Param("repartitionByGroupingColumn",
                                        "Kept for API parity: rows are "
                                        "group-contiguous by construction "
                                        "here (no partitions to repartition)",
                                        bool, True)
    referenceDataset = Param("referenceDataset", "Precomputed BinMapper (or "
                             "gbdt.Dataset) reused for binning — the "
                             "reference-dataset broadcast analog",
                             is_complex=True)
    useMissing = Param("useMissing", "Handle missing values specially", bool, True)
    zeroAsMissing = Param("zeroAsMissing", "Treat zero as missing", bool, False)

    def _reference_mapper(self, X=None):
        """referenceDataset param → BinMapper (accepts a Dataset too).
        With ``X`` (the post-missing-params training matrix): validate that
        every feature carrying NaN has a missing bin — a reference mapper
        built WITHOUT the same zeroAsMissing/useMissing mapping would bin
        those rows into the last real bin at fit yet route them as missing
        at predict, silently corrupting the model."""
        ref = self.get("referenceDataset")
        if ref is None:
            return None
        mapper = getattr(ref, "mapper", ref)
        if X is not None:
            need = np.isnan(np.asarray(X)).any(axis=0)
            have = np.asarray(mapper.nan_mask)
            bad = np.flatnonzero(need[: len(have)] & ~have)
            if bad.size:
                raise ValueError(
                    "referenceDataset's bin mapper has no missing bin for "
                    f"feature(s) {bad.tolist()} that contain missing values "
                    "after useMissing/zeroAsMissing preprocessing; build the "
                    "reference dataset from identically-preprocessed data")
        return mapper

    def _base_config(self, **overrides) -> BoosterConfig:
        mc = self.get("monotoneConstraints")
        cfg = BoosterConfig(
            num_iterations=self.getNumIterations(),
            learning_rate=self.getLearningRate(),
            num_leaves=self.getNumLeaves(),
            max_bin=self.getMaxBin(),
            max_depth=self.getMaxDepth(),
            boosting_type=self.getBoostingType(),
            lambda_l1=self.getLambdaL1(),
            lambda_l2=self.getLambdaL2(),
            min_data_in_leaf=self.getMinDataInLeaf(),
            min_sum_hessian_in_leaf=self.getMinSumHessianInLeaf(),
            min_gain_to_split=self.getMinGainToSplit(),
            bagging_fraction=self.getBaggingFraction(),
            bagging_freq=self.getBaggingFreq(),
            feature_fraction=self.getFeatureFraction(),
            feature_fraction_bynode=self.getFeatureFractionByNode(),
            pos_bagging_fraction=self.getPosBaggingFraction(),
            neg_bagging_fraction=self.getNegBaggingFraction(),
            max_delta_step=self.getMaxDeltaStep(),
            early_stopping_round=self.getEarlyStoppingRound(),
            metric=self.get("metric"),
            drop_rate=self.getDropRate(),
            max_drop=self.getMaxDrop(),
            skip_drop=self.getSkipDrop(),
            uniform_drop=self.getUniformDrop(),
            top_rate=self.getTopRate(),
            other_rate=self.getOtherRate(),
            monotone_constraints=mc,
            seed=self.getSeed(),
            boost_from_average=self.getBoostFromAverage(),
            bin_sample_count=(self.getSamplingSubsetSize()
                              or self.getBinSampleCount()),
            cat_smooth=self.getCatSmooth(),
            cat_l2=self.getCatl2(),
            min_data_in_bin=self.getMinDataPerBin(),
            max_bin_by_feature=self.get("maxBinByFeature"),
            drop_seed=self.getDropSeed(),
            feature_fraction_seed=self.getFeatureFractionSeed(),
            extra_seed=self.getExtraSeed(),
            bagging_seed=self.getBaggingSeed(),
            improvement_tolerance=self.getImprovementTolerance(),
            data_random_seed=(self.get("dataRandomSeed")
                              if self.isSet("dataRandomSeed") else None),
            zero_as_missing=(bool(self.get("zeroAsMissing"))
                             and bool(self.get("useMissing"))),
            start_iteration=self.getStartIteration(),
            max_cat_threshold=self.getMaxCatThreshold(),
            max_cat_to_onehot=self.getMaxCatToOnehot(),
            min_data_per_group=self.getMinDataPerGroup(),
            xgboost_dart_mode=self.getXGBoostDartMode(),
            tree_learner=("voting" if self.getParallelism() == "voting_parallel"
                          else "feature" if self.getParallelism() == "feature_parallel"
                          else "auto" if self.getParallelism() == "auto"
                          else "data"),
            top_k=self.getTopK(),
        )
        for k, v in overrides.items():
            setattr(cfg, k, v)
        self._apply_pass_through(cfg)
        return cfg

    def _apply_pass_through(self, cfg: BoosterConfig) -> None:
        """passThroughArgs: 'k1=v1 k2=v2' raw overrides (LightGBMParams.scala)."""
        raw = self.get("passThroughArgs")
        if not raw:
            return
        for tok in raw.split():
            if "=" not in tok:
                continue
            key, _, val = tok.partition("=")
            if hasattr(cfg, key):
                cur = getattr(cfg, key)
                typ = type(cur) if cur is not None else str
                if typ is bool:
                    setattr(cfg, key, val.lower() in ("1", "true", "yes"))
                elif typ in (int, float):
                    setattr(cfg, key, typ(float(val)))
                else:
                    setattr(cfg, key, val)

    def _categorical_indexes(self, feature_names: Optional[List[str]]) -> List[int]:
        """categorical-slot detection (LightGBMBase.scala:167-198)."""
        idx = list(self.get("categoricalSlotIndexes") or [])
        names = self.get("categoricalSlotNames") or []
        if names and feature_names:
            idx += [feature_names.index(n) for n in names if n in feature_names]
        return sorted(set(int(i) for i in idx))

    def _apply_missing_params(self, X: np.ndarray) -> np.ndarray:
        """useMissing / zeroAsMissing preprocessing (BinMapper missing-type
        election in native LightGBM): useMissing=False coerces NaN to 0
        (missing handling disabled); zeroAsMissing=True maps exact zeros to
        NaN so they land in the missing bin, with the booster's
        zero_as_missing flag making traversal + serialization route zeros
        (missing_type=zero) — see Booster._missing_types."""
        if not self.get("useMissing"):
            return np.nan_to_num(X, nan=0.0)
        if self.get("zeroAsMissing"):
            X = np.asarray(X, np.float32).copy()
            # |x| <= kZeroThreshold (1e-35) folds into the zero bin in
            # native LightGBM, and predict-time traversal routes the same
            # band — exact zeros only would score tiny values differently
            # at fit vs transform
            X[np.abs(X) <= 1e-35] = np.nan
        return X

    def _extract_training_arrays(self, df: Table):
        X = self._apply_missing_params(
            feature_matrix(df, self.getFeaturesCol()))
        y = np.asarray(df[self.getLabelCol()], np.float32)
        w = (np.asarray(df[self.get("weightCol")], np.float32)
             if self.get("weightCol") and self.get("weightCol") in df else None)
        init = (np.asarray(df[self.get("initScoreCol")], np.float32)
                if self.get("initScoreCol") and self.get("initScoreCol") in df else None)
        return X, y, w, init

    def _split_validation(self, df: Table):
        vcol = self.get("validationIndicatorCol")
        if vcol and vcol in df:
            mask = np.asarray(df[vcol], bool)
            return df.filter(~mask), df.filter(mask)
        return df, None


class _LightGBMModelBase(Model, HasFeaturesCol, HasPredictionCol):
    leafPredictionCol = Param("leafPredictionCol", "Output column for leaf indices", str)
    featuresShapCol = Param("featuresShapCol", "Output column for SHAP values", str)
    predictDisableShapeCheck = Param(
        "predictDisableShapeCheck",
        "Truncate/pad prediction features to the trained width instead of "
        "raising on mismatch", bool, False)

    def __init__(self, booster: Optional[Booster] = None, **kwargs):
        super().__init__(**kwargs)
        self.booster = booster

    # --- persistence of the native model string --------------------------
    def _save_extra(self, path: str) -> None:
        import os

        if self.booster is not None:
            self.booster.save_native(os.path.join(path, "model.txt"))

    def _load_extra(self, path: str) -> None:
        import os

        p = os.path.join(path, "model.txt")
        if os.path.exists(p):
            with open(p) as fh:
                self.booster = Booster.from_model_string(fh.read())

    def dumpModel(self, num_iteration: int = -1) -> str:
        """JSON model dump (LightGBMModelMethods/Booster dumpModel parity)."""
        return self.booster.dump_model(num_iteration)

    def saveNativeModel(self, path: str, overwrite: bool = True) -> None:
        """LightGBMModelMethods.saveNativeModel parity."""
        import os

        if os.path.exists(path) and not overwrite:
            raise FileExistsError(path)
        self.booster.save_native(path)

    def getBoosterBestIteration(self) -> int:
        """Best iteration from early stopping (-1 without validation) —
        LightGBMModelMethods.getBoosterBestIteration parity."""
        return int(self.booster.best_iteration)

    def getBoosterBestScore(self):
        """Best validation metric value from training (None without
        validation) — the Booster.best_score surface."""
        return self.booster.best_score

    def getBoosterNumTotalIterations(self) -> int:
        return self.booster.num_trees // self.booster.models_per_iter

    def getBoosterNumTotalModel(self) -> int:
        return self.booster.num_trees

    def getBoosterNumFeatures(self) -> int:
        return self.booster.mapper.num_features

    def getBoosterNumClasses(self) -> int:
        return self.booster.num_class

    def getNativeModel(self) -> str:
        return self.booster.model_string()

    def getFeatureImportances(self, importance_type: str = "split"):
        return list(self.booster.feature_importances(importance_type))

    def getFeatureShaps(self, X) -> np.ndarray:
        return self.booster.feature_shap(np.asarray(X, np.float32))

    def _predict_matrix(self, df: Table) -> np.ndarray:
        """Feature matrix for prediction: validates the width against the
        trained model (clear error instead of an opaque gather failure);
        predictDisableShapeCheck=True instead truncates / zero-pads, the
        native predict_disable_shape_check behavior."""
        X = feature_matrix(df, self.getFeaturesCol())
        nf = self.booster.mapper.num_features
        if X.shape[1] != nf:
            if not self.get("predictDisableShapeCheck"):
                raise ValueError(
                    f"prediction data has {X.shape[1]} features but the "
                    f"model was trained with {nf}; set "
                    "predictDisableShapeCheck=True to truncate/pad")
            if X.shape[1] > nf:
                X = X[:, :nf]
            else:
                X = np.concatenate(
                    [X, np.zeros((X.shape[0], nf - X.shape[1]),
                                 X.dtype)], axis=1)
        return X

    def _maybe_extra_cols(self, out: Table, X) -> Table:
        if self.get("leafPredictionCol"):
            out = out.with_column(self.get("leafPredictionCol"),
                                  self.booster.predict_leaf(X).astype(np.float64))
        if self.get("featuresShapCol"):
            out = out.with_column(self.get("featuresShapCol"),
                                  self.booster.feature_shap(X))
        return out


# ---------------------------------------------------------------------------
# Classifier
# ---------------------------------------------------------------------------

class LightGBMClassifier(Estimator, _LightGBMParams, HasProbabilityCol, HasRawPredictionCol):
    """Binary / multiclass GBDT classifier (reference: LightGBMClassifier.scala)."""

    objective = Param("objective", "binary or multiclass", str, "binary")
    isUnbalance = Param("isUnbalance", "Adjust for unbalanced binary labels", bool, False)
    maxNumClasses = Param("maxNumClasses", "Upper bound on auto-detected "
                          "label classes (guards runaway continuous labels)",
                          int, 100)
    scalePosWeight = Param("scalePosWeight", "Positive-class weight multiplier", float, 1.0)
    thresholds = Param("thresholds", "Per-class prediction thresholds", list)

    def _fit(self, df: Table) -> "LightGBMClassificationModel":
        measures = InstrumentationMeasures()
        with measures.span("tablePreparation"):
            X, y, w, init, cfg, valid, classes = self._prepare(df)
        booster = self._run_batches(X, y, w, init, cfg, valid, measures)
        model = LightGBMClassificationModel(booster)
        model.classes_ = classes.astype(np.float64)
        self._copy_model_params(model)
        return model

    def _prepare(self, df: Table):
        """Columns to arrays, labels to class ids, params to a config."""
        train_df, valid_df = self._split_validation(df)
        X, y, w, init = self._extract_training_arrays(train_df)
        # map arbitrary label values to 0..K-1 (objectives assume contiguous
        # class ids); the model maps predictions back through classes_
        classes, y_idx = np.unique(y, return_inverse=True)
        num_class = len(classes)
        if num_class < 2:
            raise ValueError(f"need at least 2 label classes, got {classes}")
        if num_class > self.getMaxNumClasses():
            raise ValueError(
                f"detected {num_class} label classes, above maxNumClasses="
                f"{self.getMaxNumClasses()} — a continuous label column was "
                "likely passed to the classifier (raise maxNumClasses if "
                "this cardinality is intended)")
        y = y_idx.astype(np.float32)
        objective = self.getObjective()
        if objective == "binary" and num_class > 2:
            objective = "multiclass"
        cfg = self._base_config(objective=objective,
                                num_class=(num_class if objective != "binary" else 1))
        if self.getIsUnbalance() and objective == "binary":
            npos = max(float((y > 0).sum()), 1.0)
            nneg = max(float((y <= 0).sum()), 1.0)
            w = (w if w is not None else np.ones_like(y)) * np.where(y > 0, nneg / npos, 1.0)
        elif self.getScalePosWeight() != 1.0 and objective == "binary":
            w = (w if w is not None else np.ones_like(y)) * np.where(
                y > 0, self.getScalePosWeight(), 1.0)

        valid = None
        if valid_df is not None and valid_df.num_rows:
            Xv, yv, _, _ = self._extract_training_arrays(valid_df)
            yv = np.searchsorted(classes, yv).astype(np.float32)
            valid = (Xv, yv)
        return X, y, w, init, cfg, valid, classes

    def _run_batches(self, X, y, w, init, cfg, valid, measures):
        """numBatches warm-started sequential fits (LightGBMBase.scala:39-64),
        instrumented with phase spans (LightGBMPerformance analog, §5.1);
        logs the fit's ``trainingMeasures`` record (docs/observability.md)."""
        cats = self._categorical_indexes(self.get("slotNames"))
        init_model = None
        if self.get("modelString"):
            init_model = Booster.from_model_string(self.get("modelString"))
        nb = self.getNumBatches()
        if nb and nb > 1:
            rng = np.random.default_rng(self.getSeed())
            perm = rng.permutation(len(y))
            parts = np.array_split(perm, nb)
            bst = init_model
            for part in parts:
                bst = train_booster(X[part], y[part], cfg,
                                    sample_weight=None if w is None else w[part],
                                    init_score=None if init is None else init[part],
                                    categorical_features=cats, valid=valid,
                                    feature_names=self.get("slotNames"), init_model=bst,
                                    fobj=self.get("fobj"),
                                    mapper=self._reference_mapper(X[part]),
                                    measures=measures)
        else:
            bst = train_booster(X, y, cfg, sample_weight=w, init_score=init,
                                categorical_features=cats, valid=valid,
                                feature_names=self.get("slotNames"),
                                init_model=init_model, fobj=self.get("fobj"),
                                mapper=self._reference_mapper(X),
                                measures=measures)
        self._log_base("trainingMeasures", measures.report())
        return bst

    def _copy_model_params(self, model):
        for p in ("featuresCol", "predictionCol", "probabilityCol", "rawPredictionCol",
                  "leafPredictionCol", "featuresShapCol", "thresholds",
                  "predictDisableShapeCheck"):
            if self.hasParam(p) and model.hasParam(p) and self.isSet(p):
                model.set(p, self.get(p))


class LightGBMClassificationModel(_LightGBMModelBase, HasProbabilityCol, HasRawPredictionCol):
    thresholds = Param("thresholds", "Per-class prediction thresholds", list)

    classes_: Optional[np.ndarray] = None   # original label values, index = class id

    def _transform(self, df: Table) -> Table:
        X = self._predict_matrix(df)
        raw = self.booster.raw_score(X)
        prob = self.booster.predict(X)
        out = df
        if raw.ndim == 1:
            raw2 = np.stack([-raw, raw], axis=1)
            prob2 = np.stack([1 - prob, prob], axis=1)
        else:
            raw2, prob2 = raw, prob
        out = out.with_column(self.getRawPredictionCol(), raw2)
        out = out.with_column(self.getProbabilityCol(), prob2)
        th = self.get("thresholds")
        scaled = prob2 / np.asarray(th)[None, :] if th else prob2
        pred = np.argmax(scaled, 1)
        if self.classes_ is not None:
            pred = np.asarray(self.classes_)[pred]
        out = out.with_column(self.getPredictionCol(), pred.astype(np.float64))
        return self._maybe_extra_cols(out, X)

    def _save_extra(self, path: str) -> None:
        import os

        super()._save_extra(path)
        if self.classes_ is not None:
            np.save(os.path.join(path, "classes.npy"), np.asarray(self.classes_))

    def _load_extra(self, path: str) -> None:
        import os

        super()._load_extra(path)
        p = os.path.join(path, "classes.npy")
        if os.path.exists(p):
            self.classes_ = np.load(p)


# ---------------------------------------------------------------------------
# Regressor
# ---------------------------------------------------------------------------

class LightGBMRegressor(Estimator, _LightGBMParams):
    """GBDT regressor (reference: LightGBMRegressor.scala). Objectives:
    regression, regression_l1, huber, fair, poisson, quantile, mape, gamma,
    tweedie."""

    objective = Param("objective", "Regression objective", str, "regression")
    alpha = Param("alpha", "Huber/quantile alpha", float, 0.9)
    tweedieVariancePower = Param("tweedieVariancePower", "Tweedie variance power", float, 1.5)

    _run_batches = LightGBMClassifier._run_batches
    _copy_model_params = LightGBMClassifier._copy_model_params

    def _fit(self, df: Table) -> "LightGBMRegressionModel":
        measures = InstrumentationMeasures()
        with measures.span("tablePreparation"):
            train_df, valid_df = self._split_validation(df)
            X, y, w, init = self._extract_training_arrays(train_df)
            cfg = self._base_config(objective=self.getObjective(),
                                    alpha=self.getAlpha(),
                                    tweedie_variance_power=self.getTweedieVariancePower())
            valid = None
            if valid_df is not None and valid_df.num_rows:
                Xv, yv, _, _ = self._extract_training_arrays(valid_df)
                valid = (Xv, yv)
        booster = self._run_batches(X, y, w, init, cfg, valid, measures)
        model = LightGBMRegressionModel(booster)
        self._copy_model_params(model)
        return model


class LightGBMRegressionModel(_LightGBMModelBase):
    def _transform(self, df: Table) -> Table:
        X = self._predict_matrix(df)
        out = df.with_column(self.getPredictionCol(), self.booster.predict(X).astype(np.float64))
        return self._maybe_extra_cols(out, X)


# ---------------------------------------------------------------------------
# Ranker
# ---------------------------------------------------------------------------

class LightGBMRanker(Estimator, _LightGBMParams, HasGroupCol):
    """LambdaRank GBDT (reference: LightGBMRanker.scala). Rows are re-sorted
    group-contiguously before training — the analog of the reference's
    repartitionForGroupColumn (LightGBMRanker.scala:88-116)."""

    objective = Param("objective", "Ranking objective", str, "lambdarank")
    maxPosition = Param("maxPosition", "NDCG truncation for optimization", int, 20)
    labelGain = Param("labelGain", "Relevance gains per label value", list)
    evalAt = Param("evalAt", "NDCG@k eval positions", list, [1, 2, 3, 4, 5])

    _copy_model_params = LightGBMClassifier._copy_model_params

    def _fit(self, df: Table) -> "LightGBMRankerModel":
        measures = InstrumentationMeasures()
        with measures.span("tablePreparation"):
            train_df, valid_df = self._split_validation(df)
            gcol = self.getGroupCol()
            train_df = train_df.sort_by(gcol)       # group-contiguous layout
            X, y, w, init = self._extract_training_arrays(train_df)
            groups = np.asarray(train_df[gcol])
            _, sizes = np.unique(groups, return_counts=True)
            cfg = self._base_config(objective="lambdarank",
                                    lambdarank_truncation_level=self.getMaxPosition(),
                                    eval_at=tuple(self.getEvalAt()),
                                    label_gain=tuple(self.get("labelGain") or ()))
            valid = None
            if valid_df is not None and valid_df.num_rows:
                valid_df = valid_df.sort_by(gcol)
                Xv, yv, _, _ = self._extract_training_arrays(valid_df)
                _, sv = np.unique(np.asarray(valid_df[gcol]), return_counts=True)
                valid = (Xv, yv, None, sv)
        cats = self._categorical_indexes(self.get("slotNames"))
        booster = train_booster(X, y, cfg, sample_weight=w, init_score=init,
                                categorical_features=cats, group_sizes=sizes,
                                valid=valid, feature_names=self.get("slotNames"),
                                fobj=self.get("fobj"),
                                mapper=self._reference_mapper(X),
                                measures=measures)
        self._log_base("trainingMeasures", measures.report())
        model = LightGBMRankerModel(booster)
        self._copy_model_params(model)
        return model


class LightGBMRankerModel(_LightGBMModelBase):
    def _transform(self, df: Table) -> Table:
        X = self._predict_matrix(df)
        out = df.with_column(self.getPredictionCol(), self.booster.predict(X).astype(np.float64))
        return self._maybe_extra_cols(out, X)
