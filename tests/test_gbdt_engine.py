"""GBDT engine unit tests: binning, histograms, grower, objectives, model IO."""

import jax.numpy as jnp
import numpy as np
import pytest

from synapseml_tpu.gbdt import BoosterConfig, train_booster
from synapseml_tpu.gbdt.boosting import Booster
from synapseml_tpu.gbdt.grower import GrowerConfig, forest_predict, grow_tree
from synapseml_tpu.ops.histogram import leaf_histograms
from synapseml_tpu.ops.quantize import apply_bins, compute_bin_mapper


def test_bin_mapper_quantiles():
    rng = np.random.default_rng(0)
    X = rng.normal(size=(5000, 3)).astype(np.float32)
    m = compute_bin_mapper(X, max_bin=64)
    binned = np.asarray(apply_bins(m, X))
    assert binned.max() < 64
    # bins should be roughly balanced for a continuous feature
    counts = np.bincount(binned[:, 0], minlength=64)
    nz = counts[counts > 0]
    assert nz.min() > 15


def test_bin_mapper_few_distinct_values():
    X = np.repeat(np.array([[0.0], [1.0], [2.0]], np.float32), 10, axis=0)
    m = compute_bin_mapper(X, max_bin=255)
    binned = np.asarray(apply_bins(m, X)).ravel()
    assert len(np.unique(binned)) == 3


def test_bin_mapper_nan_goes_last():
    X = np.array([[0.0], [1.0], [np.nan]], np.float32)
    base = np.linspace(0, 1, 100)[:, None].astype(np.float32)
    m = compute_bin_mapper(np.concatenate([X, base]), max_bin=16)
    binned = np.asarray(apply_bins(m, X)).ravel()
    assert binned[2] == binned.max()
    assert binned[2] > binned[1] > binned[0]


def test_leaf_histogram_matches_numpy():
    rng = np.random.default_rng(1)
    n, f, b, leaves = 500, 4, 16, 3
    binned = rng.integers(0, b, size=(n, f)).astype(np.uint8)
    node = rng.integers(0, leaves, size=n).astype(np.int32)
    g = rng.normal(size=n).astype(np.float32)
    h = rng.uniform(0.1, 1, size=n).astype(np.float32)
    hist = np.asarray(leaf_histograms(jnp.asarray(binned), jnp.asarray(node),
                                      jnp.asarray(g), jnp.asarray(h), leaves, b))
    for leaf in range(leaves):
        for feat in range(f):
            mask = node == leaf
            expect_g = np.bincount(binned[mask, feat], weights=g[mask], minlength=b)
            np.testing.assert_allclose(hist[leaf, feat, :, 0], expect_g, rtol=1e-4, atol=1e-4)
    # count channel sums to n for every feature
    assert np.allclose(hist[..., 2].sum(axis=(0, 2)), n)


def test_grow_tree_perfect_split():
    """A single feature perfectly separating labels must be found."""
    n = 200
    X = np.linspace(0, 1, n)[:, None].astype(np.float32)
    y = (X[:, 0] > 0.5).astype(np.float32)
    # max_bin > #distinct values → midpoint boundaries → the exact 0.5 split
    # exists (min_data_in_bin=1: the default 3 merges single-sample bins,
    # matching native LightGBM's minDataPerBin default)
    m = compute_bin_mapper(X, max_bin=255, min_data_in_bin=1)
    binned = apply_bins(m, X)
    g = jnp.asarray(0.5 - y)   # logistic grad at score 0
    h = jnp.full(n, 0.25)
    cfg = GrowerConfig(num_leaves=4, num_bins=255, min_data_in_leaf=5)
    tree, node = grow_tree(binned, g, h, jnp.ones(n), jnp.ones(1, bool),
                           jnp.zeros(1, bool), jnp.zeros(1, jnp.int32), cfg)
    assert int(tree.num_splits) >= 1
    # first split must be on feature 0 near the middle
    assert int(tree.split_feature[0]) == 0
    node = np.asarray(node)
    # left group gets positive leaf value (negative grad sum → pulls up)
    vals = np.asarray(tree.leaf_value)[node]
    assert (vals[y == 1] > 0).all() and (vals[y == 0] < 0).all()


def test_monotone_constraint_enforced():
    rng = np.random.default_rng(2)
    n = 2000
    X = rng.uniform(size=(n, 1)).astype(np.float32)
    y = np.sin(X[:, 0] * 6).astype(np.float32)    # non-monotone target
    cfg = BoosterConfig(objective="regression", num_iterations=20,
                        monotone_constraints=[1])
    bst = train_booster(X, y, cfg)
    grid = np.linspace(0.01, 0.99, 50)[:, None].astype(np.float32)
    pred = bst.predict(grid)
    assert (np.diff(pred) >= -1e-6).all()


def test_categorical_split():
    rng = np.random.default_rng(3)
    n = 2000
    cats = rng.integers(0, 10, size=n)
    y = np.isin(cats, [2, 5, 7]).astype(np.float32)   # value only via subset
    X = np.stack([cats.astype(np.float32), rng.normal(size=n).astype(np.float32)], 1)
    cfg = BoosterConfig(objective="binary", num_iterations=10)
    bst = train_booster(X, y, cfg, categorical_features=[0])
    p = bst.predict(X)
    assert ((p > 0.5) == (y > 0.5)).mean() > 0.99


def test_objectives_gradient_check():
    from synapseml_tpu.gbdt.objectives import get_objective

    rng = np.random.default_rng(4)
    score = jnp.asarray(rng.normal(size=50).astype(np.float32))
    w = jnp.ones(50)
    for name, y in [
        ("binary", (rng.uniform(size=50) > 0.5).astype(np.float32)),
        ("regression", rng.normal(size=50).astype(np.float32)),
        ("poisson", rng.poisson(3.0, size=50).astype(np.float32)),
        ("tweedie", rng.gamma(2.0, size=50).astype(np.float32)),
    ]:
        import jax

        obj = get_objective(name, num_class=1)
        loss = {
            "binary": lambda s: -jnp.mean(yj * jax.nn.log_sigmoid(s)
                                          + (1 - yj) * jax.nn.log_sigmoid(-s)) * 50,
            "regression": lambda s: 0.5 * jnp.sum((s - yj) ** 2),
            "poisson": lambda s: jnp.sum(jnp.exp(s) - yj * s),
            "tweedie": lambda s: jnp.sum(-yj * jnp.exp((1 - 1.5) * s) / (1 - 1.5)
                                         + jnp.exp((2 - 1.5) * s) / (2 - 1.5)),
        }[name]
        yj = jnp.asarray(y)
        g_expect = jax.grad(loss)(score)
        g, h = obj.grad_hess(score, yj, w)
        np.testing.assert_allclose(np.asarray(g), np.asarray(g_expect), rtol=2e-3, atol=2e-3)
        assert (np.asarray(h) > 0).all()


def test_model_string_roundtrip(binary_data):
    Xtr, Xte, ytr, yte = binary_data
    cfg = BoosterConfig(objective="binary", num_iterations=10)
    bst = train_booster(Xtr, ytr, cfg)
    s = bst.model_string()
    assert s.startswith("tree\nversion=v3")
    b2 = Booster.from_model_string(s)
    np.testing.assert_allclose(b2.predict(Xte), bst.predict(Xte), atol=1e-5)


def test_feature_importances(binary_data):
    Xtr, _, ytr, _ = binary_data
    bst = train_booster(Xtr, ytr, BoosterConfig(objective="binary", num_iterations=5))
    imp = bst.feature_importances("split")
    assert imp.sum() > 0 and (imp >= 0).all()
    gain = bst.feature_importances("gain")
    assert gain.sum() > 0


def test_shap_additivity(binary_data):
    Xtr, Xte, ytr, _ = binary_data
    bst = train_booster(Xtr, ytr, BoosterConfig(objective="binary", num_iterations=10))
    sh = bst.feature_shap(Xte[:20])
    raw = bst.raw_score(Xte[:20])
    np.testing.assert_allclose(sh.sum(axis=1), raw, atol=1e-4)


def test_warm_start_continues(binary_data):
    Xtr, Xte, ytr, yte = binary_data
    cfg = BoosterConfig(objective="binary", num_iterations=5)
    b1 = train_booster(Xtr, ytr, cfg)
    b2 = train_booster(Xtr, ytr, BoosterConfig(objective="binary", num_iterations=5),
                       init_model=b1)
    assert b2.num_trees == 10
    from sklearn.metrics import log_loss

    assert log_loss(yte, b2.predict(Xte)) < log_loss(yte, b1.predict(Xte))


def test_dataset_prebinned_matches_raw(binary_data):
    """Dataset (LightGBM-Dataset analog: bin once, device-resident) must give
    the identical model to the raw-matrix path."""
    from synapseml_tpu.gbdt import Dataset

    X, _, y, _ = binary_data
    cfg = BoosterConfig(objective="binary", num_iterations=5, num_leaves=15)
    b_raw = train_booster(X, y, cfg)
    ds = Dataset(X, y).block_until_ready()
    b_ds = train_booster(ds, None, cfg)
    np.testing.assert_allclose(b_raw.predict(X[:100]), b_ds.predict(X[:100]),
                               rtol=1e-6)
    # labels/weights ride along; reuse across configs skips re-binning
    cfg2 = BoosterConfig(objective="binary", num_iterations=3, num_leaves=7,
                         seed=3)
    b2 = train_booster(ds, None, cfg2)
    assert len(b2.trees) == 3


@pytest.mark.parametrize("size,np_rows,chunk", [(512, 2048, 256),
                                                (2048, 2048, 256),
                                                (384, 1920, 128)])
def test_chunk_window_covers_every_range_and_is_aligned(size, np_rows, chunk):
    """The one window rule the split step and the sliced histogram share:
    static length, chunk-aligned start, inside the table, and covering any
    range of at most ``size`` rows wherever it starts."""
    from synapseml_tpu.gbdt.grower import _chunk_window

    for start in (0, 1, chunk - 1, chunk, np_rows // 2 + 7, np_rows - size,
                  np_rows - 1):
        length = min(size, np_rows - start)
        cs, S = _chunk_window(jnp.int32(start), size, np_rows, chunk)
        cs = int(cs)
        assert S == min(size + chunk, np_rows)
        assert cs % chunk == 0 and 0 <= cs and cs + S <= np_rows
        assert cs <= start and start + length <= cs + S


def test_split_counter_names_the_path_that_moves_rows():
    from synapseml_tpu.gbdt.grower import split_counter

    assert split_counter(GrowerConfig(), 28) == "splitsPartitionSort"
    assert split_counter(GrowerConfig(growth_policy="depthwise"), 28) is None


def test_partition_bucket_off_the_chip_is_a_stable_partition():
    """The argsort path against plain NumPy: inside the range left rows
    keep their order before right rows, outside it nothing moves."""
    from synapseml_tpu.gbdt.grower import _partition_bucket

    rng = np.random.default_rng(4)
    Np, FP, chunk, size = 1024, 8, 128, 256
    start, length, fsel, thr = 300, 200, 3, 9
    bT = rng.integers(0, 16, size=(FP, Np)).astype(np.int32)
    pos = rng.permutation(Np).astype(np.int32)
    g, h = (rng.normal(size=Np).astype(np.float32) for _ in range(2))
    m = (rng.uniform(size=Np) > 0.3).astype(np.float32)
    out = _partition_bucket(*map(jnp.asarray, (pos, g, h, m, bT)),
                            jnp.int32(start), jnp.int32(length),
                            jnp.int32(fsel), lambda binrow: binrow > thr,
                            size, chunk, 256, False)
    inside = np.arange(start, start + length)
    right = bT[fsel, inside] > thr
    order = np.arange(Np)
    order[inside] = np.concatenate([inside[~right], inside[right]])
    for got, want in zip(out[:4], (pos, g, h, m)):
        np.testing.assert_array_equal(np.asarray(got), want[order])
    np.testing.assert_array_equal(np.asarray(out[4]), bT[:, order])
    assert int(out[5]) == int((~right).sum())


def test_sparse_csr_input_matches_dense():
    """scipy CSR input (the reference's sparse dataset path) must train the
    identical model to the densified matrix, via Dataset and directly."""
    import scipy.sparse as sp

    from synapseml_tpu.gbdt import Dataset

    rng = np.random.default_rng(7)
    n, f = 3000, 12
    dense = rng.normal(size=(n, f)).astype(np.float32)
    dense[rng.uniform(size=(n, f)) < 0.8] = 0.0      # 80% sparse
    y = (dense[:, 0] + 0.5 * dense[:, 1] > 0.1).astype(np.float32)
    csr = sp.csr_matrix(dense)

    cfg = BoosterConfig(objective="binary", num_iterations=5, num_leaves=15)
    b_dense = train_booster(dense, y, cfg)
    b_csr = train_booster(csr, y, cfg)
    np.testing.assert_allclose(b_dense.predict(dense[:100]),
                               b_csr.predict(dense[:100]), rtol=1e-6)

    ds = Dataset(csr, label=y)
    assert ds.X is None and ds._sparse is not None
    b_ds = train_booster(ds, None, cfg)
    np.testing.assert_allclose(b_dense.predict(dense[:100]),
                               b_ds.predict(dense[:100]), rtol=1e-6)
    # warm start needs raw rows -> densified on demand from the kept CSR
    b_warm = train_booster(ds, None, cfg, init_model=b_ds)
    assert b_warm.num_trees == 10


def test_sparse_nan_election_beyond_sample():
    """NaN-bin election for sparse input must see the FULL matrix: a NaN that
    exists only outside the boundary sample still gets a dedicated NaN bin."""
    import scipy.sparse as sp

    from synapseml_tpu.gbdt import Dataset

    rng = np.random.default_rng(11)
    n = 3000
    dense = rng.normal(size=(n, 3)).astype(np.float32)
    dense[rng.uniform(size=(n, 3)) < 0.7] = 0.0
    # NaNs in feature 1 confined to the TAIL rows: with bin_sample_count=256
    # and seed=0 the row sample misses most of them with high probability,
    # but the full-matrix election must still flag the feature
    dense[n - 5:, 1] = np.nan
    csr = sp.csr_matrix(dense)
    ds = Dataset(csr, bin_sample_count=256)
    assert bool(ds.mapper.nan_mask[1])
    binned = np.asarray(ds.binned)
    nanbin = ds.mapper.nan_bins[1]
    assert (binned[n - 5:, 1] == nanbin).all()

    # predict accepts CSR too
    y = (np.nan_to_num(dense[:, 0]) > 0).astype(np.float32)
    b = train_booster(Dataset(csr, label=y),
                      None, BoosterConfig(objective="binary", num_iterations=3))
    p_csr = b.predict(csr[:50])
    p_dense = b.predict(dense[:50])
    np.testing.assert_allclose(p_csr, p_dense, rtol=1e-6)


def test_feature_fraction_bynode():
    """Per-node feature sampling: deterministic per seed, actually restricts
    the per-node search, and samples identically in the fused scan and the
    host loop (a no-op callback forces the host path)."""
    rng = np.random.default_rng(5)
    n = 2000
    X = rng.normal(size=(n, 8)).astype(np.float32)
    y = (X[:, 0] + 0.5 * X[:, 1] - 0.25 * X[:, 2] > 0).astype(np.float32)
    cfg = BoosterConfig(objective="binary", num_iterations=5, num_leaves=15,
                        feature_fraction_bynode=0.5, seed=9)
    b1 = train_booster(X, y, cfg)
    b2 = train_booster(X, y, cfg)
    for t1, t2 in zip(b1.trees, b2.trees):        # deterministic
        np.testing.assert_array_equal(np.asarray(t1.split_feature),
                                      np.asarray(t2.split_feature))
    b_full = train_booster(X, y, BoosterConfig(
        objective="binary", num_iterations=5, num_leaves=15, seed=9))
    diff = any(not np.array_equal(np.asarray(a.split_feature),
                                  np.asarray(b.split_feature))
               for a, b in zip(b1.trees, b_full.trees))
    assert diff, "bynode sampling had no effect on split choices"
    # fused (b1) vs host loop (callback forces host path) must match exactly
    b_host = train_booster(X, y, cfg, callbacks=[lambda it, trees: None])
    for tf, th in zip(b1.trees, b_host.trees):
        np.testing.assert_array_equal(np.asarray(tf.split_feature),
                                      np.asarray(th.split_feature))
        np.testing.assert_allclose(np.asarray(tf.leaf_value),
                                   np.asarray(th.leaf_value), rtol=1e-6)
    # accuracy stays sane
    assert ((b1.predict(X) > 0.5) == (y > 0.5)).mean() > 0.9


def test_stratified_pos_neg_bagging():
    """posBaggingFraction / negBaggingFraction: per-class sampling rates show
    up in the realized in-bag class balance; fused and host paths agree."""
    rng = np.random.default_rng(6)
    n = 4000
    X = rng.normal(size=(n, 4)).astype(np.float32)
    y = (rng.uniform(size=n) < 0.5).astype(np.float32)
    cfg = BoosterConfig(objective="binary", num_iterations=4,
                        bagging_freq=1, pos_bagging_fraction=0.9,
                        neg_bagging_fraction=0.2, seed=3)
    b = train_booster(X, y, cfg)
    # with negatives sampled at 0.2 vs positives 0.9, root counts shrink
    # asymmetrically; verify via internal_count of the first tree's root
    root_count = int(np.asarray(b.trees[0].internal_count)[0])
    expected = 0.9 * (y > 0).sum() + 0.2 * (y == 0).sum()
    assert abs(root_count - expected) < 0.15 * expected
    # host path (forced by callback) samples identically
    b_host = train_booster(X, y, cfg, callbacks=[lambda it, trees: None])
    for tf, th in zip(b.trees, b_host.trees):
        np.testing.assert_array_equal(np.asarray(tf.split_feature),
                                      np.asarray(th.split_feature))


def test_dart_weighted_drop_runs():
    rng = np.random.default_rng(8)
    X = rng.normal(size=(1000, 4)).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.float32)
    for uniform in (False, True):
        cfg = BoosterConfig(objective="binary", num_iterations=8,
                            boosting_type="dart", drop_rate=0.5,
                            skip_drop=0.0, uniform_drop=uniform, seed=2)
        b = train_booster(X, y, cfg)
        assert b.num_trees == 8
        assert ((b.predict(X) > 0.5) == (y > 0.5)).mean() > 0.9


def test_fused_cache_key_covers_stratified_bagging():
    """Two same-process fits differing only in neg_bagging_fraction must not
    share a fused executable (the fractions are traced-in constants)."""
    rng = np.random.default_rng(12)
    X = rng.normal(size=(2000, 4)).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.float32)
    c1 = BoosterConfig(objective="binary", num_iterations=3, bagging_freq=1,
                       seed=2)
    c2 = BoosterConfig(objective="binary", num_iterations=3, bagging_freq=1,
                       seed=2, neg_bagging_fraction=0.2)
    rc1 = int(np.asarray(train_booster(X, y, c1).trees[0].internal_count)[0])
    rc2 = int(np.asarray(train_booster(X, y, c2).trees[0].internal_count)[0])
    assert rc1 == 2000 and rc2 < 1500, (rc1, rc2)
    # non-binary objectives reject stratified bagging (native parity)
    with pytest.raises(ValueError):
        train_booster(X, np.abs(X[:, 0]),
                      BoosterConfig(objective="regression", num_iterations=2,
                                    bagging_freq=1, pos_bagging_fraction=0.5))


def test_depth_bounded_inference_matches_full_walk(binary_data):
    """Predictions with the true-max-depth pointer chase must equal the
    worst-case num_leaves-1 walk."""
    from synapseml_tpu.gbdt.grower import forest_max_depth, forest_predict

    Xtr, Xte, ytr, _ = binary_data
    bst = train_booster(Xtr, ytr, BoosterConfig(objective="binary",
                                                num_iterations=8))
    d = forest_max_depth(bst.trees)
    assert 1 <= d <= bst.config.num_leaves - 1
    forest = bst.forest()
    full = forest_predict(forest, jnp.asarray(Xte[:100]), output="sum")
    fast = forest_predict(forest, jnp.asarray(Xte[:100]), output="sum",
                          depth=d)
    np.testing.assert_allclose(np.asarray(fast), np.asarray(full), rtol=1e-6)
    # the booster's own predict path uses the cached depth
    assert bst._depth_cache == d
    p = bst.predict(Xte[:50])
    assert np.isfinite(p).all()


def test_dump_model_json(binary_data):
    """dumpModel parity: LightGBM-format JSON with a recursive
    tree_structure whose leaf values reproduce the model's predictions."""
    import json

    Xtr, Xte, ytr, _ = binary_data
    bst = train_booster(Xtr, ytr, BoosterConfig(objective="binary",
                                                num_iterations=4))
    doc = json.loads(bst.dump_model())
    assert doc["name"] == "tree" and doc["num_tree_per_iteration"] == 1
    assert len(doc["tree_info"]) == 4
    assert doc["objective"].startswith("binary")
    t0 = doc["tree_info"][0]["tree_structure"]
    assert t0["decision_type"] in ("<=", "==") and "left_child" in t0

    # walk the JSON tree by hand for a few rows; raw sum must match raw_score
    def walk(node, row):
        while "leaf_value" not in node:
            f, thr = node["split_feature"], node["threshold"]
            x = row[f]
            if np.isnan(x):
                go_left = node["default_left"]
            else:
                go_left = x <= thr
            node = node["left_child"] if go_left else node["right_child"]
        return node["leaf_value"]

    # base score is folded into the first tree's leaves (LightGBM stores no
    # separate base), so the plain leaf sum IS the raw score
    raw = bst.raw_score(Xte[:20])
    for i in range(20):
        s = sum(walk(t["tree_structure"], Xte[i]) for t in doc["tree_info"])
        np.testing.assert_allclose(s, raw[i], rtol=1e-5, atol=1e-6)

    # categorical split: "a||b" threshold string, and routing matches
    rng = np.random.default_rng(3)
    cats = rng.integers(0, 8, size=1500)
    yc = np.isin(cats, [2, 5]).astype(np.float32)
    Xc = np.stack([cats.astype(np.float32),
                   rng.normal(size=1500).astype(np.float32)], 1)
    bc = train_booster(Xc, yc, BoosterConfig(objective="binary",
                                             num_iterations=2),
                       categorical_features=[0])
    dc = json.loads(bc.dump_model())
    root = dc["tree_info"][0]["tree_structure"]
    assert root["decision_type"] == "=="
    left_cats = {int(v) for v in root["threshold"].split("||")}
    assert left_cats and left_cats <= set(range(8))

    def walk_cat(node, row):
        while "leaf_value" not in node:
            if node["decision_type"] == "==":
                inset = str(int(row[node["split_feature"]])) in                     node["threshold"].split("||")
                node = node["left_child"] if inset else node["right_child"]
            else:
                node = (node["left_child"]
                        if row[node["split_feature"]] <= node["threshold"]
                        else node["right_child"])
        return node["leaf_value"]

    raw_c = bc.raw_score(Xc[:30])
    for i in range(30):
        s = sum(walk_cat(t["tree_structure"], Xc[i])
                for t in dc["tree_info"])
        np.testing.assert_allclose(s, raw_c[i], rtol=1e-4, atol=1e-5)


def test_predict_num_iteration(binary_data):
    """num_iteration-limited scoring equals a booster truncated to that many
    rounds (LightGBM predict num_iteration semantics)."""
    Xtr, Xte, ytr, _ = binary_data
    bst = train_booster(Xtr, ytr, BoosterConfig(objective="binary",
                                                num_iterations=8))
    short = Booster(bst.mapper, bst.config, bst.trees[:3],
                    bst.tree_weights[:3], bst.base_score)
    np.testing.assert_allclose(bst.raw_score(Xte[:50], num_iteration=3),
                               short.raw_score(Xte[:50]), rtol=1e-6)
    # out-of-range request clamps to the full model
    np.testing.assert_allclose(bst.raw_score(Xte[:50], num_iteration=99),
                               bst.raw_score(Xte[:50]), rtol=1e-6)

    # rf: prefix scoring must RE-average over the prefix count
    rf = train_booster(Xtr, ytr, BoosterConfig(
        objective="binary", num_iterations=6, boosting_type="rf",
        bagging_freq=1, bagging_fraction=0.6, seed=4))
    rf_short = Booster(rf.mapper, rf.config, rf.trees[:2],
                       rf.tree_weights[:2], rf.base_score)
    np.testing.assert_allclose(rf.raw_score(Xte[:50], num_iteration=2),
                               rf_short.raw_score(Xte[:50]), rtol=1e-5)


def test_multiclass_shap_additivity():
    """Multiclass pred_contrib: per-class blocks of (F+1) whose sums equal
    the per-class raw scores (LightGBM layout)."""
    rng = np.random.default_rng(13)
    n, f, k = 600, 5, 3
    X = rng.normal(size=(n, f)).astype(np.float32)
    y = (X[:, 0] + 0.3 * rng.normal(size=n) > 0).astype(np.float32) \
        + (X[:, 1] > 0.5)
    bst = train_booster(X, y.astype(np.float32),
                        BoosterConfig(objective="multiclass", num_class=k,
                                      num_iterations=4))
    sh = bst.feature_shap(X[:25])
    assert sh.shape == (25, k * (f + 1))
    raw = bst.raw_score(X[:25])                    # (N, K)
    blocks = sh.reshape(25, k, f + 1)
    np.testing.assert_allclose(blocks.sum(axis=2), raw, atol=1e-4)


def test_new_native_params():
    """minDataPerBin / maxBinByFeature / cat_l2 / seeds / start_iteration."""
    rng = np.random.default_rng(14)
    X = rng.normal(size=(2000, 4)).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.float32)

    # maxBinByFeature caps a single feature's bins
    m = compute_bin_mapper(X, max_bin=63, max_bin_by_feature=[8, 63, 63, 63])
    assert m.num_bins[0] <= 8 and m.num_bins[1] > 8

    # min_data_in_bin merges under-filled bins
    sparse_vals = np.concatenate([np.zeros(1990), np.arange(10)]).astype(
        np.float32)[:, None]
    m1 = compute_bin_mapper(sparse_vals, max_bin=255, min_data_in_bin=1)
    m3 = compute_bin_mapper(sparse_vals, max_bin=255, min_data_in_bin=5)
    assert m3.num_bins[0] < m1.num_bins[0]

    # cat_l2 regularizes categorical gains (huge value suppresses cat splits)
    cats = rng.integers(0, 6, size=2000).astype(np.float32)
    Xc = np.stack([cats, X[:, 1]], 1)
    yc = np.isin(cats, [1, 4]).astype(np.float32)
    b_lo = train_booster(Xc, yc, BoosterConfig(objective="binary",
                                               num_iterations=1, cat_l2=0.0),
                         categorical_features=[0])
    b_hi = train_booster(Xc, yc, BoosterConfig(objective="binary",
                                               num_iterations=1, cat_l2=1e9),
                         categorical_features=[0])
    assert int(np.asarray(b_lo.trees[0].split_type)[0]) == 1
    assert int(np.asarray(b_hi.trees[0].split_type)[0]) == 0

    # independent seeds change the sampled feature masks
    import jax

    from synapseml_tpu.gbdt.boosting import _sample_features_impl
    base = BoosterConfig(objective="binary", feature_fraction=0.5, seed=7)
    alt = BoosterConfig(objective="binary", feature_fraction=0.5, seed=7,
                        feature_fraction_seed=99)
    key = jax.random.PRNGKey(7)
    masks_a = [np.asarray(_sample_features_impl(base, 24, key, it))
               for it in range(4)]
    masks_b = [np.asarray(_sample_features_impl(alt, 24, key, it))
               for it in range(4)]
    assert any(not np.array_equal(a, b) for a, b in zip(masks_a, masks_b))

    # start_iteration drops the leading rounds at predict time
    bst = train_booster(X, y, BoosterConfig(objective="binary",
                                            num_iterations=6))
    import dataclasses
    bst.config = dataclasses.replace(bst.config, start_iteration=2)
    tail = Booster(bst.mapper,
                   dataclasses.replace(bst.config, start_iteration=0),
                   bst.trees[2:], bst.tree_weights[2:], bst.base_score)
    np.testing.assert_allclose(bst.raw_score(X[:50]),
                               tail.raw_score(X[:50]), rtol=1e-6)
    # SHAP honors the window (additivity against the windowed prediction)
    sh = bst.feature_shap(X[:10])
    np.testing.assert_allclose(sh.sum(axis=1), bst.raw_score(X[:10]),
                               atol=1e-4)
    # ...but warm starts must NOT inherit the window: continued training sees
    # the full margin
    b2 = train_booster(X, y, BoosterConfig(objective="binary",
                                           num_iterations=2),
                       init_model=bst)
    full = Booster(bst.mapper,
                   dataclasses.replace(bst.config, start_iteration=0),
                   bst.trees, bst.tree_weights, bst.base_score)
    b2_ref = train_booster(X, y, BoosterConfig(objective="binary",
                                               num_iterations=2),
                           init_model=full)
    np.testing.assert_allclose(
        np.asarray(b2.trees[-1].leaf_value),
        np.asarray(b2_ref.trees[-1].leaf_value), rtol=1e-6)


def test_categorical_onehot_and_group_params():
    """maxCatToOnehot (one-vs-rest for small cardinality) and
    minDataPerGroup (thin groups excluded) semantics."""
    rng = np.random.default_rng(15)
    n = 3000
    cats = rng.integers(0, 3, size=n)          # 3 categories <= onehot cap 4
    y = (cats == 1).astype(np.float32)
    X = np.stack([cats.astype(np.float32),
                  rng.normal(size=n).astype(np.float32)], 1)
    bst = train_booster(X, y, BoosterConfig(objective="binary",
                                            num_iterations=10,
                                            min_data_per_group=1),
                        categorical_features=[0])
    t0 = bst.trees[0]
    assert int(np.asarray(t0.split_type)[0]) == 1
    # one-vs-rest: exactly ONE category in the left bitset
    bits = np.asarray(t0.cat_bitset)[0]
    popcount = sum(bin(int(w)).count("1") for w in bits)
    assert popcount == 1
    assert ((bst.predict(X) > 0.5) == (y > 0.5)).mean() > 0.99

    # minDataPerGroup: a tiny perfectly-separating category is ignored when
    # the threshold exceeds its size
    cats2 = np.where(np.arange(n) < 20, 7, rng.integers(0, 3, size=n))
    y2 = (cats2 == 7).astype(np.float32)
    X2 = np.stack([cats2.astype(np.float32),
                   rng.normal(size=n).astype(np.float32)], 1)
    b_lo = train_booster(X2, y2, BoosterConfig(objective="binary",
                                               num_iterations=1,
                                               min_data_per_group=1,
                                               min_data_in_leaf=5),
                         categorical_features=[0])
    b_hi = train_booster(X2, y2, BoosterConfig(objective="binary",
                                               num_iterations=1,
                                               min_data_per_group=100,
                                               min_data_in_leaf=5),
                         categorical_features=[0])
    # low threshold isolates category 7 immediately; high threshold cannot
    bits_lo = np.asarray(b_lo.trees[0].cat_bitset)[0]
    assert (bits_lo[7 >> 5] >> (7 & 31)) & 1
    bits_hi = np.asarray(b_hi.trees[0].cat_bitset)[0]
    assert not ((bits_hi[7 >> 5] >> (7 & 31)) & 1)


def test_xgboost_dart_mode_runs():
    rng = np.random.default_rng(16)
    X = rng.normal(size=(1000, 4)).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.float32)
    b = train_booster(X, y, BoosterConfig(objective="binary",
                                          num_iterations=6,
                                          boosting_type="dart",
                                          drop_rate=0.5, skip_drop=0.0,
                                          xgboost_dart_mode=True, seed=3))
    assert b.num_trees == 6
    assert ((b.predict(X) > 0.5) == (y > 0.5)).mean() > 0.9


def test_weighted_quantile_zero_weight_tail_finite():
    """ADVICE r2: zero-weight rows sort last as an inf sentinel; when the
    quantile lands strictly inside the last positive row's span, the
    interpolation partner must NOT read the inf tail."""
    from synapseml_tpu.gbdt.objectives import _weighted_quantile

    y = jnp.asarray([1.0, 2.0, 7.0])
    w = jnp.asarray([1.0, 9.0, 0.0])       # third row bagged-out / padding
    q = float(_weighted_quantile(y, w, 0.5))
    assert np.isfinite(q), q
    # quantile of {1 (w=1), 2 (w=9)} at 0.5 interpolates inside row 2's span
    assert 1.0 <= q <= 2.0, q
    # init_score path end-to-end: an l1 fit with a zero-weight row stays finite
    rng = np.random.default_rng(5)
    X = rng.normal(size=(64, 3)).astype(np.float32)
    yy = X[:, 0].astype(np.float32)
    sw = np.ones(64, np.float32)
    sw[-1] = 0.0
    b = train_booster(X, yy, BoosterConfig(objective="regression_l1",
                                           num_iterations=3),
                      sample_weight=sw)
    assert np.isfinite(b.predict(X)).all()


def test_fused_cache_key_covers_sampling_seeds():
    """ADVICE r2: extra_seed / feature_fraction_seed are traced-in Python
    ints — two fits differing only in them must not share an executable
    (i.e. must produce different sampling streams, hence different trees)."""
    from synapseml_tpu.gbdt.boosting import _fused_static_key

    base = dict(objective="binary", num_iterations=3, boosting_type="goss",
                feature_fraction=0.5, seed=7)
    c1 = BoosterConfig(**base)
    c2 = BoosterConfig(**base, extra_seed=99)
    c3 = BoosterConfig(**base, feature_fraction_seed=42)
    g = c1.grower(False)
    ks = {_fused_static_key(c, g, 512, 4, 1, 0, "auc", None)
          for c in (c1, c2, c3)}
    assert len(ks) == 3
    rng = np.random.default_rng(17)
    X = rng.normal(size=(512, 4)).astype(np.float32)
    y = (X[:, 0] + 0.3 * rng.normal(size=512) > 0).astype(np.float32)
    t1 = train_booster(X, y, c1).trees
    t3 = train_booster(X, y, c3).trees
    diff = any(not np.array_equal(np.asarray(a.split_feature),
                                  np.asarray(b.split_feature))
               for a, b in zip(t1, t3))
    assert diff, "feature_fraction_seed had no effect (stale fused cache?)"


def test_cat_counts_from_full_column():
    """ADVICE r2: cat_counts (maxCatToOnehot decision) counts distinct
    categories on the FULL column, not the bin-boundary subsample."""
    rng = np.random.default_rng(3)
    n = 5000
    X = rng.normal(size=(n, 2)).astype(np.float32)
    # category column: values 0..2 everywhere except ONE row with value 7
    c = rng.integers(0, 3, size=n).astype(np.float32)
    c[1234] = 7.0
    X[:, 1] = c
    m = compute_bin_mapper(X, sample_count=100, categorical_features=[1],
                           seed=0)
    assert int(m.cat_counts[1]) == 4


def test_cat_presence_sparse_and_override():
    """Sparse path: cat bin occupancy from the FULL CSR matrix (implicit
    zeros + explicit entries), not the boundary sample."""
    import scipy.sparse as sp

    from synapseml_tpu.gbdt.dataset import bin_sparse

    rng = np.random.default_rng(9)
    n = 4000
    dense = np.zeros((n, 3), np.float32)
    dense[:, 0] = rng.normal(size=n)
    # cat col: mostly implicit zeros, a few 1s/2s, ONE row of category 6
    idx = rng.choice(n, size=60, replace=False)
    dense[idx, 1] = rng.integers(1, 3, size=60).astype(np.float32)
    dense[idx[0], 1] = 6.0
    dense[:, 2] = rng.normal(size=n)
    mapper, binned = bin_sparse(sp.csr_matrix(dense), None, 255,
                                bin_sample_count=200,
                                categorical_features=[1], seed=0)
    # distinct bins: {0, 1 or 2 (at least one), 6} — exact count from FULL data
    expect = len(np.unique(dense[:, 1]))
    assert int(mapper.cat_counts[1]) == expect, (mapper.cat_counts[1], expect)


def test_param_list_default_not_shared():
    """get() must hand out a COPY of mutable class-level defaults."""
    from synapseml_tpu.models.gbdt import LightGBMRanker

    r1 = LightGBMRanker()
    lst = r1.getEvalAt()
    lst.append(99)
    assert r1.getEvalAt() == [1, 2, 3, 4, 5]
    assert LightGBMRanker().getEvalAt() == [1, 2, 3, 4, 5]


def test_shap_additivity_with_missing_values():
    """pred_contrib must follow the PREDICTION path's missing routing:
    contributions on NaN rows sum to the raw score (LightGBM TreeSHAP uses
    the same Decision fn as inference)."""
    from synapseml_tpu.gbdt.shap import forest_shap

    rng = np.random.default_rng(23)
    X = rng.normal(size=(500, 4)).astype(np.float32)
    X[rng.random(500) < 0.3, 0] = np.nan
    X[:, 3] = rng.integers(0, 4, size=500)
    X[rng.random(500) < 0.2, 3] = np.nan
    y = (np.nan_to_num(X[:, 0]) + X[:, 1] > 0).astype(np.float32)
    bst = train_booster(X, y, BoosterConfig(objective="binary",
                                            num_iterations=5, num_leaves=8),
                        categorical_features=[3])
    Xt = X[:80]
    contrib = forest_shap(bst, Xt)
    np.testing.assert_allclose(contrib.sum(axis=1), bst.raw_score(Xt),
                               rtol=1e-4, atol=1e-4)


def test_shap_additivity_categorical_edge_values():
    """Categorical SHAP routing parity on edge inputs: -0.5 (tests category
    0), +inf / out-of-range (clip to last tracked bit) — same conversion as
    the prediction path, no crash."""
    from synapseml_tpu.gbdt.shap import forest_shap

    rng = np.random.default_rng(29)
    X = rng.normal(size=(400, 3)).astype(np.float32)
    X[:, 2] = rng.integers(0, 4, size=400)
    y = ((X[:, 2] == 0) | (X[:, 0] > 0.8)).astype(np.float32)
    bst = train_booster(X, y, BoosterConfig(objective="binary",
                                            num_iterations=4, num_leaves=8),
                        categorical_features=[2])
    Xt = X[:12].copy()
    Xt[0, 2] = -0.5          # truncates to category 0
    Xt[1, 2] = np.inf        # clips to the last tracked bit
    Xt[2, 2] = 1e9           # out-of-range
    Xt[3, 2] = -7.0          # clips to -1 -> never a member
    contrib = forest_shap(bst, Xt)
    np.testing.assert_allclose(contrib.sum(axis=1), bst.raw_score(Xt),
                               rtol=1e-4, atol=1e-4)


def test_map_metric_hand_computed_and_early_stopping():
    """map@k eval (LightGBM MapMetric): hand-computed AP on a known ranking,
    plus metric="map@2" driving ranker validation without error."""
    import jax.numpy as jnp

    from synapseml_tpu.gbdt.objectives import make_grouped, map_at_k

    # one query, 4 docs; scores rank doc order [d0, d1, d2, d3];
    # relevance [1, 0, 1, 0] -> AP@4 = (1/1 + 2/3) / 2 = 0.8333
    labels = np.asarray([1.0, 0.0, 1.0, 0.0])
    scores = np.asarray([4.0, 3.0, 2.0, 1.0])
    gi = make_grouped(labels, np.asarray([4]))
    v = float(map_at_k(jnp.asarray(labels), jnp.asarray(scores), gi, 4))
    assert abs(v - (1.0 + 2.0 / 3.0) / 2.0) < 1e-6, v
    # AP@1: only d0 counted, denom min(2,1)=1 -> 1.0
    v1 = float(map_at_k(jnp.asarray(labels), jnp.asarray(scores), gi, 1))
    assert abs(v1 - 1.0) < 1e-6, v1

    rng = np.random.default_rng(11)
    n, q = 600, 30
    X = rng.normal(size=(n, 4)).astype(np.float32)
    y = (rng.random(n) < 0.3).astype(np.float32)
    sizes = np.full(q, n // q, np.int64)
    cfg = BoosterConfig(objective="lambdarank", num_iterations=8,
                        metric="map@2", early_stopping_round=3)
    bst = train_booster(X, y, cfg, group_sizes=sizes,
                        valid=(X, y, None, sizes))
    assert bst.num_trees >= 1


def test_mape_metric_not_misrouted_to_ranking():
    """'mape' must reach the pointwise metric table — startswith('map')
    would have misrouted it into the ranking branch."""
    rng = np.random.default_rng(13)
    X = rng.normal(size=(300, 3)).astype(np.float32)
    y = np.abs(X[:, 0]).astype(np.float32) + 1.0
    b = train_booster(X, y, BoosterConfig(objective="mape", metric="mape",
                                          num_iterations=4),
                      valid=(X, y))
    assert b.num_trees >= 1
    assert np.isfinite(b.predict(X[:10])).all()


def test_objective_loss_metrics_drive_validation():
    """Exp-family / robust objectives early-stop on their own loss
    (LightGBM default metric = the objective), with cfg hyper-parameters
    reaching the metric."""
    import jax.numpy as jnp

    from synapseml_tpu.gbdt.objectives import METRICS

    # hand-check: quantile pinball at alpha 0.8 on a known pair
    y = jnp.asarray([2.0, 0.0])
    pred = jnp.asarray([0.0, 1.0])
    v = float(METRICS["quantile"](y, pred, alpha=0.8))
    # d = [2, -1]: max(.8*2, -.2*2)=1.6; max(.8*-1, -.2*-1)=0.2 -> mean 0.9
    assert abs(v - 0.9) < 1e-6, v
    # poisson NLL decreases as pred approaches y
    a = float(METRICS["poisson"](jnp.asarray([3.0]), jnp.asarray([3.0])))
    b = float(METRICS["poisson"](jnp.asarray([3.0]), jnp.asarray([1.0])))
    assert a < b

    rng = np.random.default_rng(31)
    X = rng.normal(size=(400, 3)).astype(np.float32)
    yv = np.exp(X[:, 0] * 0.5 + 0.1 * rng.normal(size=400)).astype(np.float32)
    for obj in ("poisson", "tweedie", "quantile", "huber", "fair", "gamma"):
        bst = train_booster(X, yv, BoosterConfig(objective=obj,
                                                 num_iterations=4,
                                                 early_stopping_round=3),
                            valid=(X, yv))
        assert bst.num_trees >= 1, obj
        assert np.isfinite(bst.predict(X[:5])).all(), obj


def test_cross_entropy_soft_labels():
    """cross_entropy/xentropy: binary log-loss over CONTINUOUS labels in
    [0,1] (LightGBM xentropy); prediction is a probability."""
    rng = np.random.default_rng(37)
    X = rng.normal(size=(500, 3)).astype(np.float32)
    # soft targets: a noisy probability driven by f0
    y = (1.0 / (1.0 + np.exp(-2.0 * X[:, 0]))
         + 0.05 * rng.normal(size=500)).clip(0, 1).astype(np.float32)
    for obj in ("cross_entropy", "xentropy"):
        bst = train_booster(X, y, BoosterConfig(objective=obj,
                                                num_iterations=6,
                                                early_stopping_round=3),
                            valid=(X, y))
        p = bst.predict(X[:100])
        assert ((p >= 0) & (p <= 1)).all()
        # correlation with the soft target, not just finiteness
        assert np.corrcoef(p, y[:100])[0, 1] > 0.7


def test_weighted_validation_metrics():
    """Validation sample weights (valid[2]) weight the eval metric —
    LightGBM semantics. A weight vector concentrated on mispredicted rows
    must change the metric value."""
    import jax.numpy as jnp

    from synapseml_tpu.gbdt.objectives import METRICS

    y = jnp.asarray([1.0, 0.0, 1.0, 0.0])
    p = jnp.asarray([0.9, 0.1, 0.2, 0.8])    # rows 2,3 badly predicted
    unw = float(METRICS["binary_logloss"](y, p))
    heavy = float(METRICS["binary_logloss"](y, p,
                                            weight=jnp.asarray(
                                                [0.0, 0.0, 1.0, 1.0])))
    light = float(METRICS["binary_logloss"](y, p,
                                            weight=jnp.asarray(
                                                [1.0, 1.0, 0.0, 0.0])))
    assert light < unw < heavy
    # weighted rmse hand-check: sqrt((1*4 + 3*1)/4)
    r = float(METRICS["rmse"](jnp.asarray([0.0, 0.0]),
                              jnp.asarray([2.0, 1.0]),
                              weight=jnp.asarray([1.0, 3.0])))
    assert abs(r - np.sqrt((4.0 + 3.0) / 4.0)) < 1e-6

    # end-to-end: the recorded best_score IS the weighted logloss of the
    # best iteration's predictions (reverting the wv plumbing would leave
    # best_score at the unweighted value and fail this)
    rng = np.random.default_rng(41)
    X = rng.normal(size=(400, 3)).astype(np.float32)
    yy = (X[:, 0] > 0).astype(np.float32)
    wv = np.ones(400, np.float32)
    wv[:200] = 10.0
    b = train_booster(X, yy, BoosterConfig(objective="binary",
                                           num_iterations=4,
                                           metric="binary_logloss"),
                      valid=(X, yy, wv, None))
    pred_best = b.predict(X, num_iteration=b.best_iteration + 1)
    expect_w = float(METRICS["binary_logloss"](
        jnp.asarray(yy), jnp.asarray(pred_best), weight=jnp.asarray(wv)))
    expect_unw = float(METRICS["binary_logloss"](jnp.asarray(yy),
                                                 jnp.asarray(pred_best)))
    assert abs(b.best_score - expect_w) < 1e-5, (b.best_score, expect_w)
    assert abs(expect_w - expect_unw) > 1e-6   # the weights actually matter


def test_auc_tie_correction():
    """AUC handles tied scores via the trapezoid rule (half credit), with
    weights — validated against hand computation and random agreement with
    the rank formula when no ties exist."""
    import jax.numpy as jnp

    from synapseml_tpu.gbdt.objectives import auc

    # all scores tied -> AUC exactly 0.5 (previously 0.0/1.0 by sort order)
    y = jnp.asarray([1.0, 0.0, 1.0, 0.0])
    s = jnp.zeros(4)
    assert abs(float(auc(y, s)) - 0.5) < 1e-6
    # hand case: scores [1,1,2], labels [0,1,1]: pos@1 ties one neg (0.5),
    # pos@2 beats one neg (1.0) -> auc = 1.5/2
    v = float(auc(jnp.asarray([0.0, 1.0, 1.0]), jnp.asarray([1.0, 1.0, 2.0])))
    assert abs(v - 0.75) < 1e-6
    # weighted hand case: same but neg weight 2: pos@1 -> 0.5*2, pos@2 -> 2
    v = float(auc(jnp.asarray([0.0, 1.0, 1.0]), jnp.asarray([1.0, 1.0, 2.0]),
                  jnp.asarray([2.0, 1.0, 1.0])))
    assert abs(v - (1.0 + 2.0) / (2.0 * 2.0)) < 1e-6
    # no ties: matches the Mann-Whitney rank statistic computed in numpy
    rng = np.random.default_rng(3)
    yy = (rng.random(200) > 0.5).astype(np.float32)
    sc = rng.normal(size=200).astype(np.float32)
    got = float(auc(jnp.asarray(yy), jnp.asarray(sc)))
    pos_s, neg_s = sc[yy > 0], sc[yy == 0]
    expect = (pos_s[:, None] > neg_s[None, :]).mean()
    assert abs(got - float(expect)) < 1e-5


def test_label_gain_table_wired():
    """labelGain (LightGBMRankerParams) replaces the default 2^label - 1
    gains in BOTH the lambdarank objective and the NDCG eval."""
    from synapseml_tpu.gbdt.objectives import make_grouped, ndcg_at_k

    labels = np.asarray([2.0, 1.0, 0.0])
    scores = np.asarray([1.0, 2.0, 3.0])   # worst ordering
    gi = make_grouped(labels, np.asarray([3]))
    # custom gains [0, 1, 10]: DCG = 0/1 + 1/log2(3) + 10/2;
    # IDCG = 10/1 + 1/log2(3) + 0
    got = float(ndcg_at_k(jnp.asarray(labels), jnp.asarray(scores), gi, 3,
                          label_gain=(0.0, 1.0, 10.0)))
    import math

    dcg = 1.0 / math.log2(3) + 10.0 / 2.0
    idcg = 10.0 + 1.0 / math.log2(3)
    assert abs(got - dcg / idcg) < 1e-6, got
    # default table still matches the old formula
    got_d = float(ndcg_at_k(jnp.asarray(labels), jnp.asarray(scores), gi, 3))
    dcg_d = 1.0 / math.log2(3) + 3.0 / 2.0
    idcg_d = 3.0 + 1.0 / math.log2(3)
    assert abs(got_d - dcg_d / idcg_d) < 1e-6

    # training with a degenerate gain table that nulls label 1 must differ
    # from the default (the table reaches the objective)
    rng = np.random.default_rng(19)
    n, q = 400, 20
    X = rng.normal(size=(n, 3)).astype(np.float32)
    y = rng.integers(0, 3, size=n).astype(np.float32)
    sizes = np.full(q, n // q, np.int64)
    b1 = train_booster(X, y, BoosterConfig(objective="lambdarank",
                                           num_iterations=4, seed=3),
                       group_sizes=sizes)
    b2 = train_booster(X, y, BoosterConfig(objective="lambdarank",
                                           num_iterations=4, seed=3,
                                           label_gain=(0.0, 0.0, 100.0)),
                       group_sizes=sizes)
    assert not np.allclose(b1.predict(X[:50]), b2.predict(X[:50]))


def test_label_gain_ragged_groups_and_validation():
    """Pad slots contribute ZERO gain even when the table's entry 0 is
    nonzero (ragged groups), and an undersized table fails fast like
    LightGBM."""
    import math

    from synapseml_tpu.gbdt.objectives import make_grouped, ndcg_at_k

    # ragged: group sizes (1, 3); nonzero gain for label 0
    labels = np.asarray([1.0, 1.0, 0.0, 0.0])
    scores = np.asarray([5.0, 3.0, 2.0, 1.0])
    gi = make_grouped(labels, np.asarray([1, 3]))
    got = float(ndcg_at_k(jnp.asarray(labels), jnp.asarray(scores), gi, 3,
                          label_gain=(1.0, 7.0)))
    # group 1 (single relevant doc): ndcg 1.0. group 2: perfect order of
    # [1,0,0] -> dcg = 7 + 1/log2(3) + 1/2, idcg identical -> 1.0
    assert abs(got - 1.0) < 1e-6, got
    with pytest.raises(ValueError, match="label_gain"):
        rng = np.random.default_rng(5)
        X = rng.normal(size=(40, 2)).astype(np.float32)
        y = rng.integers(0, 4, size=40).astype(np.float32)
        train_booster(X, y, BoosterConfig(objective="lambdarank",
                                          num_iterations=2,
                                          label_gain=(0.0, 1.0)),
                      group_sizes=np.full(4, 10, np.int64))


def test_serving_fn_matches_predict():
    """serving_fn (single fused jitted dispatch, the io/serving handler
    path) must agree with predict() for binary and multiclass models."""
    import numpy as np

    from synapseml_tpu.gbdt import BoosterConfig, Dataset, train_booster

    rng = np.random.default_rng(0)
    X = rng.normal(size=(600, 6)).astype(np.float32)
    yb = (X[:, 0] * X[:, 1] > 0).astype(np.float32)
    b = train_booster(Dataset(X, yb), None,
                      BoosterConfig(objective="binary", num_iterations=10,
                                    num_leaves=15))
    np.testing.assert_allclose(np.asarray(b.serving_fn()(X)), b.predict(X),
                               rtol=1e-6, atol=1e-6)

    ym = (np.digitize(X[:, 0], [-0.5, 0.5])).astype(np.float32)
    bm = train_booster(Dataset(X, ym), None,
                       BoosterConfig(objective="multiclass", num_class=3,
                                     num_iterations=6, num_leaves=7))
    np.testing.assert_allclose(np.asarray(bm.serving_fn()(X)),
                               bm.predict(X), rtol=1e-6, atol=1e-6)

    # the prediction window must apply to serving too (code-review r5)
    bw = train_booster(Dataset(X, yb), None,
                       BoosterConfig(objective="binary", num_iterations=10,
                                     num_leaves=15, start_iteration=4))
    np.testing.assert_allclose(np.asarray(bw.serving_fn()(X)),
                               bw.predict(X), rtol=1e-6, atol=1e-6)
