"""The split step's stable-partition kernel against the XLA path it replaces
on the chip: ``argsort`` of the 4-way key plus five gathers. Bit for bit.

The kernel runs interpreted here (the grower's ``partition_window`` is
replaced by one with ``interpret=True``), at a small chunk and a small
feature block; tiling is checked by the compile-only test in
``test_chip_bringup.py`` and on the chip by ``test_tpu_e2e.py``.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from synapseml_tpu.gbdt import grower
from synapseml_tpu.gbdt.grower import GrowerConfig, grow_tree
from synapseml_tpu.ops.quantize import apply_bins, compute_bin_mapper

CHUNK = 256          # the grower's row chunk here; the kernel's step is 128
NP = 8 * CHUNK


@pytest.fixture(autouse=True)
def small_interpreted_kernel(monkeypatch):
    from synapseml_tpu.ops import partition_kernel

    monkeypatch.setattr(partition_kernel, "PARTITION_CHUNK", 128)
    monkeypatch.setattr(partition_kernel, "FEATURE_BLOCK", 16)
    monkeypatch.setattr(grower, "partition_window", functools.partial(
        partition_kernel.partition_window, interpret=True))


def _bits(x):
    x = jnp.asarray(x)
    return np.asarray(x if x.dtype == jnp.int32
                      else lax.bitcast_convert_type(x, jnp.int32))


def _table(seed, B=256, fp=8, bag=False, grads="normal", pos_base=0):
    rng = np.random.default_rng(seed)
    bT = jnp.asarray(rng.integers(0, B, size=(fp, NP)), jnp.int32)
    g = rng.normal(size=NP).astype(np.float32)
    if grads == "negative_and_denormal":
        g = -np.abs(g)
        g[::3] = np.float32(-1e-41)          # denormal-sized
        g[1::7] = np.float32(3e-39)
        g[5::11] = -0.0
    h = rng.uniform(0.1, 2.0, size=NP).astype(np.float32)
    m = ((rng.uniform(size=NP) > 0.3).astype(np.float32) if bag
         else np.ones(NP, np.float32))
    pos = rng.permutation(NP).astype(np.int32) + pos_base
    return (jnp.asarray(pos), jnp.asarray(g * m), jnp.asarray(h * m),
            jnp.asarray(m), bT)


# (start, length, bucket size, route, table keywords)
CASES = {
    "length_zero": (700, 0, 2 * CHUNK, "half", {}),
    "whole_window": (0, NP, NP, "half", {}),
    "unaligned_both_ends": (CHUNK + 37, 3 * CHUNK - 101, 4 * CHUNK, "half",
                            {}),
    "one_row": (3 * CHUNK + 5, 1, 2 * CHUNK, "half", {}),
    "one_row_goes_right": (3 * CHUNK + 5, 1, 2 * CHUNK, "all_right", {}),
    "window_flush_with_table_end": (NP - 2 * CHUNK + 11, 2 * CHUNK - 11,
                                    2 * CHUNK, "half", {}),
    "range_ends_at_table_end_small_bucket": (NP - 300, 300, 2 * CHUNK,
                                             "half", {}),
    "all_left": (CHUNK + 37, 3 * CHUNK - 101, 4 * CHUNK, "all_left", {}),
    "all_right": (CHUNK + 37, 3 * CHUNK - 101, 4 * CHUNK, "all_right", {}),
    "whole_table_all_left": (0, NP, NP, "all_left", {}),
    "whole_table_all_right": (0, NP, NP, "all_right", {}),
    "left_count_a_whole_tile": (0, NP, NP, "first_384_left", {}),
    "bagging_mask_with_zeros": (CHUNK - 3, 2 * CHUNK, 4 * CHUNK, "half",
                                dict(bag=True)),
    "bins_256": (5, 4 * CHUNK - 9, 4 * CHUNK, "half", dict(B=256)),
    "bins_1024": (5, 4 * CHUNK - 9, 4 * CHUNK, "half", dict(B=1024)),
    "features_32_bins_1024": (5, 4 * CHUNK - 9, 4 * CHUNK, "half",
                              dict(B=1024, fp=32)),
    # feature blocks of 16 here: two whole ones; three with a short last
    # one (40 = 16 + 16 + 8); one of 8
    "two_feature_blocks": (5, 4 * CHUNK - 9, 4 * CHUNK, "half", dict(fp=32)),
    "short_last_feature_block": (CHUNK + 37, 3 * CHUNK - 101, 4 * CHUNK,
                                 "half", dict(fp=40)),
    "short_last_feature_block_bins_1024": (0, NP, NP, "half",
                                           dict(B=1024, fp=40)),
    "negative_and_denormal_gradients": (CHUNK + 1, 2 * CHUNK + 77,
                                        4 * CHUNK, "half",
                                        dict(grads="negative_and_denormal")),
    "pos_above_2_to_24": (CHUNK + 1, 2 * CHUNK + 77, 4 * CHUNK, "half",
                          dict(pos_base=(1 << 24) + 12345)),
    "pos_near_int32_max": (CHUNK + 1, 2 * CHUNK + 77, 4 * CHUNK, "half",
                           dict(pos_base=(1 << 31) - 1 - NP)),
}


def _route(kind, B):
    if kind == "all_left":
        return lambda binrow: jnp.zeros(binrow.shape, bool)
    if kind == "all_right":
        return lambda binrow: jnp.ones(binrow.shape, bool)
    return lambda binrow: binrow > B // 2


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_matches_argsort_and_five_gathers(case):
    start, length, size, kind, kw = CASES[case]
    B = kw.get("B", 256)
    pos, g, h, m, bT = _table(sorted(CASES).index(case), **kw)
    if kind == "first_384_left":
        # the left stream ends on a tile boundary: the seam tile is all the
        # right stream's
        bT = bT.at[0].set(jnp.where(jnp.arange(NP) < 384, 0, B - 1))
    route = _route(kind, B)
    args = (pos, g, h, m, bT, jnp.int32(start), jnp.int32(length),
            jnp.int32(0), route, size, CHUNK, B)
    want = grower._partition_bucket(*args, False)
    got = grower._partition_bucket(*args, True)
    assert int(got[5]) == int(want[5])                  # nl_loc
    for name, a, b in zip(("pos", "gs", "hs", "ms", "bT"), got, want):
        assert a.dtype == b.dtype and a.shape == b.shape, name
        np.testing.assert_array_equal(_bits(a), _bits(b), err_msg=name)


def test_two_classes_order_as_the_four_way_key():
    """The kernel's reference (two classes) is the grower's argsort of the
    4-way key, for every split of a window into before / left / right /
    after."""
    from synapseml_tpu.ops.partition_kernel import partition_window_xla

    rng = np.random.default_rng(0)
    S = 512
    idx = np.arange(S)
    for start, length in [(0, S), (100, 300), (511, 1), (17, 0)]:
        gr = rng.uniform(size=S) > 0.5
        key = np.where(idx < start, -1, np.where(idx >= start + length, 2,
                                                 gr.astype(np.int32)))
        second = (idx >= start + length) | ((idx >= start) & gr)
        src4 = np.asarray(jnp.argsort(jnp.asarray(key), stable=True))
        v = jnp.arange(S, dtype=jnp.int32)
        got = partition_window_xla(jnp.asarray(second), 0, v[None, :], v,
                                   v.astype(jnp.float32),
                                   v.astype(jnp.float32),
                                   v.astype(jnp.float32))
        np.testing.assert_array_equal(np.asarray(got[0]), src4)


def test_kernel_step_divides_the_row_chunk():
    from synapseml_tpu.ops.partition_kernel import partition_chunk

    assert partition_chunk(2048) == 128          # patched to 128 here
    assert partition_chunk(96) == 96
    assert partition_chunk(192) == 96


@pytest.mark.parametrize("fp,block", [(8, 8), (32, 32), (128, 128),
                                      (136, 72), (256, 128), (1024, 128),
                                      (2048, 128), (2056, 128)])
def test_feature_block_is_bounded_whatever_the_width(monkeypatch, fp, block):
    from synapseml_tpu.ops import partition_kernel

    monkeypatch.setattr(partition_kernel, "FEATURE_BLOCK", 128)
    assert partition_kernel.feature_block(fp) == block
    assert block % 8 == 0 and block <= 128
    assert -(-fp // block) == -(-fp // 128)      # the fewest blocks


def test_the_entry_compiles_unless_told_to_interpret():
    """``partition_window`` has no backend rule of its own: off the chip,
    without ``interpret``, the TPU kernel does not run."""
    from synapseml_tpu.ops.partition_kernel import partition_window

    pos, g, h, m, bT = _table(0)
    with pytest.raises(Exception):
        jax.block_until_ready(partition_window(
            jnp.zeros(2 * CHUNK, bool), 0, bT, pos, g, h, m, 256, CHUNK))


def test_off_the_chip_the_grower_takes_the_xla_path():
    from synapseml_tpu.ops.partition_kernel import partition_kernel_available

    assert jax.default_backend() != "tpu"
    assert partition_kernel_available(256, 32) is False


# ---------------------------------------------------------------------------
# grow_tree: the kernel path forced (interpreted) against the XLA path
# ---------------------------------------------------------------------------

def _binary_fixture():
    from sklearn.datasets import load_breast_cancer

    X, y = load_breast_cancer(return_X_y=True)
    X = X.astype(np.float32)
    X[::7, 3] = np.nan                       # learned missing direction
    return X, y.astype(np.float32), []


def _categorical_fixture():
    rng = np.random.default_rng(3)
    n = 1500
    cats = rng.integers(0, 10, size=n)
    y = np.isin(cats, [2, 5, 7]).astype(np.float32)
    X = np.stack([cats.astype(np.float32),
                  rng.normal(size=n).astype(np.float32),
                  (cats % 3 + rng.normal(size=n)).astype(np.float32)], 1)
    return X, y, [0]


# fixture, bagged, max_bin, GrowerConfig overrides, extras (monotone
# constraints, a node key for feature_fraction_bynode). The two paths that
# are left: the kernel (the chip's) and argsort + five gathers (elsewhere).
GROW_CASES = {
    "binary": ("binary", False, 255, {}, {}),
    "binary_bagged": ("binary", True, 255, {}, {}),
    "binary_bins_1023": ("binary", False, 1023, {}, {}),
    "categorical": ("categorical", False, 255, {}, {}),
    "categorical_bagged": ("categorical", True, 255, {}, {}),
    # what the cross-layout tests fed: the NaN column at the default
    # min_data_in_leaf with 15 leaves, and 31 leaves of at least 5 rows
    "nan_15_leaves": ("binary", False, 255, dict(min_data_in_leaf=20), {}),
    "nan_15_leaves_bagged": ("binary", True, 255,
                             dict(min_data_in_leaf=20), {}),
    "nan_31_leaves": ("binary", False, 255, dict(num_leaves=31), {}),
    "nan_31_leaves_bagged": ("binary", True, 255, dict(num_leaves=31), {}),
    "bynode_feature_fraction": ("binary", False, 255,
                                dict(feature_fraction_bynode=0.5),
                                dict(node_key=7)),
    "monotone": ("binary", False, 255, {}, dict(monotone=True)),
    "max_depth_3": ("binary", False, 255, dict(max_depth=3),
                    dict(min_splits=4)),
    "two_leaves": ("binary", False, 255, dict(num_leaves=2),
                   dict(min_splits=1)),
}


@pytest.mark.parametrize("case", sorted(GROW_CASES))
def test_grow_tree_identical_through_the_kernel(monkeypatch, case):
    fixture, bagged, max_bin, over, extra = GROW_CASES[case]
    monkeypatch.setenv("SYNAPSEML_TPU_HIST_CHUNK", "128")
    X, y, cat = (_binary_fixture if fixture == "binary"
                 else _categorical_fixture)()
    n, f = X.shape
    mapper = compute_bin_mapper(X, max_bin=max_bin, categorical_features=cat)
    binned = apply_bins(mapper, X)
    rng = np.random.default_rng(11)
    p = 1.0 / (1.0 + np.exp(-rng.normal(scale=0.3, size=n)))
    g, h = jnp.asarray(p - y, jnp.float32), jnp.asarray(p * (1 - p),
                                                        jnp.float32)
    bag = jnp.asarray((rng.uniform(size=n) > 0.3) if bagged
                      else np.ones(n), jnp.float32)
    is_cat = jnp.zeros(f, bool).at[jnp.asarray(cat, jnp.int32)].set(True)
    cfg = GrowerConfig(**{**dict(num_leaves=15, num_bins=max_bin,
                                 min_data_in_leaf=5,
                                 has_categorical=bool(cat)), **over})
    nan_bins = jnp.asarray(mapper.nan_bins, jnp.int32)
    mono = jnp.asarray(rng.integers(-1, 2, size=f) if extra.get("monotone")
                       else np.zeros(f), jnp.int32)
    node_key = (jax.random.key_data(jax.random.PRNGKey(extra["node_key"]))
                if "node_key" in extra else None)

    def grow():
        # the choice is made while tracing, and grow_tree's own jit would
        # answer the second call from the first's trace
        return jax.jit(lambda b, g_, h_, m_: grow_tree.__wrapped__(
            b, g_, h_, m_, jnp.ones(f, bool), is_cat, mono, cfg,
            nan_bins=nan_bins, node_key=node_key))(binned, g, h, bag)

    tree_x, node_x = grow()
    taken = []

    def forced(num_bins_padded, fp):
        taken.append((num_bins_padded, fp))
        return True

    monkeypatch.setattr(grower, "partition_kernel_available", forced)
    tree_k, node_k = grow()
    assert taken and int(tree_x.num_splits) >= extra.get("min_splits", 8)
    for field in tree_x._fields:
        # bool and uint32 fields compare as int32
        got, want = (_bits(getattr(t, field).astype(jnp.int32)
                           if getattr(t, field).dtype != jnp.float32
                           else getattr(t, field))
                     for t in (tree_k, tree_x))
        np.testing.assert_array_equal(got, want, err_msg=field)
    np.testing.assert_array_equal(np.asarray(node_k), np.asarray(node_x))


# ---------------------------------------------------------------------------
# the fit's record says which path moved the rows
# ---------------------------------------------------------------------------

def test_fit_counts_its_splits_under_the_path_that_moved_the_rows():
    from synapseml_tpu.core.logging import InstrumentationMeasures
    from synapseml_tpu.gbdt import BoosterConfig, train_booster

    rng = np.random.default_rng(5)
    X = rng.normal(size=(1500, 6)).astype(np.float32)
    y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(np.float32)
    m = InstrumentationMeasures()
    bst = train_booster(X, y, BoosterConfig(num_iterations=3, seed=7),
                        measures=m)
    counted = {k: v for k, v in m.report().items()
               if k.startswith("count:splitsPartition")}
    assert counted == {"count:splitsPartitionSort":
                       sum(int(t.num_splits) for t in bst.trees)}
