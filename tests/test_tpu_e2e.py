"""Real-TPU end-to-end suite (SURVEY §4 item 5: the reference's only true
multi-node testing is its Databricks/Synapse notebook E2E jobs; the analog
here is a small on-chip suite).

Run with:  SYNAPSEML_TPU_E2E=1 python -m pytest tests/test_tpu_e2e.py -q
(the normal suite pins the cpu platform, so these auto-skip there; with the
variable set, finding no TPU is a failure, not a skip).
"""

import os

import numpy as np
import pytest

pytestmark = pytest.mark.skipif(
    os.environ.get("SYNAPSEML_TPU_E2E") != "1",
    reason="real-TPU e2e: set SYNAPSEML_TPU_E2E=1 (requires a TPU device)")


@pytest.fixture(scope="module")
def tpu():
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        pytest.fail(f"SYNAPSEML_TPU_E2E=1 but jax found {devs[0].platform!r} "
                    f"({devs[0].device_kind}), not a TPU")
    return devs[0]


def test_pallas_kernel_matches_fallback_on_chip(tpu):
    """The MXU histogram kernel must agree with the XLA scatter fallback on
    REAL hardware (CI only checks the interpreter)."""
    import jax.numpy as jnp

    from synapseml_tpu.ops.hist_kernel import _hist_pallas, _hist_xla

    rng = np.random.default_rng(0)
    n, fp, b = 4096, 8, 256
    bT = jnp.asarray(rng.integers(0, 255, size=(fp, n)), jnp.int32)
    g = jnp.asarray(rng.normal(size=n), jnp.float32)
    h = jnp.asarray(rng.uniform(0.1, 1, size=n), jnp.float32)
    m = jnp.ones(n, jnp.float32)
    kern = np.asarray(_hist_pallas(bT, g, h, m, b))
    ref = np.asarray(_hist_xla(bT, g, h, m, b))
    np.testing.assert_allclose(kern, ref, rtol=1e-3, atol=1e-3)


def test_gbdt_train_predict_on_chip(tpu):
    from synapseml_tpu.gbdt import BoosterConfig, Dataset, train_booster

    rng = np.random.default_rng(1)
    X = rng.normal(size=(20_000, 12)).astype(np.float32)
    y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(np.float32)
    ds = Dataset(X, y).block_until_ready()
    bst = train_booster(ds, None, BoosterConfig(objective="binary",
                                                num_iterations=10))
    acc = ((bst.predict(X[:2000]) > 0.5) == (y[:2000] > 0.5)).mean()
    assert acc > 0.9, acc


def test_bucketed_runner_donates_on_chip(tpu):
    """The serving runner donates its padded input buffer on TPU (CPU never
    exercises donation): bucketed predict must equal plain predict, on every
    bucket and after warmup."""
    from synapseml_tpu.gbdt import BoosterConfig, train_booster

    rng = np.random.default_rng(9)
    X = rng.normal(size=(5_000, 8)).astype(np.float32)
    y = (X[:, 0] + X[:, 1] > 0).astype(np.float32)
    bst = train_booster(X, y, BoosterConfig(objective="binary",
                                            num_iterations=5))
    serve = bst.serving_fn(max_batch_size=32)
    stats = serve.warmup()
    assert stats["total_compiles"] == len(stats["buckets"])
    want = bst.predict(X[:100])
    for n in (1, 3, 32, 100):
        np.testing.assert_allclose(np.asarray(serve(X[:n])), want[:n],
                                   rtol=1e-6, atol=1e-6)
    after = serve.runner.stats()
    assert after["total_compiles"] == after["warmup_compiles"]


def test_onnx_bf16_on_chip(tpu):
    import jax

    from synapseml_tpu.onnx.importer import OnnxFunction
    from synapseml_tpu.onnx.modelgen import make_resnet

    m = make_resnet(18, num_classes=10, image_size=64)
    x = np.random.default_rng(3).normal(size=(8, 3, 64, 64)).astype(np.float32)
    f32 = np.asarray(jax.jit(OnnxFunction(m).as_jax(["data"])[0])(x)[0])
    b16 = np.asarray(jax.jit(
        OnnxFunction(m, precision="bfloat16").as_jax(["data"])[0])(x)[0])
    # logits-level agreement; argmax agreement on nearly all rows
    assert (f32.argmax(-1) == b16.argmax(-1)).mean() >= 0.9


def test_dl_step_on_chip(tpu):
    import jax.numpy as jnp

    from synapseml_tpu.dl import FlaxTrainer, TrainConfig, make_backbone

    rng = np.random.default_rng(4)
    X = rng.uniform(size=(64, 32, 32, 3)).astype(np.float32)
    y = rng.integers(0, 2, size=64).astype(np.float32)
    tr = FlaxTrainer(make_backbone("resnet18", 2, dtype=jnp.bfloat16),
                     TrainConfig(batch_size=16, max_epochs=1))
    tr.fit(X, y)
    assert np.isfinite(np.asarray(tr.predict_logits(X[:8]))).all()


def test_sparse_ingest_on_chip(tpu):
    """Device-side CSR binning (zero-bin broadcast + nnz scatter) matches
    dense apply_bins on REAL hardware (CI checks the CPU path only)."""
    import scipy.sparse as sp

    from synapseml_tpu.gbdt import BoosterConfig, Dataset, train_booster

    rng = np.random.default_rng(5)
    n, f = 50_000, 30
    nnz = int(n * f * 0.02)
    r = rng.integers(0, n, size=nnz)
    c = rng.integers(0, f, size=nnz)
    v = rng.normal(size=nnz).astype(np.float32)
    Xs = sp.csr_matrix((v, (r, c)), shape=(n, f))
    y = (np.asarray(Xs[:, 0].todense()).ravel() > 0.1).astype(np.float32)
    ds = Dataset(Xs, y).block_until_ready()
    Xd = np.asarray(Xs.todense(), np.float32)
    from synapseml_tpu.ops.quantize import apply_bins

    dense_binned = np.asarray(apply_bins(ds.mapper, Xd))
    np.testing.assert_array_equal(np.asarray(ds.binned), dense_binned)
    bst = train_booster(ds, None, BoosterConfig(objective="binary",
                                                num_iterations=5))
    assert np.isfinite(bst.predict(Xd[:500])).all()


def test_kernel_chunk_variants_agree_on_chip(tpu):
    """The grid-sweep knobs (chunk, feature_block) are bitwise-neutral on
    REAL hardware."""
    import jax.numpy as jnp

    from synapseml_tpu.ops.hist_kernel import _hist_pallas

    rng = np.random.default_rng(6)
    n, fp, b = 8192, 16, 256
    bT = jnp.asarray(rng.integers(0, 255, size=(fp, n)), jnp.int32)
    g = jnp.asarray(rng.normal(size=n), jnp.float32)
    h = jnp.ones(n, jnp.float32)
    m = jnp.ones(n, jnp.float32)
    # explicit baseline chunk: the env-tuned default (SYNAPSEML_TPU_HIST_CHUNK)
    # may be a non-divisor of n or coincide with a swept variant
    base = np.asarray(_hist_pallas(bT, g, h, m, b, chunk=2048))
    for chunk in (1024, 4096):
        for fb in (8, 16):
            got = np.asarray(_hist_pallas(bT, g, h, m, b, chunk=chunk,
                                          feature_block=fb))
            np.testing.assert_array_equal(got, base)


def test_segmented_kernel_on_chip(tpu):
    """Scalar-prefetch segmented kernel on REAL hardware vs the scatter
    reference; the gate is True on the chip or raises KernelError."""
    import jax.numpy as jnp

    from synapseml_tpu.ops.hist_kernel import (_hist_pallas_range, _hist_xla,
                                               segmented_histograms_available)

    assert segmented_histograms_available(256) is True
    rng = np.random.default_rng(0)
    FP, Np, B = 16, 16384, 256
    bT = jnp.asarray(rng.integers(0, B, size=(FP, Np)).astype(np.int32))
    g = jnp.asarray(rng.normal(size=Np).astype(np.float32))
    h = jnp.asarray(rng.uniform(0.1, 1, size=Np).astype(np.float32))
    m = jnp.ones(Np, jnp.float32)
    got = np.asarray(_hist_pallas_range(bT, g, h, m, 5000, 3000, B, 8192))
    idx = np.arange(Np)
    sel = jnp.asarray(((idx >= 5000) & (idx < 8000)).astype(np.float32))
    want = np.asarray(_hist_xla(bT, g * sel, h * sel, m * sel, B))
    np.testing.assert_allclose(got, want, rtol=1e-3, atol=1e-3)


def test_grower_segmented_matches_sliced_on_chip(tpu, monkeypatch):
    """The segmented histogram kernel and the sliced path
    (SYNAPSEML_TPU_SEGMENTED=0) must grow identical trees on hardware."""
    import jax

    from synapseml_tpu.gbdt import BoosterConfig, train_booster
    from synapseml_tpu.gbdt import boosting, grower

    rng = np.random.default_rng(2)
    X = rng.normal(size=(20000, 12)).astype(np.float32)
    y = (X[:, 0] * X[:, 1] > 0).astype(np.float32)
    cfg = dict(objective="binary", num_iterations=3)

    def fresh_trace():
        # the env is read at trace time, and grow_tree's own jit would
        # answer the second fit from the first's trace
        boosting._FUSED_RUNNERS.clear()
        jax.clear_caches()

    def no_segments(*a, **k):
        raise AssertionError("the sliced path took the segmented kernel")

    fresh_trace()
    b_seg = train_booster(X, y, BoosterConfig(**cfg))
    monkeypatch.setenv("SYNAPSEML_TPU_SEGMENTED", "0")
    monkeypatch.setattr(grower, "range_histogram", no_segments)
    fresh_trace()
    b_sli = train_booster(X, y, BoosterConfig(**cfg))
    fresh_trace()
    for ts, tl in zip(b_seg.trees, b_sli.trees):
        np.testing.assert_array_equal(np.asarray(ts.split_feature),
                                      np.asarray(tl.split_feature))
        np.testing.assert_allclose(np.asarray(ts.leaf_value),
                                   np.asarray(tl.leaf_value), rtol=1e-5)


def test_kernel_checks_pass_on_chip(tpu):
    """Every trace-time kernel check passes on THIS chip: each compiles its
    kernel at the production chunk, runs it and compares with the XLA
    reference, raising KernelError otherwise (there is no fallback)."""
    from synapseml_tpu.ops.attention_kernel import (_check_flash_block_kernel,
                                                    _check_flash_kernel)
    from synapseml_tpu.ops.hist_kernel import (_check_hist_kernel,
                                               _check_level_kernel,
                                               _check_range_kernel, pad_bins)

    b = pad_bins(255)
    _check_hist_kernel(b)
    _check_range_kernel(b)
    _check_level_kernel(b, 8)
    _check_flash_kernel()
    _check_flash_block_kernel()


@pytest.mark.parametrize("window,first_row,start,length", [
    (4096, 2048 * 700, 2048 * 700 + 777, 1500),        # the smallest bucket
    (3_500_032, 0, 1234, 2_200_000)])                  # the whole table
def test_partition_kernel_on_chip(tpu, window, first_row, start, length):
    """The compiled stable-partition kernel at the benchmark cell's shapes
    (32 padded features, 3,500,032 rows) against argsort + five gathers,
    bit for bit; its trace-time check passes or raises KernelError."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from synapseml_tpu.ops.partition_kernel import (
        _partition_check_inputs, partition_kernel_available,
        partition_window, partition_window_xla)

    assert partition_kernel_available(256, 32) is True
    args = _partition_check_inputs(4, 256, 3_500_032, 32, first_row, window,
                                   start, length)
    got = jax.jit(lambda *a: partition_window(*a, 256, 2048))(*args)
    want = jax.jit(partition_window_xla)(*args)
    for a, b in zip(got, want):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(
            np.asarray(lax.bitcast_convert_type(a, jnp.int32)),
            np.asarray(lax.bitcast_convert_type(b, jnp.int32)))


@pytest.mark.parametrize("bins,fp", [(1024, 136), (256, 1024),
                                     (1024, 2048)])
def test_partition_kernel_check_passes_on_wide_tables(tpu, bins, fp):
    """The gate's self-check runs at the table's own width: a short last
    feature block, and 1,024 and 2,048 features (8 and 16 blocks), one and
    two byte planes a bin. Bit for bit, or KernelError."""
    from synapseml_tpu.ops.partition_kernel import partition_kernel_available

    assert partition_kernel_available(bins, fp) is True


def test_fit_counts_kernel_splits_on_chip(tpu):
    """On the chip every leaf-wise split goes through the partition kernel,
    and the fit's record says so."""
    from synapseml_tpu.core.logging import InstrumentationMeasures
    from synapseml_tpu.gbdt import BoosterConfig, train_booster

    rng = np.random.default_rng(2)
    X = rng.normal(size=(20000, 12)).astype(np.float32)
    y = (X[:, 0] * X[:, 1] > 0).astype(np.float32)
    m = InstrumentationMeasures()
    bst = train_booster(X, y, BoosterConfig(objective="binary",
                                            num_iterations=3),
                        measures=m)
    counted = {k: v for k, v in m.report().items()
               if k.startswith("count:splitsPartition")}
    assert counted == {"count:splitsPartitionKernel":
                       sum(int(t.num_splits) for t in bst.trees)}


def test_level_kernel_on_chip(tpu):
    """The multi-leaf level kernel (depthwise / streamed growth) vs the
    slot-keyed scatter, 136 features wide, uneven slots with padded tails."""
    from synapseml_tpu.ops import hist_kernel as hk

    C, B = 2048, 256
    caps = [3, 1, 2, 1, 1, 4, 2, 2]
    bT, g, h, m, starts, slot_row = hk._level_check_inputs(7, B, caps, C,
                                                           fp=136)
    got = np.asarray(hk._hist_pallas_level(bT, g, h, m, starts, B, len(caps),
                                           chunk=C))
    want = np.asarray(hk._hist_level_xla(bT, g, h, m, slot_row, B,
                                         len(caps)))
    np.testing.assert_allclose(got, want, rtol=1e-3, atol=1e-3)


def test_depthwise_growth_on_chip(tpu, monkeypatch):
    """Depthwise growth through the level kernel trains a usable model and
    agrees with the same policy on the scatter path (SYNAPSEML_TPU_LEVEL=0)."""
    from synapseml_tpu.gbdt import BoosterConfig, train_booster
    from synapseml_tpu.gbdt import boosting

    rng = np.random.default_rng(8)
    X = rng.normal(size=(30_000, 12)).astype(np.float32)
    y = (X[:, 0] * X[:, 1] + 0.5 * X[:, 2] > 0).astype(np.float32)
    cfg = dict(objective="binary", num_iterations=5,
               growth_policy="depthwise")
    b_k = train_booster(X, y, BoosterConfig(**cfg))
    monkeypatch.setenv("SYNAPSEML_TPU_LEVEL", "0")
    boosting._FUSED_RUNNERS.clear()      # the env is read at trace time
    b_s = train_booster(X, y, BoosterConfig(**cfg))
    boosting._FUSED_RUNNERS.clear()
    p_k, p_s = b_k.predict(X[:2000]), b_s.predict(X[:2000])
    assert ((p_k > 0.5) == (y[:2000] > 0.5)).mean() > 0.8
    np.testing.assert_allclose(p_k, p_s, atol=5e-3)


def _highest(fn, *args, **kw):
    """Reference at full f32 matmul precision (the TPU default is a bf16
    pass, which is also what the kernels' own f32 matmuls use — hence the
    1e-2 tolerances below; measured 2.6e-3 on a v5e)."""
    import jax

    with jax.default_matmul_precision("highest"):
        return np.asarray(fn(*args, **kw))


def test_flash_attention_on_chip(tpu):
    """The Pallas flash-attention kernel agrees with the XLA reference on
    REAL hardware (CI only checks the interpreter), causal and full, incl.
    non-divisible lengths."""
    from synapseml_tpu.ops.attention_kernel import flash_attention
    from synapseml_tpu.parallel.ring_attention import attention_reference

    rng = np.random.default_rng(0)
    q, k, v = (rng.normal(size=(2, 300, 4, 64)).astype(np.float32)
               for _ in range(3))
    for causal in (False, True):
        got = np.asarray(flash_attention(q, k, v, causal=causal))
        want = _highest(attention_reference, q, k, v, causal=causal)
        np.testing.assert_allclose(got, want, rtol=1e-2, atol=1e-2)


def test_flash_attention_block_on_chip(tpu):
    """The ring's state-carrying kernel vs ring_attention._block_attention,
    folding a second K/V block into carried state, compared after
    normalization."""
    import jax
    import jax.numpy as jnp

    from synapseml_tpu.ops.attention_kernel import (comparable_state,
                                                    flash_attention_block)
    from synapseml_tpu.parallel.ring_attention import _block_attention

    rng = np.random.default_rng(1)
    sq = sk = 1024
    q = jnp.asarray(rng.normal(size=(1, sq, 12, 64)), jnp.float32)
    k1, v1, k2, v2 = (jnp.asarray(rng.normal(size=(1, sk, 12, 64)),
                                  jnp.float32) for _ in range(4))
    m0 = jnp.full((1, 12, sq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((1, 12, sq), jnp.float32)
    o0 = jnp.zeros((1, sq, 12, 64), jnp.float32)
    for causal in (False, True):
        with jax.default_matmul_precision("highest"):
            st = _block_attention(q, k1, v1, m0, l0, o0, sk, 0, causal, 0.125)
            mw, lw, ow = _block_attention(q, k2, v2, *st, sk, sk, causal,
                                          0.125)
        mg, lg, og = flash_attention_block(q, k2, v2, *st, q_offset=sk,
                                           k_offset=sk, causal=causal,
                                           scale=0.125)
        for got, want in zip(comparable_state(mg, lg, og),
                             comparable_state(mw, lw, ow)):
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=1e-2, atol=1e-2)


def test_ring_attention_with_kernel_on_chip(tpu):
    """ring_self_attention over every visible chip picks the fused block
    kernel by backend and equals attention_reference."""
    import jax
    import jax.numpy as jnp

    from synapseml_tpu.parallel import make_mesh
    from synapseml_tpu.parallel.ring_attention import (attention_reference,
                                                       ring_self_attention)

    mesh = make_mesh({"data": 1, "seq": len(jax.devices())})
    rng = np.random.default_rng(2)
    q, k, v = (jnp.asarray(rng.normal(size=(1, 2048, 4, 64)), jnp.float32)
               for _ in range(3))
    for causal in (False, True):
        ring = jax.jit(lambda a, b, c: ring_self_attention(
            a, b, c, mesh, causal=causal))
        assert "tpu_custom_call" in ring.lower(q, k, v).compile().as_text()
        want = _highest(attention_reference, q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(ring(q, k, v)), want,
                                   rtol=1e-2, atol=1e-2)
