"""Distributed-path tests on the virtual 8-device CPU mesh.

The reference exercises its whole distributed stack in-process via Spark
`local[*]` (SURVEY §4.1); these tests do the same with 8 XLA host devices:
sharded histograms must equal single-device histograms (the psum the compiler
inserts replaces LightGBM's ring allreduce), and mesh helpers must compose."""

import jax.numpy as jnp
import numpy as np

from synapseml_tpu.parallel import (DATA_AXIS, allreduce_mean, allreduce_sum,
                                    allreduce_sum_quantized, make_mesh,
                                    reduce_scatter_sum_quantized, shard_apply,
                                    shard_rows)
from synapseml_tpu.ops.histogram import leaf_histograms, sharded_histogram_fn


def test_make_mesh_axes(eight_devices):
    mesh = make_mesh({"data": 4, "model": 2}, devices=eight_devices)
    assert mesh.shape == {"data": 4, "model": 2}
    mesh2 = make_mesh({"data": -1}, devices=eight_devices)
    assert mesh2.shape["data"] == 8


def test_sharded_histogram_equals_local(eight_devices):
    rng = np.random.default_rng(0)
    n, f, b, leaves = 1024, 6, 32, 4
    binned = rng.integers(0, b, size=(n, f)).astype(np.uint8)
    node = rng.integers(0, leaves, size=n).astype(np.int32)
    g = rng.normal(size=n).astype(np.float32)
    h = rng.uniform(0.1, 1, size=n).astype(np.float32)

    local = np.asarray(leaf_histograms(jnp.asarray(binned), jnp.asarray(node),
                                       jnp.asarray(g), jnp.asarray(h), leaves, b))

    mesh = make_mesh(devices=eight_devices)
    fn = sharded_histogram_fn(mesh, leaves, b)
    sb, sn, sg, sh = shard_rows(mesh, binned, node, g, h)
    dist = np.asarray(fn(sb, sn, sg, sh))
    np.testing.assert_allclose(dist, local, rtol=1e-5, atol=1e-4)


def test_collectives_inside_shard_map(eight_devices):
    mesh = make_mesh(devices=eight_devices)
    x = np.arange(8, dtype=np.float32)

    def body(xs):
        s = allreduce_sum(xs.sum())
        m = allreduce_mean(xs.sum())
        return jnp.stack([s, m])

    from jax.sharding import PartitionSpec as P

    out = shard_apply(mesh, body, in_specs=P(DATA_AXIS), out_specs=P(None))(x)
    assert float(out[0]) == 28.0
    assert float(out[1]) == 3.5


def test_allreduce_sum_quantized_matches_psum(eight_devices):
    """The int8 wire must reproduce an exact psum to per-block quantization
    tolerance, and every device must see bit-identical dequantized bytes."""
    mesh = make_mesh(devices=eight_devices)
    rng = np.random.default_rng(7)
    x = rng.normal(size=(8, 13, 37)).astype(np.float32) * 10.0

    from jax.sharding import PartitionSpec as P

    def body(xs):
        return allreduce_sum_quantized(xs[0], block=64), \
            allreduce_sum(xs[0])

    approx, exact = shard_apply(mesh, body, in_specs=P(DATA_AXIS),
                                out_specs=(P(None), P(None)))(x)
    approx, exact = np.asarray(approx), np.asarray(exact)
    # quantize-once wire: the integer psum is exact, so the only loss is
    # each device's one snap to the shared int8 grid (<= scale/2 =
    # maxabs/254 per device) -> total <= n * maxabs / 254
    tol = np.abs(x).max() * 8 / 254.0
    np.testing.assert_allclose(approx, exact, atol=tol)
    assert np.abs(approx - exact).max() > 0          # it really quantized
    xi = rng.integers(-50, 50, size=(8, 16, 16)).astype(np.float32)
    approx, exact = shard_apply(mesh, body, in_specs=P(DATA_AXIS),
                                out_specs=(P(None), P(None)))(xi)
    tol = np.abs(xi).max() * 8 / 254.0
    np.testing.assert_allclose(np.asarray(approx), np.asarray(exact), atol=tol)


def test_reduce_scatter_sum_quantized_owns_chunks(eight_devices):
    mesh = make_mesh(devices=eight_devices)
    rng = np.random.default_rng(3)
    x = rng.normal(size=(8, 16, 64)).astype(np.float32)

    from jax.sharding import PartitionSpec as P

    def body(xs):
        return reduce_scatter_sum_quantized(xs[0], block=128)

    out = shard_apply(mesh, body, in_specs=P(DATA_AXIS),
                      out_specs=P(DATA_AXIS))(x)
    want = x.sum(axis=0)       # concatenated owned chunks == full sum
    tol = np.abs(x).max() * 8 / 254.0
    np.testing.assert_allclose(np.asarray(out), want, atol=tol)


def test_distributed_training_matches_single(binary_data, eight_devices):
    """Training with rows device-put onto an 8-device mesh must give the same
    model as single-device (same histograms → same splits)."""
    from synapseml_tpu.gbdt import BoosterConfig, train_booster

    Xtr, Xte, ytr, _ = binary_data
    n = (len(ytr) // 8) * 8      # even shards, no padding rows
    cfg = BoosterConfig(objective="binary", num_iterations=5)
    b1 = train_booster(Xtr[:n], ytr[:n], cfg)
    p1 = b1.predict(Xte)

    mesh = make_mesh(devices=eight_devices)
    b2 = train_booster(Xtr[:n], ytr[:n], cfg, mesh=mesh)
    p2 = b2.predict(Xte)
    # float32 histogram accumulation order differs across shards, so tied splits
    # may resolve differently — same tolerance philosophy as the reference's
    # benchmark CSVs (±0.1 AUC); here predictions must agree closely
    np.testing.assert_allclose(p1, p2, atol=5e-3)
