"""Benchmarks: the reference's headline workloads on one TPU chip.

Prints ONE JSON line for the primary metric (GBDT training throughput —
the driver contract: {"metric", "value", "unit", "vs_baseline"}), with the
other headline workloads (BASELINE.md: ResNet-50 fine-tune imgs/sec/chip,
ONNX ResNet-50 batch inference, serving latency) embedded under "extras" in
the same line. `python bench.py --all` (or BENCH_ALL=1) runs every workload;
the default runs GBDT plus whatever fits in a soft time budget.

One process per chip: the parent never imports jax. Every workload, the GBDT
primary included, runs in its own `python bench.py --only <name>` child, one
after another, and each result names the platform, device kind and device
count it ran on. A workload that needs the chip and finds no TPU exits
non-zero and prints no value; a workload that fails makes the run exit
non-zero.

Baselines (the reference publishes no absolute numbers — BASELINE.json
published: {}; these are documented estimates of the systems the reference
actually runs on):
  * GBDT: single-node multicore LightGBM C++ on HIGGS-shape data
    (~4e6 row-iterations/s on a modern 16-core host; LightGBM's own
    parallel-learning experiments' accounting).
  * ResNet-50 fine-tune: ~400 imgs/sec — published V100-class single-GPU
    mixed-precision training throughput (the reference's DeepVisionClassifier
    runs Horovod on such GPUs).
  * ONNX ResNet-50 batch inference: ~1000 imgs/sec — V100-class
    onnxruntime-gpu throughput (ONNXModel.scala's backend).
  * Serving: the reference claims "sub-millisecond" (README.md) — baseline
    p50 = 1 ms.
"""

from __future__ import annotations

import functools
import json
import math
import os
import sys
import time

import numpy as np



BASELINE_GBDT_ROW_ITERS = 4.0e6
BASELINE_RESNET_IMGS_SEC = 400.0
BASELINE_ONNX_IMGS_SEC = 1000.0
BASELINE_SERVING_P50_MS = 1.0
# served ResNet-50 p50: ~1 ms compute at the 1000 imgs/s onnxruntime-gpu
# anchor (BASELINE_ONNX_IMGS_SEC) plus ~4 ms HTTP + JSON image-payload
# overhead at the reference's serving layer — the comparable end-to-end
# request latency, not the bare model step
BASELINE_RESNET_SERVING_P50_MS = 5.0
# measured pre-bucketing serving throughput at 16 concurrent keep-alive
# clients (per-observed-shape recompiles + polling serve loop); the serving
# perf guard (ci.sh) checks the BucketedRunner pipeline clears 2x this
BASELINE_SERVING_REQS_PER_SEC = 98.0
# BERT-base seq-128 fine-tune: ~100 ex/s is V100-class mixed-precision
# training throughput (the reference's DeepTextClassifier hardware);
# onnxruntime-gpu BERT-base batch inference on the same class: ~400 seq/s
BASELINE_BERT_TRAIN_EX_SEC = 100.0
BASELINE_ONNX_BERT_SEQ_SEC = 400.0

N_ROWS = 500_000
N_FEATURES = 28
TIMED_ITERS = 25


def bench_gbdt():
    """Training row-iterations/sec = rows x boosting iterations / wall time
    (steady-state loop, binning + compile excluded) — the same accounting
    LightGBM uses for its parallel experiments. HIGGS-style config: dense
    floats, binary objective, 31 leaves, 255 bins."""
    import jax

    from synapseml_tpu.gbdt import BoosterConfig, Dataset, train_booster

    rng = np.random.default_rng(0)
    X = rng.normal(size=(N_ROWS, N_FEATURES)).astype(np.float32)
    margin = X[:, 0] * X[:, 1] + 0.5 * X[:, 2] + 0.2 * rng.normal(size=N_ROWS)
    y = (margin > 0).astype(np.float32)

    # Stage once: Dataset bins on device and keeps the quantized matrix
    # HBM-resident — LightGBM's own Dataset-vs-train split, and the same
    # accounting its parallel-learning experiments use (dataset construction
    # excluded from the timed iteration loop).
    ds = Dataset(X, y).block_until_ready()

    train_booster(ds, None, BoosterConfig(
        objective="binary", num_iterations=TIMED_ITERS))  # compile + cache
    cfg = BoosterConfig(objective="binary", num_iterations=TIMED_ITERS,
                        seed=1)
    t0 = time.perf_counter()
    booster = train_booster(ds, None, cfg)
    jax.block_until_ready(booster.trees[-1].leaf_value)
    v = N_ROWS * TIMED_ITERS / (time.perf_counter() - t0)
    return {"metric": "gbdt_train_row_iters_per_sec_per_chip",
            "value": round(v, 1), "unit": "row-iterations/sec/chip",
            "vs_baseline": round(v / BASELINE_GBDT_ROW_ITERS, 3)}


def bench_resnet50_train(batch=32, image=224, warmup=2, steps=8):
    """ResNet-50 fine-tune imgs/sec/chip (DeepVisionClassifier.py:31-268
    parity workload: CIFAR-class labels, 224x224 inputs, bf16 compute)."""
    import jax
    import jax.numpy as jnp
    import optax

    from synapseml_tpu.dl.backbones import make_backbone

    model = make_backbone("resnet50", 10, dtype=jnp.bfloat16)
    rng = np.random.default_rng(0)
    imgs = jnp.asarray(rng.uniform(size=(batch, image, image, 3)),
                       jnp.float32)
    labels = jnp.asarray(rng.integers(0, 10, size=batch))
    variables = model.init(jax.random.PRNGKey(0), imgs[:1], train=False)
    params = variables["params"]
    batch_stats = variables.get("batch_stats", {})
    tx = optax.sgd(1e-2, momentum=0.9)
    opt_state = tx.init(params)

    @jax.jit
    def step(params, batch_stats, opt_state, x, y):
        def loss_fn(p, bs):
            logits, mutated = model.apply(
                {"params": p, "batch_stats": bs}, x, train=True,
                mutable=["batch_stats"])
            oh = jax.nn.one_hot(y, 10)
            loss = -jnp.mean(jnp.sum(jax.nn.log_softmax(
                logits.astype(jnp.float32)) * oh, -1))
            return loss, mutated["batch_stats"]
        (loss, new_bs), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, batch_stats)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), new_bs, opt_state, loss

    for _ in range(warmup):
        params, batch_stats, opt_state, loss = step(params, batch_stats,
                                                    opt_state, imgs, labels)
    jax.block_until_ready(loss)
    t0 = time.perf_counter()
    for _ in range(steps):
        params, batch_stats, opt_state, loss = step(params, batch_stats,
                                                    opt_state, imgs, labels)
    jax.block_until_ready(loss)
    dt = time.perf_counter() - t0
    v = batch * steps / dt
    return {"metric": "resnet50_finetune_imgs_per_sec_per_chip",
            "value": round(v, 1), "unit": "imgs/sec/chip",
            "vs_baseline": round(v / BASELINE_RESNET_IMGS_SEC, 3)}


def bench_bert_finetune(batch=32, seq=128, warmup=2, steps=8):
    """BERT-base SST-2-shape fine-tune examples/sec/chip (DeepTextClassifier
    parity workload — BASELINE.md: BERT-base on SST-2). Random-init weights
    from config (zero-egress environment); identical compute to a checkpoint
    fine-tune step: full forward/backward + adamw update in bf16."""
    import jax
    import jax.numpy as jnp
    import optax

    from transformers import BertConfig, FlaxBertForSequenceClassification

    model = FlaxBertForSequenceClassification(
        BertConfig(num_labels=2), seed=0, dtype=jnp.bfloat16)
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(100, 30000, size=(batch, seq)), jnp.int32)
    attn = jnp.ones((batch, seq), jnp.int32)
    labels = jnp.asarray(rng.integers(0, 2, size=batch), jnp.int32)
    tx = optax.adamw(2e-5)
    params = model.params
    opt_state = tx.init(params)
    dropout_rng = jax.random.PRNGKey(0)

    @jax.jit
    def step(params, opt_state, key):
        def loss_fn(p):
            logits = model(input_ids=ids, attention_mask=attn, params=p,
                           dropout_rng=key, train=True).logits
            oh = jax.nn.one_hot(labels, 2)
            return -jnp.mean(jnp.sum(
                jax.nn.log_softmax(logits.astype(jnp.float32)) * oh, -1))
        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    for _ in range(warmup):
        key, dropout_rng = jax.random.split(dropout_rng)
        params, opt_state, loss = step(params, opt_state, key)
    jax.block_until_ready(loss)
    t0 = time.perf_counter()
    for _ in range(steps):
        key, dropout_rng = jax.random.split(dropout_rng)
        params, opt_state, loss = step(params, opt_state, key)
    jax.block_until_ready(loss)
    dt = time.perf_counter() - t0
    v = batch * steps / dt
    return {"metric": "bert_base_finetune_ex_per_sec_per_chip",
            "value": round(v, 1),
            # random-init is explicit in the record: identical COMPUTE to a
            # checkpoint fine-tune step, but not a converged-quality claim
            "unit": f"examples/sec/chip (seq={seq}; random-init weights, "
                    "full fwd/bwd + adamw bf16)",
            "vs_baseline": round(v / BASELINE_BERT_TRAIN_EX_SEC, 3)}


def bench_onnx_bert(batch=32, seq=128, warmup=2, steps=8):
    """ONNX BERT-base-shape encoder batch inference seq/sec/chip through the
    importer (ONNXModel.scala:145-423 workload; BASELINE.md: ONNX BERT-base).
    Generated 12-layer/768-hidden/12-head encoder — the same op mix
    (MatMul/Transpose/Softmax/LayerNorm/Gelu) as an exported BERT-base."""
    import jax

    from synapseml_tpu.onnx.importer import OnnxFunction
    from synapseml_tpu.onnx.modelgen import make_transformer_encoder

    m = make_transformer_encoder(num_layers=12, d_model=768, num_heads=12,
                                 seq_len=seq, d_ff=3072, num_classes=2)
    fn = OnnxFunction(m)
    jfn = jax.jit(fn.as_jax(["embeddings"])[0])
    x = jax.device_put(np.random.default_rng(0).normal(
        size=(batch, seq, 768)).astype(np.float32))
    for _ in range(warmup):
        out = jfn(x)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(steps):
        out = jfn(x)
    jax.block_until_ready(out)
    dt = time.perf_counter() - t0
    v = batch * steps / dt
    return {"metric": "onnx_bert_base_inference_seq_per_sec_per_chip",
            "value": round(v, 1), "unit": f"sequences/sec/chip (seq={seq})",
            "vs_baseline": round(v / BASELINE_ONNX_BERT_SEQ_SEC, 3)}


def bench_onnx_inference(batch=64, image=224, warmup=2, steps=8,
                         precision="float32"):
    """ONNX ResNet-50 batch inference imgs/sec/chip through the importer
    (ONNXModel.scala:145-423 workload; model generated by onnx/modelgen —
    genuine ResNet-50 graph, 175 nodes). ``precision='bfloat16'`` runs the
    TPU mixed-precision path (floatPrecision param on ONNXModel)."""
    import jax

    from synapseml_tpu.onnx.importer import OnnxFunction
    from synapseml_tpu.onnx.modelgen import make_resnet

    m = make_resnet(50, num_classes=1000, image_size=image)
    fn = OnnxFunction(m, precision=precision)
    jfn = jax.jit(fn.as_jax(["data"])[0])
    # device-resident input: the metric is inference compute, not host->device
    # transfer (38 MB/step; same convention as bench_resnet50_train)
    x = jax.device_put(np.random.default_rng(0).normal(
        size=(batch, 3, image, image)).astype(np.float32))
    for _ in range(warmup):
        out = jfn(x)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(steps):
        out = jfn(x)
    jax.block_until_ready(out)
    dt = time.perf_counter() - t0
    v = batch * steps / dt
    tag = "_bf16" if precision == "bfloat16" else ""
    return {"metric": f"onnx_resnet50_inference{tag}_imgs_per_sec_per_chip",
            "value": round(v, 1), "unit": "imgs/sec/chip",
            "vs_baseline": round(v / BASELINE_ONNX_IMGS_SEC, 3)}


# one payload shape for the forest serving bench — must match the fixture's
# 8 training features below
_SERVING_PAYLOAD = b'{"x": [0.1, 0.1, 0.1, 0.1, 0.1, 0.1, 0.1, 0.1]}'


def _gbdt_serving_handler():
    """Serving-bench fixture: a REAL trained GBDT forest (50 trees x 31
    leaves on 8 features) behind the micro-batcher — the reference's
    served-model story (README Spark Serving cell serves fitted models;
    VERDICT r4 #3: a sub-ms claim must hold for a model, not a toy). The
    forest predicts through the jitted binned traversal."""
    from synapseml_tpu.core.table import Table
    from synapseml_tpu.gbdt import BoosterConfig, Dataset, train_booster

    rng = np.random.default_rng(0)
    Xtr = rng.normal(size=(4000, 8)).astype(np.float32)
    ytr = (Xtr[:, 0] * Xtr[:, 1] + 0.5 * Xtr[:, 2] > 0).astype(np.float32)
    booster = train_booster(
        Dataset(Xtr, ytr), None,
        BoosterConfig(objective="binary", num_iterations=50, num_leaves=31))
    # bucketed serving path (core/inference.py): one fused dispatch per
    # batch, one AOT-compiled executable per bucket — zero steady-state
    # recompiles regardless of the observed micro-batch sizes
    predict = booster.serving_fn(max_batch_size=32)

    def handler(df: Table) -> Table:
        x = np.asarray([v["x"] for v in df["value"]], np.float32)
        out = np.asarray(predict(x))
        return Table({"id": df["id"], "reply": out.astype(np.float64)})

    # ServingServer.start() warms the whole bucket ladder through this hook
    # before the listener opens; the metrics GET surfaces runner.stats()
    handler.warmup = predict.warmup
    handler.runner = predict.runner
    return handler


def _resnet_serving_handler():
    """Serving-bench fixture: the torch-exported ResNet-50 topology (slim
    width, 53 convs) imported through OnnxFunction and served per-image —
    the ONNX-model-behind-HTTP story (ONNXModel + Spark Serving in the
    reference). Payload carries the full image as JSON, so the number is an
    honest end-to-end cost including wire serialization."""
    import os as _os

    from synapseml_tpu.core.table import Table
    from synapseml_tpu.onnx.importer import OnnxFunction
    from synapseml_tpu.onnx.protoio import Model

    path = _os.path.join(_os.path.dirname(_os.path.abspath(__file__)),
                         "tests", "resources", "onnx", "torch_resnet50.onnx")
    with open(path, "rb") as f:
        fn = OnnxFunction(Model.parse(f.read()))
    jf, names = fn.as_jax()
    # coarse 2-rung ladder (1, 8): the latency probe serves single images,
    # so warmup compiles the 53-conv net twice, not once per power of two
    from synapseml_tpu.core.inference import BucketedRunner

    runner = BucketedRunner(jf, max_batch_size=8, growth=8.0,
                            name="bench.resnet_serving")

    def handler(df: Table) -> Table:
        x = np.asarray([v["x"] for v in df["value"]], np.float32)
        out = np.asarray(runner(x)[0])
        return Table({"id": df["id"],
                      "reply": [r.tolist() for r in out]})

    def _warm():
        return runner.warmup(np.zeros((1, 3, 64, 64), np.float32))

    handler.warmup = _warm
    handler.runner = runner
    return handler


def _resnet_payload() -> bytes:
    import json as _json

    img = np.round(np.random.default_rng(1).uniform(
        -1, 1, size=(3, 64, 64)), 3)
    return _json.dumps({"x": img.tolist()}).encode()


def _measure_latency(port: int, path: str, n_requests: int,
                     warmup: int = 20, payload: bytes = None):
    """Keep-alive client latency probe → (p50_ms, p99_ms)."""
    import http.client

    payload = payload or _SERVING_PAYLOAD
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=5)

    def one():
        conn.request("POST", path, body=payload,
                     headers={"Content-Type": "application/json"})
        r = conn.getresponse()
        body = r.read()
        if r.status != 200:   # http.client does not raise on 5xx
            raise RuntimeError(f"serving error {r.status}: {body[:120]!r}")

    for _ in range(warmup):
        one()
    lat = []
    for _ in range(n_requests):
        t0 = time.perf_counter()
        one()
        lat.append((time.perf_counter() - t0) * 1e3)
    conn.close()
    lat = np.sort(np.asarray(lat))
    return float(lat[len(lat) // 2]), float(lat[int(len(lat) * 0.99)])


def bench_serving(n_requests=200):
    """End-to-end serving latency for a REAL served model — a trained
    50-tree GBDT forest (accept → queue → jitted forest predict → reply;
    io/serving.py) vs the reference's "sub-millisecond" Spark Serving claim
    for served fitted models."""
    import json as _json

    from synapseml_tpu.io.serving import ServingServer

    # latency-optimized serving config: no artificial batch-formation wait
    # (batches still form under concurrent backlog); keep-alive client
    # connection as any production caller would hold
    handler = _gbdt_serving_handler()
    server = ServingServer(handler, host="127.0.0.1",
                           port=0, max_batch_size=32, max_batch_latency=0.0)
    server.start()     # AOT-warms the bucket ladder before the listener opens
    try:
        p50, p99 = _measure_latency(server.port, server.api_path, n_requests)
        payload = _SERVING_PAYLOAD

        # throughput under concurrent load: the micro-batcher should coalesce
        # backlogged requests into one pipeline call per drain
        import threading

        n_threads, per = 16, 50
        ok_counts = [0] * n_threads

        def worker(slot):
            import http.client as hc
            c = hc.HTTPConnection("127.0.0.1", server.port, timeout=10)
            try:
                for _ in range(per):
                    c.request("POST", server.api_path, body=payload,
                              headers={"Content-Type": "application/json"})
                    r = c.getresponse()
                    r.read()
                    if r.status == 200:
                        ok_counts[slot] += 1
            except Exception:
                pass          # count only completed requests below
            finally:
                c.close()

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(n_threads)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        done = sum(ok_counts)
        if done < n_threads * per * 0.95:
            raise RuntimeError(f"serving concurrency: only {done}/"
                               f"{n_threads * per} requests succeeded")
        rps = done / (time.perf_counter() - t0)
        stats = handler.runner.stats()
        steady_compiles = stats["total_compiles"] - stats["warmup_compiles"]
        if steady_compiles:
            raise RuntimeError(
                "serving perf contract broken: %d post-warmup XLA compiles "
                "(per-bucket counts: %s)" % (steady_compiles,
                                             stats["compiles"]))
        # throughput is its own recorded artifact (the CI serving perf guard
        # and the 2x acceptance floor read this metric, not the unit string)
        record_measurement({
            "metric": "serving_requests_per_sec", "value": round(rps, 1),
            "unit": "req/s (@%d concurrent keep-alive clients; per-bucket "
                    "compiles %s; %d warmup / 0 steady-state)" % (
                        n_threads, stats["compiles"],
                        stats["warmup_compiles"]),
            "vs_baseline": round(rps / BASELINE_SERVING_REQS_PER_SEC, 3)})
        return {"metric": "serving_latency_p50_ms", "value": round(p50, 3),
                "unit": "ms (gbdt forest 50x31; p99=%.3f; %.0f req/s @%d "
                        "concurrent; buckets %s all pre-compiled)" % (
                            p99, rps, n_threads, stats["buckets"]),
                "vs_baseline": round(BASELINE_SERVING_P50_MS / max(p50, 1e-9), 3)}
    finally:
        server.stop()


def bench_serving_resnet(n_requests=60):
    """Latency for a served ONNX vision model: the torch-exported ResNet-50
    topology behind the same HTTP batcher, full image payload on the wire —
    the honest (non-sub-ms) companion number to the forest headline."""
    from synapseml_tpu.io.serving import ServingServer

    server = ServingServer(_resnet_serving_handler(), host="127.0.0.1",
                           port=0, max_batch_size=8, max_batch_latency=0.0)
    server.start()
    try:
        p50, p99 = _measure_latency(server.port, server.api_path,
                                    n_requests, warmup=5,
                                    payload=_resnet_payload())
        return {"metric": "serving_resnet50_latency_p50_ms",
                "value": round(p50, 3),
                "unit": "ms (p99=%.3f; 64x64 image JSON payload)" % p99,
                "vs_baseline": round(
                    BASELINE_RESNET_SERVING_P50_MS / max(p50, 1e-9), 3)}
    finally:
        server.stop()


MEASUREMENTS_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                 "docs", "measurements.json")

# metrics of the workloads main() runs on the virtual CPU mesh by construction
# (_CPU_WORKLOADS: same-platform ratios and host-side fabric rates). These
# record on any platform. Everything else is chip-fact-only — the committed
# artifacts hold on-chip numbers (round-3 policy, enforced in code instead
# of by manual cleanup).
_HOST_SIDE_METRICS = frozenset({"serving_fabric_reqs_per_sec",
                                "gbdt_voting_vs_data_parallel_speedup",
                                "gbdt_distributed_auto_vs_manual"})


def _device_stamp() -> dict:
    """Where this process ran, as jax reports it. Only workload children
    call this: the parent never imports jax."""
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "device_kind": devs[0].device_kind,
            "device_count": len(devs)}


def record_measurement(entry: dict, path: str = None):
    """Append a successful measurement to the committed on-chip measurement
    log (docs/measurements.json) with a capture timestamp and the device it
    ran on — so chip numbers survive as artifacts instead of living only in
    markdown (VERDICT r2 'what's missing' #4). Called in the workload's own
    process, after it has run."""
    import datetime

    path = path or MEASUREMENTS_PATH
    rec = dict(entry)
    rec["captured_at"] = datetime.datetime.now(
        datetime.timezone.utc).isoformat(timespec="milliseconds")
    for k, v in _device_stamp().items():
        rec.setdefault(k, v)
    if (rec["platform"] != "tpu"
            and rec.get("metric") not in _HOST_SIDE_METRICS
            and os.environ.get("SYNAPSEML_TPU_RECORD_ALL") != "1"):
        return   # off-chip numbers must not pollute the committed artifacts
    try:
        # several recorders can interleave (workload children, scale proof,
        # manual runs). Neither flock nor a lockfile protocol is dependable
        # in this container (flock verifiably does NOT exclude across
        # processes here), so the primitive is a single O_APPEND write() per
        # record — atomic line appends to a JSONL journal, no
        # read-modify-write at all. The pretty array (docs/measurements.json)
        # is DERIVED from journal + legacy entries; regenerating it races
        # harmlessly.
        line = json.dumps(rec) + "\n"
        fd = os.open(path + "l", os.O_CREAT | os.O_WRONLY | os.O_APPEND,
                     0o644)
        try:
            os.write(fd, line.encode())
        finally:
            os.close(fd)
        log = _read_measurements(path)
        tmp = f"{path}.{os.getpid()}.tmp"
        with open(tmp, "w") as f:
            json.dump(log, f, indent=1)
        os.replace(tmp, path)
    except Exception as e:  # recording must never sink a measurement
        print(f"# measurement log write failed: {e}", file=sys.stderr)


def _perf_row(kind: str, arm: str, features: dict, observed_s: float,
              **extra):
    """Append one perfmodel training row (core/perfmodel journal). Every
    bench arm that prices an alternative labels it here, so the model's
    training set grows with every bench run. Best-effort: a row-write
    failure must never sink the measurement itself."""
    try:
        from synapseml_tpu.core import perfmodel

        perfmodel.append_training_row(kind, arm, features, observed_s,
                                      **extra)
    except Exception as e:
        print(f"# perf row write failed ({kind}/{arm}): {e}",
              file=sys.stderr)


def _read_measurements(path: str = None):
    """All recorded entries in capture order: the legacy/derived array
    (docs/measurements.json) merged with the append-only JSONL journal
    (docs/measurements.jsonl), deduplicated by (metric, captured_at)."""
    path = path or MEASUREMENTS_PATH
    entries = []
    try:
        with open(path) as f:
            entries.extend(e for e in json.load(f) if isinstance(e, dict))
    except Exception:
        pass
    try:
        with open(path + "l") as f:
            for ln in f:
                ln = ln.strip()
                if ln:
                    try:
                        e = json.loads(ln)
                    except json.JSONDecodeError:
                        continue        # torn line from a dying process
                    if isinstance(e, dict):
                        entries.append(e)
    except OSError:
        pass
    seen, out = set(), []
    for e in entries:
        key = (e.get("metric"), e.get("captured_at"), str(e.get("value")))
        if key in seen:
            continue
        seen.add(key)
        out.append(e)
    out.sort(key=lambda e: e.get("captured_at", ""))
    return out


def bench_sparse_ingest(rows=1_000_000, cols=200, density=0.01):
    """Sparse CSR → device-resident binned Dataset ingest throughput
    (VERDICT r2 #7: the dense-detour path wiped out CSR's memory advantage;
    the device scatter path ships O(nnz) bytes). Baseline: LightGBM's own
    CSR dataset construction is IO-bound on the same accounting — report
    rows/s with the dense-equivalent rows/s alongside."""
    import jax
    import scipy.sparse as sp

    from synapseml_tpu.gbdt import Dataset

    rng = np.random.default_rng(0)
    nnz = int(rows * cols * density)
    r = rng.integers(0, rows, size=nnz)
    c = rng.integers(0, cols, size=nnz)
    v = rng.normal(size=nnz).astype(np.float32)
    X = sp.csr_matrix((v, (r, c)), shape=(rows, cols))
    y = rng.integers(0, 2, size=rows).astype(np.float32)
    t0 = time.perf_counter()
    ds = Dataset(X, y, keep_raw=False).block_until_ready()
    dt = time.perf_counter() - t0
    del ds
    rps = rows / dt
    return {"metric": "sparse_ingest_rows_per_sec",
            "value": round(rps, 1),
            "unit": f"rows/sec ({cols} cols, {density:.0%} density, "
                    f"nnz={X.nnz})",
            # vs the 4e6-row-iters GBDT accounting this is a staging metric;
            # report the ratio to a 1M-rows/s dense-staging reference
            "vs_baseline": round(rps / 1.0e6, 3)}


def bench_serving_distributed(n_requests=200):
    """Multi-worker serving path: 2 per-process-style workers + gateway
    (io/distributed_serving.py; DistributedHTTPSource.scala:203-312 analog).
    Measures the end-to-end client → gateway → worker → reply latency — the
    forwarding hop the reference stubs (InternalHandler NotImplementedError)
    priced against the head-node number from bench_serving."""
    from synapseml_tpu.io import ServingGateway, ServingServer

    handler = _gbdt_serving_handler()     # same served model as bench_serving
    workers = [ServingServer(handler, host="127.0.0.1", port=0,
                             max_batch_size=32,
                             max_batch_latency=0.0).start()
               for _ in range(2)]
    # worker 0 is co-located with the gateway, as in the real deployment
    # (process 0 runs both): it rides the direct-queue fast path
    gw = ServingGateway([s.url for s in workers], port=0,
                        mode="least_loaded", local_worker=workers[0],
                        local_index=0).start()
    try:
        p50, p99 = _measure_latency(gw.port, gw.api_path, n_requests)
        forwarded = gw.stats["forwarded"]
        return {"metric": "serving_distributed_latency_p50_ms",
                "value": round(p50, 3),
                "unit": "ms (p99=%.3f; 2 workers; %d forwards)" % (
                    p99, forwarded),
                "vs_baseline": round(BASELINE_SERVING_P50_MS / max(p50, 1e-9),
                                     3)}
    finally:
        gw.stop()
        for s in workers:
            s.stop()


def bench_fabric_scaling(n_threads=8, per_thread=40):
    """Aggregate fabric throughput vs worker count (1/2/4): the same served
    GBDT forest replicated behind the gateway, concurrent keep-alive
    clients, aggregate req/s per replica count — the number the membership
    layer's autoscaling hook trades on (ISSUE: fabric tentpole). One
    process, so the curve prices gateway routing overhead honestly rather
    than claiming linear multi-host speedup."""
    import http.client as hc
    import threading

    from synapseml_tpu.io import ServingGateway, ServingServer

    handler = _gbdt_serving_handler()     # trained once, replicated
    payload = _SERVING_PAYLOAD
    rates = {}
    for n_workers in (1, 2, 4):
        workers = [ServingServer(handler, host="127.0.0.1", port=0,
                                 max_batch_size=32,
                                 max_batch_latency=0.0).start()
                   for _ in range(n_workers)]
        gw = ServingGateway([s.url for s in workers], port=0,
                            mode="least_loaded", local_worker=workers[0],
                            local_index=0).start()
        try:
            _measure_latency(gw.port, gw.api_path, 5, warmup=15)  # warm conns
            ok_counts = [0] * n_threads

            def client(slot):
                c = hc.HTTPConnection("127.0.0.1", gw.port, timeout=10)
                try:
                    for _ in range(per_thread):
                        c.request("POST", gw.api_path, body=payload,
                                  headers={"Content-Type":
                                           "application/json"})
                        r = c.getresponse()
                        r.read()
                        if r.status == 200:
                            ok_counts[slot] += 1
                except Exception:
                    pass      # count only completed requests below
                finally:
                    c.close()

            threads = [threading.Thread(target=client, args=(i,))
                       for i in range(n_threads)]
            t0 = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            done = sum(ok_counts)
            if done < n_threads * per_thread * 0.95:
                raise RuntimeError(
                    f"fabric scaling @{n_workers}w: only {done}/"
                    f"{n_threads * per_thread} requests succeeded")
            rates[n_workers] = done / (time.perf_counter() - t0)
        finally:
            gw.stop()
            for s in workers:
                s.stop()
    return {"metric": "serving_fabric_reqs_per_sec",
            "value": round(rates[4], 1),
            "unit": "req/s aggregate (1w=%.0f 2w=%.0f 4w=%.0f; %d clients)"
                    % (rates[1], rates[2], rates[4], n_threads),
            "vs_baseline": round(rates[4] / max(rates[1], 1e-9), 3)}


def bench_fabric_federation(n_threads=8, per_thread=100, trials=3):
    """Federation arms of the fabric-scaling curve (ISSUE: federated
    gateway tier): K peer gateways (K in 1/2/4/8) fronting one FIXED fleet
    of 32 echo workers, all in one process on CPU. The fleet is fixed so a
    doubling varies ONLY the gateway count — worker-scan cost per request
    is identical across arms and the curve isolates the federation tax
    (gossip replicators, lease renewal, ring refresh) plus gateway routing.
    The handler is a no-op echo ON PURPOSE: no model compute in the loop.
    Two numbers per arm:

    * aggregate req/s with clients spread round-robin over every gateway —
      best over ``trials`` rounds, with the rounds INTERLEAVED across arms
      (every arm visits every time window, so one scheduler burst degrades
      one round of one arm, not an arm's whole measurement),
    * control-plane convergence time — ``federate()`` to every gateway
      seeing every peer alive with zero replication lag (entries_behind
      == 0), the health-endpoint number operators watch after a topology
      change.

    The guard is CORE-NORMALIZED: doubling gateways on an N-core host can
    add at most min(2K,N)/min(K,N) real parallelism, so the bar is
    rate(2K) >= 0.9 x that x rate(K) per doubling — on a 1-CPU box it
    degenerates to "the federation tax per doubling is <= 10%", which is
    exactly the claim a single-host CI can honestly test."""
    import http.client as hc
    import threading

    from synapseml_tpu.io import ServingGateway, ServingServer, federate

    def echo(df):
        return df.with_column("reply", df["value"])

    def one(c, path):
        c.request("POST", path, body=_SERVING_PAYLOAD,
                  headers={"Content-Type": "application/json"})
        r = c.getresponse()
        r.read()
        return r.status

    def run_arm(k, urls):
        """One full arm round: K federated gateways over the shared fleet;
        returns (req/s, control-plane convergence seconds)."""
        gws = [ServingGateway(urls, port=0, gossip_interval=0.2,
                              peer_timeout=1.0).start()
               for _ in range(k)]
        try:
            t0 = time.perf_counter()
            federate(gws)

            def _converged():
                for gw in gws:
                    peers = gw._peers_alive(gw._clock())
                    if len(peers) != k - 1 or not all(
                            p["alive"] for p in peers.values()):
                        return False
                    if gw.gossip.entries_behind() != 0:
                        return False
                return True

            deadline = time.time() + 30.0
            while not _converged():
                if time.time() > deadline:
                    raise RuntimeError(f"federation @{k}gw control "
                                       "plane never converged")
                time.sleep(0.01)
            dt_converge = time.perf_counter() - t0
            ok_counts = [0] * n_threads
            # every client warms each keep-alive gateway connection (and,
            # across clients, the gateways' pooled worker links) OFF the
            # clock — handshakes scale with K and would masquerade as
            # federation tax — then all release through a barrier together
            barrier = threading.Barrier(n_threads + 1, timeout=60)

            def client(slot):
                conns = [hc.HTTPConnection("127.0.0.1", gw.port,
                                           timeout=10) for gw in gws]
                path = gws[0].api_path
                try:
                    for c in conns:
                        for _ in range(4):
                            one(c, path)
                    barrier.wait()
                    for i in range(per_thread):
                        if one(conns[(slot + i) % k], path) == 200:
                            ok_counts[slot] += 1
                except Exception:
                    pass      # count only completed requests below
                finally:
                    for c in conns:
                        c.close()

            threads = [threading.Thread(target=client, args=(i,))
                       for i in range(n_threads)]
            for t in threads:
                t.start()
            barrier.wait()
            t1 = time.perf_counter()
            for t in threads:
                t.join()
            done = sum(ok_counts)
            if done < n_threads * per_thread * 0.95:
                raise RuntimeError(
                    f"federation @{k}gw: only {done}/"
                    f"{n_threads * per_thread} requests succeeded")
            return done / (time.perf_counter() - t1), dt_converge
        finally:
            for gw in gws:
                gw.stop()

    n_workers = 32      # fixed fleet: each doubling varies ONLY gateways
    workers = [ServingServer(echo, host="127.0.0.1", port=0,
                             max_batch_size=32,
                             max_batch_latency=0.0).start()
               for _ in range(n_workers)]
    urls = [s.url for s in workers]
    arms = (1, 2, 4, 8)
    rounds = []
    rates = {k: 0.0 for k in arms}
    converge = {}
    try:
        for _round in range(trials):
            this = {}
            for k in arms:
                rate, dt = run_arm(k, urls)
                this[k] = rate
                rates[k] = max(rates[k], rate)
                converge.setdefault(k, dt)
            rounds.append(this)
    finally:
        for s in workers:
            s.stop()
    cores = os.cpu_count() or 1
    # each doubling ratio is judged WITHIN a round (adjacent time windows
    # share scheduler weather; cross-round ratios compound two independent
    # noise draws) and the guard takes the best round per doubling — a
    # systematic >10% federation tax still fails every round
    doublings = {}
    guard_ok = True
    for k in arms[:-1]:
        expected = min(2 * k, cores) / min(k, cores)
        ratio = max(r[2 * k] / max(r[k], 1e-9) for r in rounds)
        doublings[f"{k}gw->{2 * k}gw"] = round(ratio, 3)
        guard_ok = guard_ok and ratio >= 0.9 * expected
    return {"metric": "federated_gateway_reqs_per_sec",
            "value": round(rates[8], 1),
            "unit": ("req/s aggregate @8gw (1gw=%.0f 2gw=%.0f 4gw=%.0f "
                     "8gw=%.0f; %d clients, %d cores, 32 workers)"
                     % (rates[1], rates[2], rates[4], rates[8],
                        n_threads, cores)),
            "vs_baseline": round(rates[8] / max(rates[1], 1e-9), 3),
            "gateway_reqs_per_s": {str(k): round(v, 1)
                                   for k, v in rates.items()},
            "convergence_time_s": {str(k): round(v, 3)
                                   for k, v in converge.items()},
            "scaling_per_doubling": doublings,
            "cores": cores,
            "guard": {"scaling_ge_0p9x_linear_core_normalized": guard_ok}}

def _vw_bench_handler():
    """Third tenant family for the multi-tenant bench: a frozen
    epsilon-greedy VW policy (the online-learning serving shape)."""
    from synapseml_tpu.online import GreedyPolicy, make_policy_handler
    from synapseml_tpu.vw.learner import (VWConfig, VWState,
                                          make_sparse_batch)

    cfg = VWConfig(num_bits=12, batch_size=8, learning_rate=0.5)

    def featurize(_v=None):
        return list(make_sparse_batch(
            [[a * 7 + 1, a * 7 + 2] for a in range(3)],
            [[1.0, 1.0]] * 3, pad_to=4))

    return make_policy_handler(
        GreedyPolicy(VWState.init(cfg.num_bits), cfg, epsilon=1.0,
                     seed=0, version="v0"), featurize)


def bench_multitenant(n_threads_per_tenant=2, per_thread=60, n_workers=2):
    """Fleet-consolidation price (ISSUE 12 acceptance): K=3 model families
    (gbdt forest, dl runner, vw policy) sharing ONE M-worker fleet + QoS
    layer, versus K dedicated single-model fleets on the SAME worker count
    serving the same per-tenant load (run one at a time — the time-sliced
    alternative consolidation replaces). Reported value is the shared/
    dedicated aggregate-req/s ratio; the acceptance bar is >= 0.8x, guarded
    in ci.sh. Per-tenant p99 from the shared run rides in the unit string —
    the per-tenant QoS bound the isolation tests assert qualitatively."""
    import http.client as hc
    import threading

    from synapseml_tpu.core.qos import QoSController
    from synapseml_tpu.io import ServingGateway, ServingServer

    handlers = {"gbdt": _gbdt_serving_handler(),
                "dl": _resnet_serving_handler(),
                "vw": _vw_bench_handler()}
    payloads = {"gbdt": _SERVING_PAYLOAD, "dl": _resnet_payload(),
                "vw": b'{"user": 7}'}

    def drive(gw_port, gw_path, tenants):
        """Concurrent keep-alive clients per tenant -> (elapsed_s, done,
        {tenant: p99_ms}). Raises if any request fails — a bench run must
        not silently price errors as throughput."""
        lat = {t: [] for t in tenants}
        errors = []
        lock = threading.Lock()

        def client(tenant):
            c = hc.HTTPConnection("127.0.0.1", gw_port, timeout=30)
            mine = []
            try:
                for _ in range(per_thread):
                    t0 = time.perf_counter()
                    c.request("POST", gw_path, body=payloads[tenant],
                              headers={"Content-Type": "application/json",
                                       "X-Tenant": tenant})
                    r = c.getresponse()
                    body = r.read()
                    if r.status != 200:
                        raise RuntimeError(
                            f"{tenant}: {r.status} {body[:80]!r}")
                    mine.append((time.perf_counter() - t0) * 1e3)
            except Exception as e:  # noqa: BLE001
                with lock:
                    errors.append(repr(e))
            finally:
                c.close()
            with lock:
                lat[tenant].extend(mine)

        threads = [threading.Thread(target=client, args=(t,))
                   for t in tenants for _ in range(n_threads_per_tenant)]
        t0 = time.perf_counter()
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        elapsed = time.perf_counter() - t0
        if errors:
            raise RuntimeError(f"multitenant bench errors: {errors[:3]}")
        done = sum(len(v) for v in lat.values())
        p99 = {t: float(np.sort(np.asarray(v))[int(len(v) * 0.99)])
               for t, v in lat.items()}
        return elapsed, done, p99

    def fleet(tenants):
        """M workers serving exactly ``tenants``, one gateway; returns the
        drive() tuple and tears everything down."""
        workers = []
        for _ in range(n_workers):
            w = ServingServer(None, host="127.0.0.1", port=0,
                              max_batch_size=32, max_batch_latency=0.0,
                              qos=QoSController())
            for t in tenants:
                w.add_tenant(t, handlers[t])
            workers.append(w.start())
        gw = ServingGateway([w.url for w in workers], port=0,
                            mode="least_loaded").start()
        try:
            return drive(gw.port, gw.api_path, tenants)
        finally:
            gw.stop()
            for w in workers:
                w.stop()

    # shared fleet: all K tenants concurrently on M workers
    sh_elapsed, sh_done, sh_p99 = fleet(tuple(handlers))
    shared_rate = sh_done / sh_elapsed
    # dedicated baseline: K single-model fleets, same worker count, same
    # per-tenant load, run sequentially (aggregate = total work / total time)
    ded_elapsed, ded_done = 0.0, 0
    for t in handlers:
        e, d, _ = fleet((t,))
        ded_elapsed += e
        ded_done += d
    dedicated_rate = ded_done / ded_elapsed
    ratio = shared_rate / max(dedicated_rate, 1e-9)
    return {"metric": "multitenant_shared_vs_dedicated_ratio",
            "value": round(ratio, 3),
            "unit": "x aggregate req/s (shared=%.0f dedicated=%.0f; "
                    "p99 ms gbdt=%.1f dl=%.1f vw=%.1f; %dw x %d tenants)"
                    % (shared_rate, dedicated_rate, sh_p99["gbdt"],
                       sh_p99["dl"], sh_p99["vw"], n_workers,
                       len(handlers)),
            "vs_baseline": round(ratio / 0.8, 3)}


def bench_flash_attention(batch=4, seq=4096, heads=8, dim=64, steps=10):
    """Fused Pallas flash attention vs the XLA blockwise path at long
    context (S=4096): tokens/sec plus the fused-kernel speedup. Chip-fact
    metric — the kernel targets the MXU/VMEM; the CPU interpreter would
    measure nothing real."""
    import jax
    import jax.numpy as jnp

    from synapseml_tpu.ops.attention_kernel import flash_attention
    from synapseml_tpu.parallel.ring_attention import blockwise_attention

    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(rng.normal(size=(batch, seq, heads, dim)),
                           jnp.bfloat16) for _ in range(3))

    def timed(fn):
        out = fn(q, k, v)                  # compile
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        for _ in range(steps):
            out = fn(q, k, v)
        jax.block_until_ready(out)
        return steps * batch * seq / (time.perf_counter() - t0)

    from synapseml_tpu.ops.attention_kernel import divisor_block

    bs = divisor_block(seq, 512) or seq    # largest workable block divisor
    flash = jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=True))
    block = jax.jit(lambda q, k, v: blockwise_attention(
        q, k, v, block_size=bs, causal=True))
    tok_flash = timed(flash)
    tok_block = timed(block)
    return {"metric": "flash_attention_tokens_per_sec_per_chip",
            "value": round(tok_flash, 1),
            "unit": "tokens/sec/chip (causal S=%d bf16; %.2fx vs XLA "
                    "blockwise %.0f t/s)" % (seq, tok_flash / tok_block,
                                             tok_block),
            "vs_baseline": round(tok_flash / max(tok_block, 1e-9), 3)}


def bench_gbdt_depthwise():
    """OPT-IN depthwise growth policy at the same HIGGS-shape config —
    reported as its own metric, NOT folded into the primary best-of
    (different growth order than LightGBM's leaf-wise; the record carries
    the AUC of both policies so quality parity is visible)."""
    import jax

    from synapseml_tpu.gbdt import BoosterConfig, Dataset, train_booster
    from synapseml_tpu.gbdt.objectives import auc as _auc

    rng = np.random.default_rng(0)
    X = rng.normal(size=(N_ROWS, N_FEATURES)).astype(np.float32)
    margin = X[:, 0] * X[:, 1] + 0.5 * X[:, 2] + 0.2 * rng.normal(size=N_ROWS)
    y = (margin > 0).astype(np.float32)
    ds = Dataset(X, y).block_until_ready()

    cfg = BoosterConfig(objective="binary", num_iterations=TIMED_ITERS,
                        seed=1, growth_policy="depthwise")
    train_booster(ds, None, cfg)            # compile + cache
    t0 = time.perf_counter()
    b = train_booster(ds, None, cfg)
    jax.block_until_ready(b.trees[-1].leaf_value)
    v = N_ROWS * TIMED_ITERS / (time.perf_counter() - t0)
    auc_d = float(_auc(y, b.predict(X, binned=False)))
    b_l = train_booster(ds, None, BoosterConfig(
        objective="binary", num_iterations=TIMED_ITERS, seed=1))
    auc_l = float(_auc(y, b_l.predict(X, binned=False)))
    return {"metric": "gbdt_train_depthwise_row_iters_per_sec_per_chip",
            "value": round(v, 1),
            "unit": f"row-iterations/sec/chip (AUC {auc_d:.4f} vs "
                    f"leafwise {auc_l:.4f})",
            "vs_baseline": round(v / BASELINE_GBDT_ROW_ITERS, 3)}


def bench_oocore_gbdt(rows=200_000, cols=50, iters=6):
    """Out-of-core streamed GBDT vs the classic resident trainer
    (docs/out-of-core.md; ROADMAP item 2).

    Three timed runs, one growth policy (depthwise — the resident policy
    the streamed level-synchronous grower shares its split math with, so
    the ratio measures STREAMING overhead, not a policy change):

    * resident — classic ``train_booster`` with the whole binned matrix
      device-resident (the denominator);
    * streamed @ 1x — the chunk pump with default geometry, everything
      still fits (pure pump overhead);
    * streamed @ 10x — ``SYNAPSEML_TPU_STREAM_MEM_BUDGET`` pinned to a
      tenth of the quantized stream's bytes, so the (depth+1) in-flight
      chunks simulate a device 10x too small for the dataset — the
      headline out-of-core claim, guarded in ci.sh at >= 0.7x resident.
    """
    import jax

    from synapseml_tpu.gbdt import (BoosterConfig, StreamedDataset,
                                    train_booster, train_booster_streamed)
    from synapseml_tpu.ops.hist_kernel import features_padded

    rng = np.random.default_rng(0)
    X = rng.normal(size=(rows, cols)).astype(np.float32)
    y = (X[:, 0] * X[:, 1] + 0.5 * X[:, 2]
         + 0.2 * rng.normal(size=rows) > 0).astype(np.float32)
    cfg = BoosterConfig(objective="binary", num_iterations=iters, seed=1,
                        growth_policy="depthwise")

    def timed(fn):
        fn()                                    # compile + cache
        t0 = time.perf_counter()
        b = fn()
        jax.block_until_ready(b.trees[-1].leaf_value)
        return rows * iters / (time.perf_counter() - t0)

    v_res = timed(lambda: train_booster(X, y, cfg))

    ds1 = StreamedDataset.from_arrays(X, y)
    ds1.prepare(cfg)
    v_1x = timed(lambda: train_booster_streamed(ds1, cfg))

    # the quantized stream's device footprint per row (uint8 bins padded to
    # the feature tile + y/w/m/score f32 + node i32 — gbdt/stream.py)
    row_bytes = features_padded(cols) + 20
    stream_bytes = rows * row_bytes
    budget = stream_bytes // 10
    old = os.environ.get("SYNAPSEML_TPU_STREAM_MEM_BUDGET")
    os.environ["SYNAPSEML_TPU_STREAM_MEM_BUDGET"] = str(budget)
    try:
        ds10 = StreamedDataset.from_arrays(X, y)
        ds10.prepare(cfg)                       # geometry resolves NOW
        v_10x = timed(lambda: train_booster_streamed(ds10, cfg))
    finally:
        if old is None:
            os.environ.pop("SYNAPSEML_TPU_STREAM_MEM_BUDGET", None)
        else:
            os.environ["SYNAPSEML_TPU_STREAM_MEM_BUDGET"] = old

    in_flight = (ds10.depth + 1) * ds10.chunk_rows * row_bytes
    oversize = stream_bytes / max(in_flight, 1)
    ratio_1x = v_1x / max(v_res, 1e-9)
    ratio_10x = v_10x / max(v_res, 1e-9)

    # chunk-geometry A/B for the io_chunk_rows perfmodel family: short
    # streamed trains at power-of-two chunk sizes around the probe-formula
    # default (the default itself included, so the model can only displace
    # it on a measured win). Features mirror perfmodel.suggest_chunk_rows —
    # the stream's per-row device bytes, pump depth, arm chunk rows.
    import dataclasses as _dc

    from synapseml_tpu.core import perfmodel
    from synapseml_tpu.io.ingest import stream_chunk_rows, stream_depth

    c_default = stream_chunk_rows(row_bytes)
    p = int(round(np.log2(max(c_default, 2))))
    chunk_arms = sorted({c_default}
                        | {1 << q for q in (p - 1, p, p + 1)
                           if 8192 <= (1 << q) <= (1 << 20)})
    ab_cfg = _dc.replace(cfg, num_iterations=3)
    depth = stream_depth()
    chunk_ab = {}
    for cr in chunk_arms:
        ds = StreamedDataset.from_arrays(X, y, chunk_rows=cr)
        ds.prepare(ab_cfg)
        t0 = time.perf_counter()
        b = train_booster_streamed(ds, ab_cfg)
        jax.block_until_ready(b.trees[-1].leaf_value)
        dt = time.perf_counter() - t0
        # observed seconds PER ROW so rows stay comparable across bench
        # sizes (the analytic prior is also per-row)
        _perf_row("io_chunk_rows", f"c{cr}",
                  perfmodel.featurize(row_bytes=row_bytes, depth=depth,
                                      chunk_rows=cr),
                  dt / (rows * ab_cfg.num_iterations),
                  default_arm=(cr == c_default))
        chunk_ab[str(cr)] = round(rows * ab_cfg.num_iterations / dt, 1)
    return {"metric": "oocore_gbdt_streamed_row_iters_per_sec",
            "value": round(v_10x, 1),
            "unit": (f"row-iterations/sec streamed @ 10x-oversized "
                     f"({ds10.chunk_rows} rows/chunk x "
                     f"{len(ds10.chunks)} chunks; resident {v_res:.0f}, "
                     f"streamed@1x {v_1x:.0f} r-i/s)"),
            "vs_baseline": round(v_10x / BASELINE_GBDT_ROW_ITERS, 3),
            "resident_row_iters_per_s": round(v_res, 1),
            "streamed_1x_row_iters_per_s": round(v_1x, 1),
            "streamed_vs_resident_1x": round(ratio_1x, 3),
            "streamed_vs_resident_10x": round(ratio_10x, 3),
            "oversize_ratio": round(oversize, 1),
            "chunk_geometry_row_iters_per_s": chunk_ab,
            "chunk_default_rows": c_default,
            "guard": {"streamed_10x_ge_0p7x_resident": ratio_10x >= 0.7,
                      "oversize_ratio_ge_10": oversize >= 10.0}}


def bench_oocore_gbdt_mesh(rows=100_000, cols=50, iters=6):
    """Mesh-streamed GBDT at a 10x-undersized budget vs the mesh-resident
    rate (ISSUE 15 tentpole; docs/out-of-core.md mesh data plane).

    Both arms run the SAME mesh programs (``train_booster_streamed`` with
    the chunk source sharded over the data axis and per-chunk frontier
    partials psum'd through the wire ladder); ``resident=True`` stages every
    chunk device-side up front, so the ratio isolates pure streaming
    overhead — pump hand-off + H2D transfer — at mesh scale. Depthwise
    policy, matching ``bench_oocore_gbdt``: level-synchronous growth costs
    one stream pass per LEVEL instead of per split, so the bench finishes
    inside a CI budget without changing what the ratio measures. The 10x arm
    pins ``SYNAPSEML_TPU_STREAM_MEM_BUDGET`` to a tenth of the quantized
    stream, the headline claim ci.sh guards at >= 0.8x. Both arms journal
    ``gbdt_mesh_stream`` perf-model rows so the router prices streamed
    mesh runs from evidence.
    """
    import jax

    from synapseml_tpu.core import perfmodel
    from synapseml_tpu.gbdt import (BoosterConfig, StreamedDataset,
                                    train_booster_streamed)
    from synapseml_tpu.ops.hist_kernel import features_padded
    from synapseml_tpu.parallel.mesh import make_mesh

    # a 4-way data axis, not all 8 virtual devices: XLA CPU collectives
    # rendezvous all participants on an oversubscribed host, and on the
    # 1-core CI box an 8-participant frontier psum can starve and hang
    # nondeterministically. Four participants exercise the same sharded
    # data plane without the deadlock surface; num_leaves=15 keeps the
    # per-level wire payload (L,FP,B,3) small for the same reason.
    W = min(4, len(jax.devices()))
    mesh = make_mesh({"data": W}, devices=jax.devices()[:W])
    rng = np.random.default_rng(0)
    X = rng.normal(size=(rows, cols)).astype(np.float32)
    y = (X[:, 0] * X[:, 1] + 0.5 * X[:, 2]
         + 0.2 * rng.normal(size=rows) > 0).astype(np.float32)
    cfg = BoosterConfig(objective="binary", num_iterations=iters, seed=1,
                        growth_policy="depthwise", num_leaves=15)

    def timed(fn):
        fn()                                    # compile + cache
        t0 = time.perf_counter()
        b = fn()
        jax.block_until_ready(b.trees[-1].leaf_value)
        return time.perf_counter() - t0

    ds_res = StreamedDataset.from_arrays(X, y)
    dt_res = timed(lambda: train_booster_streamed(ds_res, cfg, mesh=mesh,
                                                  resident=True))
    v_res = rows * iters / dt_res

    row_bytes = features_padded(cols) + 20
    stream_bytes = rows * row_bytes
    # chunk geometry rounds chunk_rows UP to a worker multiple, which can
    # push the realized in-flight set a hair over the requested budget;
    # shave the worst-case round-up (depth+1 chunks x W-1 rows) off the
    # request so the 10x-undersized claim holds after rounding
    budget = stream_bytes // 10 - 8 * W * row_bytes
    # pump depth 1 for the streamed arm: lookahead deeper than one chunk
    # buys no overlap on a single-core CI host, while the in-flight budget
    # is split across depth+1 chunks — depth 1 means 1.5x larger chunks at
    # the SAME 10x-undersized budget, amortizing per-chunk dispatch
    old = {k: os.environ.get(k) for k in ("SYNAPSEML_TPU_STREAM_MEM_BUDGET",
                                          "SYNAPSEML_TPU_STREAM_DEPTH")}
    os.environ["SYNAPSEML_TPU_STREAM_MEM_BUDGET"] = str(budget)
    os.environ["SYNAPSEML_TPU_STREAM_DEPTH"] = "1"
    try:
        ds10 = StreamedDataset.from_arrays(X, y)
        dt_10x = timed(lambda: train_booster_streamed(ds10, cfg, mesh=mesh))
    finally:
        for k, v in old.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    v_10x = rows * iters / dt_10x

    in_flight = (ds10.depth + 1) * ds10.chunk_rows * row_bytes
    oversize = stream_bytes / max(in_flight, 1)
    ratio = v_10x / max(v_res, 1e-9)
    feats = perfmodel.featurize(rows=rows, nfeat=cols, workers=W,
                                chunk_rows=ds10.chunk_rows)
    _perf_row("gbdt_mesh_stream", "mesh_resident", feats,
              dt_res / (rows * iters), mesh=mesh, unit="s/row-iteration")
    _perf_row("gbdt_mesh_stream", "mesh_streamed_10x", feats,
              dt_10x / (rows * iters), mesh=mesh, unit="s/row-iteration")
    return {"metric": "oocore_gbdt_mesh_streamed_row_iters_per_sec",
            "value": round(v_10x, 1),
            "unit": (f"row-iterations/sec mesh-streamed @ 10x-oversized "
                     f"(data axis x{W}; {ds10.chunk_rows} rows/chunk x "
                     f"{len(ds10.chunks)} chunks; mesh-resident "
                     f"{v_res:.0f} r-i/s)"),
            "vs_baseline": round(v_10x / BASELINE_GBDT_ROW_ITERS, 3),
            "mesh_resident_row_iters_per_s": round(v_res, 1),
            "mesh_streamed_vs_resident_10x": round(ratio, 3),
            "oversize_ratio": round(oversize, 1),
            "workers": W,
            "guard": {"mesh_streamed_10x_ge_0p8x_mesh_resident":
                          ratio >= 0.8,
                      "oversize_ratio_ge_10": oversize >= 10.0}}


def bench_checkpoint_overhead(rows=50_000, cols=100, iters=20):
    """Checkpointed vs plain gbdt training at dryrun shapes: the robustness
    layer (core/checkpoint.py) must not silently regress the hot path. The
    record carries the relative train-time overhead of snapshotting every 5
    iterations plus the absolute save and verified-restore latencies."""
    import shutil
    import tempfile

    from synapseml_tpu.core.checkpoint import CheckpointStore
    from synapseml_tpu.gbdt import BoosterConfig, train_booster

    rng = np.random.default_rng(0)
    X = rng.normal(size=(rows, cols)).astype(np.float32)
    y = (X[:, 0] + 0.3 * rng.normal(size=rows) > 0).astype(np.float32)
    mk = lambda: BoosterConfig(objective="binary", num_iterations=iters,
                               seed=1)

    # warm BOTH shapes: checkpointing clamps the fused scan chunk to
    # checkpoint_every, a different jit cache entry than the plain run —
    # without this the "overhead" is dominated by that one-time compile
    warm = tempfile.mkdtemp(prefix="bench_ckpt_warm_")
    try:
        train_booster(X, y, mk())
        train_booster(X, y, mk(), checkpoint_store=warm, checkpoint_every=5)
    finally:
        shutil.rmtree(warm, ignore_errors=True)

    t0 = time.perf_counter()
    train_booster(X, y, mk())
    plain_s = time.perf_counter() - t0

    d = tempfile.mkdtemp(prefix="bench_ckpt_")
    try:
        t0 = time.perf_counter()
        train_booster(X, y, mk(), checkpoint_store=d, checkpoint_every=5)
        ckpt_s = time.perf_counter() - t0
        store = CheckpointStore(d)
        t0 = time.perf_counter()
        ckpt = store.load_latest()          # full digest-verified restore
        restore_ms = (time.perf_counter() - t0) * 1e3
        n_saves = max(1, iters // 5)
        blob_mb = sum(len(b) for b in ckpt.artifacts.values()) / 1e6
    finally:
        shutil.rmtree(d, ignore_errors=True)

    overhead = ckpt_s / plain_s - 1.0
    return {"metric": "gbdt_checkpoint_overhead_frac",
            "value": round(overhead, 4),
            "unit": (f"fraction of train time (save every 5 iters: "
                     f"{(ckpt_s - plain_s) / n_saves * 1e3:.1f} ms/save, "
                     f"restore {restore_ms:.1f} ms, {blob_mb:.2f} MB/ckpt)"),
            "vs_baseline": None}


def bench_elastic_recovery(rows=20_000, cols=50, iters=12):
    """Elastic-training recovery price (docs/resilience.md "Elastic
    training"): how long from a peer dying inside a collective to training
    being ready to run again. The three host-side components are timed
    separately because each is bounded by a different knob — stall detection
    (CollectiveWatchdog budget -> PeerLostError), survivor consensus (the
    digest-verified file barrier), and restore-to-ready (loading the agreed
    gbdt snapshot back into a runnable carry, bounded by the checkpoint
    interval)."""
    import shutil
    import tempfile
    import threading

    from synapseml_tpu.core.checkpoint import (CheckpointStore,
                                               PreemptionError)
    from synapseml_tpu.gbdt import BoosterConfig, train_booster
    from synapseml_tpu.parallel.elastic import (CollectiveWatchdog,
                                                HeartbeatMonitor,
                                                HeartbeatWriter,
                                                PeerLostError,
                                                consensus_restart_step)
    from synapseml_tpu.testing.chaos import ChaosPreemption

    # -- detection: a hung call with one stale peer heartbeat -> error
    budget_s = 0.2
    hb = tempfile.mkdtemp(prefix="bench_elastic_hb_")
    det = []
    try:
        HeartbeatWriter(hb, rank=1).beat("allreduce_sum")
        past = time.time() - 60
        os.utime(os.path.join(hb, "hb_p1.json"), (past, past))
        mon = HeartbeatMonitor(hb, timeout=0.5, expected=[0, 1], self_rank=0)
        wd = CollectiveWatchdog(timeout=budget_s, monitor=mon, poll=0.01)
        for _ in range(5):
            t0 = time.perf_counter()
            try:
                wd.run(lambda: threading.Event().wait(60), op="bench.hang")
            except PeerLostError:
                det.append((time.perf_counter() - t0) * 1e3)
    finally:
        shutil.rmtree(hb, ignore_errors=True)
    detect_ms = sorted(det)[len(det) // 2]

    # -- kill mid-train, then price the consensus barrier and the resume
    rng = np.random.default_rng(0)
    X = rng.normal(size=(rows, cols)).astype(np.float32)
    y = (X[:, 0] + 0.3 * rng.normal(size=rows) > 0).astype(np.float32)
    mk = lambda: BoosterConfig(objective="binary", num_iterations=iters,
                               seed=1)
    every = 3
    ck = tempfile.mkdtemp(prefix="bench_elastic_ck_")
    cons = tempfile.mkdtemp(prefix="bench_elastic_cons_")
    try:
        try:
            with ChaosPreemption(at={"gbdt.chunk": [iters // 2]}):
                train_booster(X, y, mk(), checkpoint_store=ck,
                              checkpoint_every=every)
        except PreemptionError:
            pass
        store = CheckpointStore(ck)
        t0 = time.perf_counter()
        agreed = consensus_restart_step(store, cons, rank=0, expected=[0],
                                        timeout=10.0)
        consensus_ms = (time.perf_counter() - t0) * 1e3
        # restore-to-ready: the resume is preempted at its very first loop
        # boundary (done == agreed step), so the elapsed time is exactly
        # setup + verified load + carry placement, no training iterations
        t0 = time.perf_counter()
        try:
            with ChaosPreemption(at={"gbdt.chunk": [agreed]}):
                train_booster(X, y, mk(), checkpoint_store=ck,
                              checkpoint_every=every)
        except PreemptionError:
            pass
        ready_ms = (time.perf_counter() - t0) * 1e3
    finally:
        shutil.rmtree(ck, ignore_errors=True)
        shutil.rmtree(cons, ignore_errors=True)

    total = detect_ms + consensus_ms + ready_ms
    return {"metric": "elastic_recovery_total_ms",
            "value": round(total, 1),
            "unit": (f"ms detect->agree->resume (detect {detect_ms:.0f} ms "
                     f"at a {budget_s:.1f}s watchdog budget, consensus "
                     f"{consensus_ms:.1f} ms, restore-to-ready "
                     f"{ready_ms:.0f} ms from step {agreed}/{iters}, "
                     f"checkpoint interval {every})"),
            "vs_baseline": None}


def bench_online_learning(n_events=8192, batch_size=64, n_requests=200):
    """Online bandit loop under live serving (docs/online-learning.md):
    sustained learner updates/s while the epsilon-greedy policy answers
    HTTP traffic, plus the promotion-gate latency (counterfactual scoring
    over the logged window + zero-downtime hot-swap). The record prices the
    whole serving→training loop, not the learner in isolation."""
    import json as _json
    import shutil
    import tempfile
    import threading
    import urllib.request

    from synapseml_tpu.core.checkpoint import CheckpointStore
    from synapseml_tpu.io.serving import ModelRegistry, ServingServer
    from synapseml_tpu.online import (FeedbackEvent, FeedbackLog,
                                      GreedyPolicy, OnlineLearnerLoop,
                                      PromotionGate, make_policy_handler,
                                      policy_builder)
    from synapseml_tpu.vw.learner import (VWConfig, VWState,
                                          make_sparse_batch)

    cfg = VWConfig(num_bits=16, batch_size=batch_size, learning_rate=0.5)
    k = 4

    def featurize(_v=None):
        return list(make_sparse_batch(
            [[a * 11 + 1, a * 11 + 2, a * 11 + 3] for a in range(k)],
            [[1.0, 1.0, 1.0]] * k, pad_to=4))

    rng = np.random.default_rng(0)
    acts = featurize()

    def events(n, seed):
        r = np.random.default_rng(seed)
        out = []
        for i in range(n):
            a = int(r.integers(1, k + 1))
            out.append(FeedbackEvent(
                key=f"b{seed}.{i}", actions=acts, action=a,
                probability=1.0 / k,
                reward=0.9 if a == 2 else float(r.random() * 0.2)))
        return out

    incumbent = GreedyPolicy(VWState.init(cfg.num_bits), cfg, epsilon=1.0,
                             seed=0, version="v0")
    srv = ServingServer(make_policy_handler(incumbent, featurize),
                        port=0, max_batch_latency=0.0).start()
    d = tempfile.mkdtemp(prefix="bench_online_")
    try:
        reg = ModelRegistry(srv, version="v0")
        gate = PromotionGate(reg, min_samples=256)
        store = CheckpointStore(d, keep_last=3)
        log = FeedbackLog(capacity=n_events + 1)
        loop = OnlineLearnerLoop(log, cfg, store=store,
                                 snapshot_every=16)
        warm = events(batch_size, seed=99)       # compile the update program
        for ev in warm:
            log.offer(ev)
        loop.run_until_drained()

        body = _json.dumps({}).encode()
        served = [0]

        def client():
            for _ in range(n_requests):
                req = urllib.request.Request(
                    srv.url, data=body,
                    headers={"Content-Type": "application/json"},
                    method="POST")
                with urllib.request.urlopen(req, timeout=10) as r:
                    r.read()
                    served[0] += 1

        for ev in events(n_events, seed=1):
            log.offer(ev)
            gate.record(ev)
        t_client = threading.Thread(target=client)
        t0 = time.perf_counter()
        t_client.start()
        updates = loop.run_until_drained()
        train_s = time.perf_counter() - t0
        t_client.join()
        serve_s = time.perf_counter() - t0

        t0 = time.perf_counter()
        dec = gate.try_promote(store, policy_builder(cfg, featurize))
        promote_ms = (time.perf_counter() - t0) * 1e3
        assert dec.promoted, f"gate refused the trained candidate: {dec}"
        updates_per_s = updates / train_s
        return {"metric": "online_learning_updates_per_s",
                "value": round(updates_per_s, 1),
                "unit": (f"updates/s ({updates_per_s * batch_size:.0f} "
                         f"events/s, batch {batch_size}, while serving "
                         f"{served[0] / serve_s:.0f} req/s; promotion "
                         f"gate+swap {promote_ms:.1f} ms over "
                         f"{dec.n_samples} logged samples)"),
                "promotion_ms": round(promote_ms, 1),
                "vs_baseline": None}
    finally:
        srv.stop()
        shutil.rmtree(d, ignore_errors=True)


def bench_voting_ab(rows=50_000, cols=100, iters=10):
    """Voting-parallel vs data-parallel GBDT A/B on the virtual 8-device CPU
    mesh at dryrun shapes (VERDICT r3 stretch #9; LightGBMParams.scala:25-27
    voting_parallel + topK). Wide feature space (200 cols, top_k=20 ->
    2k=40 aggregated) is where PV-Tree's reduced histogram allreduce pays:
    the reported ratio prices that comm saving. Same-platform ratio — valid
    off-chip by construction (both arms ride the identical mesh)."""
    import jax

    from synapseml_tpu.gbdt import BoosterConfig, train_booster
    from synapseml_tpu.gbdt.objectives import auc as _auc
    from synapseml_tpu.parallel import make_mesh

    rng = np.random.default_rng(0)
    X = rng.normal(size=(rows, cols)).astype(np.float32)
    informative = rng.choice(cols, size=8, replace=False)
    margin = sum(X[:, j] for j in informative)
    y = (margin + rng.normal(scale=0.5, size=rows) > 0).astype(np.float32)

    mesh = make_mesh({"data": 8})
    kw = dict(objective="binary", num_iterations=iters, num_leaves=15,
              max_bin=63, seed=1)
    top_k = 20
    out = {}
    for name, extra in (("data_parallel", {}),
                        ("voting", {"tree_learner": "voting",
                                    "top_k": top_k})):
        cfg = BoosterConfig(**kw, **extra)
        train_booster(X, y, cfg, mesh=mesh)      # compile + cache
        t0 = time.perf_counter()
        b = train_booster(X, y, cfg, mesh=mesh)
        jax.block_until_ready(b.trees[-1].leaf_value)
        dt = time.perf_counter() - t0
        out[name] = {"row_iters_per_s": rows * iters / dt,
                     "auc": float(_auc(y, b.predict(X, binned=False)))}
    v, d = out["voting"], out["data_parallel"]
    # collective cost model (VERDICT r4 #7): exact logical bytes both modes
    # move per split, the measured per-tree selection overhead on THIS mesh
    # (comm is memcpy here, so the whole arm delta is selection + slicing),
    # and the implied crossover link bandwidth below which voting pays.
    from synapseml_tpu.gbdt.voting import voting_cost_model

    sel_s_per_tree = max(rows * iters / v["row_iters_per_s"]
                         - rows * iters / d["row_iters_per_s"], 0.0) / iters
    model = voting_cost_model(cols, kw["max_bin"], top_k, kw["num_leaves"],
                              selection_s_per_tree=max(sel_s_per_tree, 1e-9))
    model["measured_selection_s_per_tree"] = round(sel_s_per_tree, 4)
    return {"metric": "gbdt_voting_vs_data_parallel_speedup",
            "platform": "cpu-mesh-8",   # honest provenance: never the chip
            "value": round(v["row_iters_per_s"] / d["row_iters_per_s"], 3),
            "unit": (f"x (8-dev CPU mesh, {cols} cols; voting "
                     f"{v['row_iters_per_s']:.0f} r-i/s AUC {v['auc']:.4f} "
                     f"vs data-parallel {d['row_iters_per_s']:.0f} r-i/s "
                     f"AUC {d['auc']:.4f})"),
            "collective_cost_model": model,
            # >1.0 means voting's reduced allreduce wins at this shape
            "vs_baseline": round(v["row_iters_per_s"]
                                 / d["row_iters_per_s"], 3)}


def bench_distributed_gbdt_auto(iters=10):
    """Distributed-GBDT router A/B on the virtual 8-device CPU mesh: every
    manual parallelism flag (data / voting where F > 2k / feature) vs
    ``tree_learner='auto'`` with the int8 histogram wire, on the three shapes
    the router must not misroute — wide (r05's 100-col shape), narrow
    (20-col) and tall. Same-platform ratios — valid off-chip by construction
    (all arms ride the identical mesh; each arm's rate is the best of two
    timed fits, since single fits on a contended host jitter ~10%). The wide
    dataset also runs the exact r05 configuration (data-parallel, f32 wire)
    as a same-run baseline: r05's absolute 26.6k r-i/s was captured on
    different hardware and absolute rates don't transfer, so the 1.5x claim
    is anchored to the baseline RE-MEASURED in this run. The returned record
    carries per-dataset rates, the router's recorded decision + cost-model
    inputs (booster metadata), and the two guard verdicts ci.sh enforces:
    auto >= 0.95x the best manual flag everywhere, and wide auto >= 1.5x the
    same-run data-parallel f32 baseline."""
    import jax

    from synapseml_tpu.gbdt import BoosterConfig, train_booster
    from synapseml_tpu.gbdt.voting import collective_bytes_per_split
    from synapseml_tpu.parallel import make_mesh

    mesh = make_mesh({"data": 8})
    r05_rate = 26_600.0          # round-5 driver run, 8-dev CPU mesh,
    #                              data-parallel r-i/s (record deleted PR 21)
    top_k = 20
    base = dict(objective="binary", num_leaves=15, max_bin=63, seed=1,
                top_k=top_k, hist_allreduce_dtype="int8")
    datasets = {"wide": (50_000, 100), "narrow": (12_288, 20),
                "tall": (40_960, 20)}
    results = {}
    for dname, (rows, cols) in datasets.items():
        rng = np.random.default_rng(0)
        X = rng.normal(size=(rows, cols)).astype(np.float32)
        informative = rng.choice(cols, size=8, replace=False)
        y = (sum(X[:, j] for j in informative)
             + rng.normal(scale=0.5, size=rows) > 0).astype(np.float32)
        arms = ["data"] + (["voting"] if cols > 2 * top_k else []) \
            + ["feature", "auto"]
        if dname == "wide":
            arms.append("data_f32")      # the r05 config, re-measured here
        dres = {}
        for arm in arms:
            kw = dict(base, num_iterations=iters, tree_learner=arm)
            if arm == "data_f32":
                kw.update(tree_learner="data", hist_allreduce_dtype="f32")
            # warm separately: compile + router probes land in caches, the
            # timed fits measure the steady-state production path
            train_booster(X, y, BoosterConfig(**kw), mesh=mesh)
            best_dt, cfg = float("inf"), None
            for _ in range(2):           # best-of-2 damps scheduler noise
                cfg = BoosterConfig(**kw)
                t0 = time.perf_counter()
                b = train_booster(X, y, cfg, mesh=mesh)
                jax.block_until_ready(b.trees[-1].leaf_value)
                best_dt = min(best_dt, time.perf_counter() - t0)
            dres[arm] = {"row_iters_per_s": round(rows * iters / best_dt, 1),
                         "resolved": cfg.tree_learner}
            if arm == "auto":
                dres[arm]["routing"] = b.metadata.get("routing")
            else:
                # manual arms are labelled ground truth for the perfmodel:
                # same feature schema _auto_route ranks candidates with.
                # data_f32 is excluded — same learner at a different wire
                # dtype would confound the learner family's "data" arm (it
                # prices the WIRE family below instead)
                if arm != "data_f32":
                    from synapseml_tpu.gbdt.boosting import _route_features

                    _perf_row("gbdt_tree_learner", arm,
                              _route_features(cfg, rows, cols, 8), best_dt,
                              mesh=mesh)
                if arm in ("data", "data_f32"):
                    # the same pair of timed fits prices the wire ladder:
                    # identical routing, int8 vs f32 histogram allreduce
                    from synapseml_tpu.core import perfmodel

                    wd = cfg.hist_allreduce_dtype
                    _perf_row("gbdt_wire_dtype", wd, perfmodel.featurize(
                        wire_dtype=wd, rows=rows, nfeat=cols, workers=8,
                        max_bin=base["max_bin"],
                        num_leaves=base["num_leaves"]), best_dt, mesh=mesh)
        best_manual = max(v["row_iters_per_s"] for a, v in dres.items()
                          if a not in ("auto", "data_f32"))
        auto_rate = dres["auto"]["row_iters_per_s"]
        resolved = dres["auto"]["resolved"]
        results[dname] = {
            "rows": rows, "cols": cols, "arms": dres,
            "auto_vs_best_manual": round(auto_rate / best_manual, 3),
            # logical wire bytes per tree at the resolved mode + int8 ladder
            # rung (feature-parallel reduce-scatter moves half an allreduce)
            "collective_bytes_per_tree": int(
                (base["num_leaves"] - 1)
                * collective_bytes_per_split(
                    cols, base["max_bin"],
                    top_k=(top_k if resolved == "voting" else None),
                    dtype_bytes=2.0)
                * (0.5 if resolved == "feature" else 1.0)),
        }
    min_ratio = min(r["auto_vs_best_manual"] for r in results.values())
    wide_auto = results["wide"]["arms"]["auto"]["row_iters_per_s"]
    data_f32 = results["wide"]["arms"]["data_f32"]["row_iters_per_s"]
    speedup = wide_auto / data_f32
    return {"metric": "gbdt_distributed_auto_vs_manual",
            "platform": "cpu-mesh-8",   # honest provenance: never the chip
            "value": round(min_ratio, 3),
            "unit": ("x (auto / best manual r-i/s, min over "
                     "wide/narrow/tall; auto wide "
                     f"{wide_auto:.0f} r-i/s = {speedup:.2f}x the same-run "
                     "data-parallel f32 baseline)"),
            "distributed_row_iters_per_s": wide_auto,
            "data_parallel_f32_row_iters_per_s": data_f32,
            "speedup_vs_data_parallel_f32": round(speedup, 2),
            # context only: the r05 capture ran on different hardware, so
            # its absolute rate is not comparable to this run's
            "r05_recorded_rate": r05_rate,
            "datasets": results,
            "guard": {"auto_within_5pct_of_best_manual": min_ratio >= 0.95,
                      "wide_auto_ge_1p5x_data_parallel_f32":
                          wide_auto >= 1.5 * data_f32},
            "vs_baseline": round(speedup, 3)}


def bench_dl_sharded(epochs=3):
    """ZeRO vs replicated vs pipeline A/B for the dl/ trainer on the virtual
    8-device CPU mesh (same-platform ratios, valid off-chip): a staged
    resnet18 (width 16, 16x16 inputs) and a BERT-style staged text encoder,
    each trained with identical data/seed under the three placements. Epoch 0
    absorbs compile; the best of the remaining epochs is the steady-state
    measurement (best-of damps scheduler noise on a contended host). Reports per-arm
    step time and peak per-device live state bytes
    (``dl.per_device_state_bytes``: params + optimizer moments from each
    leaf's sharding, allocator-independent), plus the two guard verdicts
    ci.sh enforces: ZeRO state bytes <= 0.6x replicated and ZeRO step time
    within 1.15x replicated on both models."""
    from synapseml_tpu import dl, parallel

    rng = np.random.default_rng(0)
    configs = {
        "resnet": dict(
            model=lambda: dl.make_staged_backbone(
                "resnet18", num_classes=10, num_stages=2,
                small_images=True, width=16),
            X=rng.normal(size=(256, 16, 16, 3)).astype(np.float32),
            y=rng.integers(0, 10, size=256)),
        "bert": dict(
            model=lambda: dl.staged_text_encoder(
                vocab_size=2048, num_classes=2, num_stages=2,
                num_layers=4, hidden=128, heads=4, max_len=64),
            X=rng.integers(0, 2048, size=(256, 64)).astype(np.int32),
            y=rng.integers(0, 2, size=256)),
    }
    mesh_data = parallel.make_mesh({"data": 8})
    mesh_pipe = parallel.make_mesh({"stage": 2, "data": 4})
    arms = {"replicated": ("replicated", mesh_data),
            "zero": ("zero", mesh_data),
            "pipeline": ("pipeline", mesh_pipe)}
    results = {}
    for cname, spec in configs.items():
        model = spec["model"]()      # one module, three placements
        cres = {}
        for aname, (sharding, mesh) in arms.items():
            cfg = dl.TrainConfig(batch_size=32, max_epochs=epochs,
                                 learning_rate=1e-3, seed=3,
                                 param_sharding=sharding,
                                 pipeline_microbatches=2)
            tr = dl.FlaxTrainer(model, cfg, mesh=mesh)
            tr.fit(spec["X"], spec["y"])
            steady = tr.history[1:]
            cres[aname] = {
                "step_ms": round(min(1e3 * e["seconds"]
                                     / max(e["steps"], 1)
                                     for e in steady), 2),
                "state_bytes_per_device":
                    tr.stats["state_bytes_per_device"],
                "final_loss": round(tr.history[-1]["loss"], 4),
            }
            # labelled step time for the dl_param_sharding family (schema of
            # perfmodel.suggest_param_sharding / trainer autoconfig)
            import jax

            from synapseml_tpu.core import perfmodel

            pb = int(sum(int(np.prod(p.shape)) * p.dtype.itemsize
                         for p in jax.tree.leaves(tr.params)))
            data_axis = int(dict(mesh.shape).get("data", 1))
            feats = dict(param_bytes=pb, batch=cfg.batch_size,
                         workers=data_axis)
            if aname == "pipeline":
                feats["stages"] = 2
            _perf_row("dl_param_sharding", aname,
                      perfmodel.featurize(**feats),
                      cres[aname]["step_ms"] / 1e3, mesh=mesh)
        rep, zero = cres["replicated"], cres["zero"]
        cres["zero_bytes_ratio"] = round(
            zero["state_bytes_per_device"]
            / max(rep["state_bytes_per_device"], 1), 3)
        cres["zero_step_ratio"] = round(
            zero["step_ms"] / max(rep["step_ms"], 1e-9), 3)
        results[cname] = cres
    worst_bytes = max(r["zero_bytes_ratio"] for r in results.values())
    worst_step = max(r["zero_step_ratio"] for r in results.values())
    return {"metric": "dl_zero_state_bytes_vs_replicated",
            "platform": "cpu-mesh-8",   # honest provenance: never the chip
            "value": worst_bytes,
            "unit": ("x (ZeRO / replicated per-device state bytes, worst of "
                     f"resnet/bert; ZeRO step time {worst_step:.2f}x "
                     "replicated worst-case)"),
            "zero_step_time_ratio": worst_step,
            "models": results,
            "guard": {"zero_bytes_le_0p6x_replicated": worst_bytes <= 0.6,
                      "zero_step_within_1p15x_replicated":
                          worst_step <= 1.15}}


def bench_dl_overlap_pipeline(epochs=3, trials=3):
    """Overlap vs fill-drain pipeline schedule A/B on the virtual 8-device
    CPU mesh (same-platform ratio, valid off-chip): the staged-BERT config
    with ZeRO within each stage group. The overlap schedule gathers each
    stage's weights once per batch into a double buffer (prefetching the
    next batch's gather behind backward) and accumulates grads through a
    donated running sum, where fill-drain pays the per-program weight
    traffic inside every per-microbatch program (docs/dl-scaling.md
    "Overlap schedule"). Activation-heavy microbatches (128-row batch,
    M=2, seq 64) make that per-program traffic the dominant cost — the
    regime the overlap schedule exists for; tiny microbatches invert the
    tradeoff (GSPMD turns ZeRO shards into cheaper sharded compute).
    Measurement: the two pipeline arms run as interleaved paired trials
    (fill, overlap, fill, overlap, ...) so both see the same host load;
    each trial's step time is best-of-steady-epochs (epoch 0 absorbs
    compile) and the reported speedup is the MEDIAN of per-trial ratios —
    one trial hit by a scheduler burst cannot flip the guard either way.
    Guards: overlap >= 1.05x faster than fill-drain, and both schedules
    match the replicated trainer's loss trajectory to <= 1e-5 (same math,
    different placement/schedule)."""
    from synapseml_tpu import dl, parallel

    rng = np.random.default_rng(0)
    X = rng.integers(0, 2048, size=(256, 64)).astype(np.int32)
    y = rng.integers(0, 2, size=256)
    model = dl.staged_text_encoder(vocab_size=2048, num_classes=2,
                                   num_stages=2, num_layers=2, hidden=256,
                                   heads=4, max_len=64)
    mesh_data = parallel.make_mesh({"data": 8})
    mesh_pipe = parallel.make_mesh({"stage": 2, "data": 4})

    def run(sharding, mesh, schedule="fill_drain"):
        cfg = dl.TrainConfig(batch_size=128, max_epochs=epochs,
                             learning_rate=1e-3, seed=3,
                             param_sharding=sharding,
                             pipeline_param_sharding="zero",
                             pipeline_microbatches=2,
                             pipeline_schedule=schedule)
        tr = dl.FlaxTrainer(model, cfg, mesh=mesh)
        tr.fit(X, y)
        steady = tr.history[1:]
        return {"step_ms": round(min(1e3 * e["seconds"] / max(e["steps"], 1)
                                     for e in steady), 2),
                "losses": [round(e["loss"], 7) for e in tr.history]}
    rep = run("replicated", mesh_data)
    ratios, fill, over = [], None, None
    for _ in range(max(int(trials), 1)):
        fill = run("pipeline", mesh_pipe, "fill_drain")
        over = run("pipeline", mesh_pipe, "overlap")
        ratios.append(fill["step_ms"] / max(over["step_ms"], 1e-9))
    speedup = float(np.median(ratios))
    parity = max(abs(a - b) for arm in (fill, over)
                 for a, b in zip(arm["losses"], rep["losses"]))
    # labelled step times for the dl_pipeline_schedule family (schema of
    # perfmodel.suggest_pipeline_schedule: 2 stages, M=2 microbatches)
    from synapseml_tpu.core import perfmodel

    for sched_arm, res in (("fill_drain", fill), ("overlap", over)):
        _perf_row("dl_pipeline_schedule", sched_arm,
                  perfmodel.featurize(stages=2, microbatches=2),
                  res["step_ms"] / 1e3, mesh=mesh_pipe)
    return {"metric": "dl_overlap_vs_fill_drain_speedup",
            "platform": "cpu-mesh-8",   # honest provenance: never the chip
            "value": round(speedup, 3),
            "unit": ("x (fill_drain / overlap step time, staged-BERT, "
                     "zero-within-group, M=2 microbatches of 64 rows, "
                     "median of paired trials)"),
            "trial_speedups": [round(r, 3) for r in ratios],
            "loss_parity_vs_replicated": parity,
            "arms": {"replicated": rep, "fill_drain": fill,
                     "overlap": over},
            "guard": {"overlap_ge_1p05x_fill_drain": speedup >= 1.05,
                      "schedule_parity_le_1em5_vs_replicated":
                          parity <= 1e-5}}


def bench_dl_seq(epochs=3):
    """Sequence-parallel attention A/B on the virtual 8-device CPU mesh
    (same-platform ratios, valid off-chip), three arms:

    1. **Training parity** — the staged-BERT config at seq 256 trained
       under zero on a data-only mesh (unsharded attention) vs a
       ``{"seq": 4, "data": 2}`` mesh with ring and with Ulysses routing.
       Seq routing is scope-only (docs/dl-scaling.md "Sequence
       parallelism"): the param tree and update math are identical, so
       the loss trajectories must agree to <= 1e-5. Per-arm steady step
       time is journaled as ``seq_attention`` perfmodel rows (the schema
       of ``perfmodel.suggest_seq_attention``).
    2. **Long sequence (8k)** — ring vs Ulysses forward at seq 8192
       (independent algorithms: P2P KV rotation vs two all-to-alls);
       their outputs must agree to <= 1e-5, a second journaled A/B
       workload, and the per-host activation bytes of the sharded
       operands must be <= 0.3x the unsharded arrays (exact sharding
       arithmetic says 1/4; measured from addressable shard bytes,
       allocator-independent like ``dl.per_device_state_bytes``).
    3. **Over-budget (32k)** — a seq-32k config whose full S x S score
       matrix (4.3 GB) exceeds the documented single-shard host budget
       (2 GiB) runs the seq-sharded ring forward to a finite result with
       per-ring-step block scores of only 268 MB. Parity for this regime
       is carried by arm 1: the 32k path is the same scoped routing,
       just a bigger shard.
    """
    from synapseml_tpu import dl, parallel
    from synapseml_tpu.core import perfmodel
    from synapseml_tpu.parallel.ring_attention import ring_self_attention
    from synapseml_tpu.parallel.ulysses import ulysses_self_attention
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    host_budget_bytes = 2 * 1024**3   # single-shard score-matrix budget
    rng = np.random.default_rng(0)

    # --- arm 1: training parity + step-time A/B at seq 256 ---------------
    seq_len, heads, hidden, bs = 256, 4, 64, 32
    X = rng.integers(0, 2048, size=(128, seq_len)).astype(np.int32)
    y = rng.integers(0, 2, size=128)
    model = dl.staged_text_encoder(vocab_size=2048, num_classes=2,
                                   num_stages=2, num_layers=2, hidden=hidden,
                                   heads=heads, max_len=seq_len)
    mesh_data = parallel.make_mesh({"data": 8})
    mesh_seq = parallel.make_mesh({"seq": 4, "data": 2})

    def run(mesh, seq_attention):
        cfg = dl.TrainConfig(batch_size=bs, max_epochs=epochs,
                             learning_rate=1e-3, seed=3,
                             param_sharding="zero",
                             seq_attention=seq_attention)
        tr = dl.FlaxTrainer(model, cfg, mesh=mesh)
        tr.fit(X, y)
        steady = tr.history[1:]
        return {"step_ms": round(min(1e3 * e["seconds"] / max(e["steps"], 1)
                                     for e in steady), 2),
                "losses": [round(e["loss"], 7) for e in tr.history],
                "seq_attention": tr.stats.get("seq_attention")}
    ref = run(mesh_data, "auto")          # no seq axis: attention unsharded
    arms = {a: run(mesh_seq, a) for a in ("ring", "ulysses")}
    parity = max(abs(a - b) for arm in arms.values()
                 for a, b in zip(arm["losses"], ref["losses"]))
    feats = perfmodel.featurize(seq_len=seq_len, heads=heads, seq_shards=4,
                                head_dim=hidden // heads, batch=bs)
    for aname, res in arms.items():
        _perf_row("seq_attention", aname, feats, res["step_ms"] / 1e3,
                  mesh=mesh_seq)

    # --- arm 2: 8k forward A/B + per-host activation bytes ---------------
    mesh_seq4 = parallel.make_mesh({"seq": 4})
    b8, s8, h8, d8 = 1, 8192, 4, 8
    qkv = [jnp.asarray(rng.normal(size=(b8, s8, h8, d8)), jnp.float32)
           for _ in range(3)]
    spec = P(None, "seq", None, None)
    qkv_sh = [jax.device_put(a, NamedSharding(mesh_seq4, spec)) for a in qkv]
    act_ratio = (qkv_sh[0].addressable_shards[0].data.nbytes
                 / qkv[0].nbytes)

    def timed(fn, *args, **kw):
        out = jax.block_until_ready(fn(*args, **kw))   # compile + warm
        best = math.inf
        for _ in range(2):
            t0 = time.perf_counter()
            out = jax.block_until_ready(fn(*args, **kw))
            best = min(best, time.perf_counter() - t0)
        return out, best
    ring_out, ring_s = timed(ring_self_attention, *qkv_sh, mesh_seq4,
                             causal=True)
    uly_out, uly_s = timed(ulysses_self_attention, *qkv_sh, mesh_seq4,
                           causal=True)
    parity_8k = float(jnp.max(jnp.abs(ring_out - uly_out)))
    feats8k = perfmodel.featurize(seq_len=s8, heads=h8, seq_shards=4,
                                  head_dim=d8, batch=b8)
    _perf_row("seq_attention", "ring", feats8k, ring_s, mesh=mesh_seq4)
    _perf_row("seq_attention", "ulysses", feats8k, uly_s, mesh=mesh_seq4)

    # --- arm 3: seq-32k over the single-shard budget ----------------------
    s32, h32, d32 = 32768, 1, 8
    full_score_bytes = 4 * h32 * s32 * s32            # f32 S x S per head
    shard_score_bytes = 4 * h32 * (s32 // 4) ** 2     # one ring-step block
    q32 = jax.device_put(
        jnp.asarray(rng.normal(size=(1, s32, h32, d32)), jnp.float32),
        NamedSharding(mesh_seq4, spec))
    out32, s32_s = timed(ring_self_attention, q32, q32, q32, mesh_seq4,
                         causal=True)
    seq32k_finite = bool(jnp.all(jnp.isfinite(out32)))
    over_budget_ok = (full_score_bytes > host_budget_bytes
                      and shard_score_bytes < host_budget_bytes
                      and seq32k_finite)
    return {"metric": "dl_seq_parity_vs_unsharded",
            "platform": "cpu-mesh-8",   # honest provenance: never the chip
            "value": parity,
            "unit": ("max |loss delta| (staged-BERT seq 256, seq x 4 ring "
                     "and ulysses vs unsharded zero, identical data/seed)"),
            "arms": {"unsharded": ref, **arms},
            "parity_8k_ring_vs_ulysses": parity_8k,
            "forward_8k_s": {"ring": round(ring_s, 4),
                             "ulysses": round(uly_s, 4)},
            "activation_bytes_ratio": round(act_ratio, 4),
            "seq32k": {"full_score_bytes": full_score_bytes,
                       "shard_block_score_bytes": shard_score_bytes,
                       "host_budget_bytes": host_budget_bytes,
                       "forward_s": round(s32_s, 4),
                       "finite": seq32k_finite},
            "guard": {"seq_parity_le_1em5_vs_unsharded": parity <= 1e-5,
                      "activation_bytes_le_0p3x": act_ratio <= 0.3,
                      "seq32k_over_budget_sharded_ok": over_budget_ok}}


def bench_automl_elastic(rows=1200, cols=10, folds=6):
    """Elastic successive-halving AutoML vs exhaustive CV (docs/automl.md).

    Three arms over the same 12-candidate LightGBM regression grid:
    ``exhaustive`` (every candidate × every fold — the pre-bracket searcher),
    ``halving`` (eta=3 rung ladder: 12×1 + 4×2 + 2×3 = 26 fold-fits, 36% of
    72), and ``halving_elastic`` (the same bracket with the full resilience
    stack on: checkpointed bracket state + per-candidate records + budget
    reaper). Guards: the bracket's winner stays within 2% of the exhaustive
    best while spending ≤40% of its fold-fit time, and the resilience stack
    costs ≤1.5× the bare bracket's wall clock. The elastic arm journals one
    structured "automl_rung" perfmodel row per rung task, so the learned
    model starts pricing candidate budgets and promotion quotas from real
    observations."""
    import shutil
    import tempfile

    from synapseml_tpu.automl import TuneHyperparameters
    from synapseml_tpu.automl.hyperparams import (DiscreteHyperParam,
                                                  HyperparamBuilder)
    from synapseml_tpu.automl.scheduler import plan_rungs
    from synapseml_tpu.core import perfmodel
    from synapseml_tpu.core.table import Table
    from synapseml_tpu.models import LightGBMRegressor

    rng = np.random.default_rng(7)
    X = rng.normal(size=(rows, cols)).astype(np.float32)
    y = (2.0 * X[:, 0] - X[:, 1] + 0.1 * rng.normal(size=rows)
         ).astype(np.float32)
    df = Table({"features": X, "label": y})

    fit_s = [0.0]

    class TimedRegressor(LightGBMRegressor):
        def _fit(self, d):
            t0 = time.perf_counter()
            try:
                return LightGBMRegressor._fit(self, d)
            finally:
                fit_s[0] += time.perf_counter() - t0

    space = (HyperparamBuilder()
             .addHyperparam("numLeaves", DiscreteHyperParam([3, 7, 15, 31]))
             .addHyperparam("learningRate",
                            DiscreteHyperParam([0.05, 0.1, 0.3]))
             .build())

    def run(halving_eta, ckpt="", **kw):
        fit_s[0] = 0.0
        t0 = time.perf_counter()
        m = TuneHyperparameters(
            model=TimedRegressor(numIterations=8), paramSpace=space,
            searchMode="grid", numFolds=folds, evaluationMetric="rmse",
            labelCol="label", parallelism=2, halvingEta=halving_eta,
            minResourceFolds=1, checkpointDir=ckpt, **kw).fit(df)
        return {"best_rmse": round(float(m.bestMetric), 5),
                "best_params": m.bestParams,
                "wall_s": round(time.perf_counter() - t0, 3),
                "fit_s": round(fit_s[0], 3)}

    exhaustive = run(0)
    halving = run(3)
    ck = tempfile.mkdtemp(prefix="bench_automl_ck_")
    rows_before = len(perfmodel.training_rows("automl_rung"))
    try:
        elastic = run(3, ckpt=ck, candidateBudgetSeconds=120.0,
                      perfJournal=True)
    finally:
        shutil.rmtree(ck, ignore_errors=True)
    rung_rows = perfmodel.training_rows("automl_rung")[rows_before:]
    per_rung = {}
    for r in rung_rows:
        per_rung[str(r.get("rung"))] = per_rung.get(str(r.get("rung")), 0) + 1

    regret = abs(halving["best_rmse"] - exhaustive["best_rmse"]) / max(
        abs(exhaustive["best_rmse"]), 1e-12)
    fit_ratio = halving["fit_s"] / max(exhaustive["fit_s"], 1e-9)
    ladder = plan_rungs(12, folds, eta=3, min_resource=1)
    spent, prev = 0, 0
    for r in ladder:
        spent += r.survivors * (r.resource - prev)
        prev = r.resource
    elastic_overhead = elastic["wall_s"] / max(halving["wall_s"], 1e-9)
    return {"metric": "automl_halving_fit_time_vs_exhaustive",
            "platform": "cpu",  # host-side scheduling economics, chip-free
            "value": round(fit_ratio, 3),
            "unit": ("x (halving fold-fit seconds / exhaustive fold-fit "
                     "seconds, 12-candidate LightGBM grid, 6-fold CV, "
                     "eta=3)"),
            "best_regret": round(regret, 5),
            "planned_fold_fits": {"halving": spent, "exhaustive": 12 * folds},
            "elastic_overhead_x": round(elastic_overhead, 3),
            "perf_rows_per_rung": per_rung,
            "arms": {"exhaustive": exhaustive, "halving": halving,
                     "halving_elastic": elastic},
            "guard": {"halving_best_within_2pct": regret <= 0.02,
                      "halving_fit_time_le_40pct": fit_ratio <= 0.40,
                      "elastic_overhead_le_1p5x": elastic_overhead <= 1.5,
                      "rung_rows_journaled": len(rung_rows) >= spent // 2}}


def _extra_workloads():
    bench_onnx_bf16 = functools.partial(bench_onnx_inference,
                                        precision="bfloat16")
    bench_onnx_bf16.__name__ = "bench_onnx_inference_bf16"
    # chip workloads FIRST: a time budget must spend itself on metrics only
    # the chip can produce before the CPU-mesh guards
    fns = (bench_gbdt_depthwise, bench_resnet50_train, bench_bert_finetune,
           bench_onnx_inference, bench_onnx_bf16, bench_onnx_bert,
           bench_flash_attention, bench_sparse_ingest,
           bench_serving, bench_serving_resnet,
           bench_serving_distributed, bench_fabric_scaling,
           bench_fabric_federation,
           bench_multitenant, bench_voting_ab,
           bench_distributed_gbdt_auto, bench_dl_sharded,
           bench_dl_overlap_pipeline, bench_dl_seq, bench_oocore_gbdt,
           bench_oocore_gbdt_mesh,
           bench_checkpoint_overhead, bench_elastic_recovery,
           bench_automl_elastic,
           bench_online_learning)
    return {f.__name__: f for f in fns}


# Workloads that run on the virtual 8-device CPU mesh whatever the machine
# holds: mesh workloads whose metrics are same-platform ratios or host-side
# recovery latencies, and the guards ci.sh asserts on the CPU. Their results
# say so (platform cpu, device_count 8). Every other workload needs the chip.
_CPU_WORKLOADS = frozenset({
    "bench_voting_ab", "bench_distributed_gbdt_auto", "bench_dl_sharded",
    "bench_dl_overlap_pipeline", "bench_dl_seq", "bench_elastic_recovery",
    "bench_oocore_gbdt_mesh", "bench_automl_elastic",
    "bench_checkpoint_overhead", "bench_oocore_gbdt",
    "bench_fabric_federation", "bench_multitenant"})


def _run_workload_subprocess(name: str, timeout_s: float) -> dict:
    """One workload in its OWN process, started by a parent that has not
    touched jax (a chip belongs to one process at a time), with a hard
    timeout. A child that exits non-zero, times out or prints no result is
    an error."""
    import subprocess

    try:
        r = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--only", name],
            capture_output=True, text=True, timeout=timeout_s,
            cwd=os.path.dirname(os.path.abspath(__file__)))
    except subprocess.TimeoutExpired:
        return {"metric": name,
                "error": f"timed out after {timeout_s:.0f}s"}
    if r.returncode == 0:
        for line in reversed(r.stdout.strip().splitlines()):
            line = line.strip()
            if line.startswith("{"):
                try:
                    return json.loads(line)
                except json.JSONDecodeError:
                    continue   # diagnostic noise; keep looking upward
    return {"metric": name,
            "error": f"rc={r.returncode}: {r.stderr[-400:]}"}


def _run_only(name: str) -> int:
    """Child mode: this process owns the device for one workload."""
    if name in _CPU_WORKLOADS:
        # must be set before the backend initializes
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=8").strip()
    stamp = _device_stamp()
    if name not in _CPU_WORKLOADS and stamp["platform"] != "tpu":
        print(f"bench.py: {name} needs a TPU and jax found "
              f"{stamp['platform']!r} ({stamp['device_kind']}); nothing "
              "measured", file=sys.stderr)
        return 1
    from synapseml_tpu.core.compile_cache import enable_compile_cache

    enable_compile_cache()
    if name == "bench_gbdt":
        # the primary runs first and alone, so the one-time journal
        # backfill cannot race another writer
        from synapseml_tpu.core.perfmodel import backfill_training_rows

        nb = backfill_training_rows()
        if nb:
            print(f"# backfilled {nb} perfmodel training rows from "
                  "docs/measurements.json", file=sys.stderr)
    result = {"bench_gbdt": bench_gbdt, **_extra_workloads()}[name]()
    result.update(stamp)
    record_measurement(result)
    print(json.dumps(result), flush=True)
    return 0


def main() -> int:
    if "--only" in sys.argv:
        return _run_only(sys.argv[sys.argv.index("--only") + 1])
    run_all = "--all" in sys.argv or os.environ.get("BENCH_ALL") == "1"
    primary = _run_workload_subprocess(
        "bench_gbdt", float(os.environ.get("BENCH_PRIMARY_TIMEOUT_S", 1500)))
    if "error" in primary:
        print(f"bench.py: primary workload failed, nothing to report: "
              f"{primary['error']}", file=sys.stderr)
        return 1
    extras = []
    budget_s = 1e9 if run_all else float(os.environ.get("BENCH_BUDGET_S", 900))
    per_workload_s = float(os.environ.get("BENCH_WORKLOAD_TIMEOUT_S", 900))
    t_start = time.perf_counter()
    for name in _extra_workloads():
        if time.perf_counter() - t_start > budget_s:
            break
        extras.append(_run_workload_subprocess(name, per_workload_s))
    out = dict(primary)
    out["extras"] = extras
    print(json.dumps(out))
    failed = [r["metric"] for r in extras if "error" in r]
    if failed:
        print(f"bench.py: workloads failed: {', '.join(failed)}",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
