"""chip_smoke.py — the main path, end to end, on the chip.

    python chip_smoke.py            one TPU chip: device, kernels, booster,
                                    trainer, server
    python chip_smoke.py --mesh4    one host with four chips: distributed
                                    booster, ZeRO trainer, ring and Ulysses

One process, the only one that touches JAX; data is generated from seeds; it
writes only under ``--out`` (the library itself keeps its compile cache where
``core/compile_cache.py`` says and builds its native helper in place). Each
phase prints one ``PHASE`` line (seconds, compile seconds apart from run
seconds, what it checked). The first failure in any phase ends the run
non-zero: nothing is caught, nothing is skipped. Anything but a TPU is a
failure. The last line of standard output is ``{"ok": true, "device":
{...}}`` with the device as JAX reports it.

``--rehearsal`` runs the same code at a tiny size on the CPU (Pallas in
interpret mode) to debug the script itself; it prints REHEARSAL, no result
line, and always exits non-zero.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time
import urllib.request

REHEARSAL_EXIT = 4
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class Phases:
    """Runs phases in order and accounts compile time to each: the sum of
    jax's backend-compile events (XLA + Mosaic compilation, or the
    persistent cache's retrieval when it hits) between start and end."""

    def __init__(self):
        import jax.monitoring as monitoring

        self.rows: dict = {}
        self._lock = threading.Lock()   # server threads compile too
        self._compile_s = 0.0
        self._counts = {"cache_hits": 0, "cache_misses": 0}
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event == _COMPILE_EVENT:
            with self._lock:
                self._compile_s += duration

    def _on_event(self, event, **_):
        key = event.rsplit("/", 1)[-1]
        if key in self._counts:
            with self._lock:
                self._counts[key] += 1

    def run(self, name, fn, *args):
        with self._lock:
            c0, n0 = self._compile_s, dict(self._counts)
        t0 = time.perf_counter()
        facts = fn(*args)
        seconds = time.perf_counter() - t0
        with self._lock:
            compile_s = self._compile_s - c0
            counts = {k: v - n0[k] for k, v in self._counts.items()}
        row = {"seconds": round(seconds, 2),
               "compile_seconds": round(compile_s, 2),
               "run_seconds": round(seconds - compile_s, 2), **counts, **facts}
        self.rows[name] = row
        print(f"PHASE {name} " + json.dumps(row), flush=True)
        return row


def _check(ok: bool, what: str):
    if not ok:
        raise AssertionError(what)   # not `assert`: must survive python -O


# --------------------------------------------------------------------------
# device
# --------------------------------------------------------------------------

def phase_device(rehearsal: bool, want_count: int) -> dict:
    import importlib.metadata as md

    import jax
    import jaxlib

    from synapseml_tpu import native
    from synapseml_tpu.core.compile_cache import enable_compile_cache
    from synapseml_tpu.gbdt import BoosterConfig
    from synapseml_tpu.ops import hist_kernel as hk

    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu" and not rehearsal:
        sys.exit(f"chip_smoke: no TPU — jax.devices()[0] is {d.platform!r} "
                 f"({d.device_kind}); nothing measured")
    if not rehearsal and len(devs) != want_count:
        sys.exit(f"chip_smoke: this mode needs {want_count} chip(s), "
                 f"jax sees {len(devs)}")
    cache_dir = enable_compile_cache()
    cfg = BoosterConfig()
    return {
        "platform": d.platform, "device_kind": d.device_kind,
        "count": len(devs),
        # list order is what parallel/mesh.make_mesh reshapes, with no
        # regard for the physical topology
        "device_order": [{"id": x.id, "coords": list(getattr(x, "coords", ()))}
                         for x in devs],
        "versions": {"python": sys.version.split()[0],
                     "jax": jax.__version__, "jaxlib": jaxlib.__version__,
                     "libtpu": md.version("libtpu")},
        "compile_cache_dir": cache_dir,
        "JAX_COMPILATION_CACHE_DIR":
            os.environ.get("JAX_COMPILATION_CACHE_DIR"),
        "gbdt_defaults": {
            "hist_chunk": hk.default_chunk(),
            "hist_pack": hk._pack_for(hk.pad_bins(cfg.max_bin) // 8,
                                      hk.FEATURE_BLOCK, None)},
        "native_available": native.available(),
    }


# --------------------------------------------------------------------------
# kernels: each Pallas kernel, compiled for the chip, against its reference
# --------------------------------------------------------------------------

def _max_err(got, want) -> float:
    import jax
    import numpy as np

    err = 0.0
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        _check(g.shape == w.shape, f"shape {g.shape} != {w.shape}")
        _check(bool(np.isfinite(g).all()), "non-finite kernel output")
        err = max(err, float(np.max(np.abs(g - w) / (1.0 + np.abs(w)))))
    return err


def phase_kernels(rehearsal: bool) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from synapseml_tpu.ops import attention_kernel as ak
    from synapseml_tpu.ops import hist_kernel as hk
    from synapseml_tpu.ops import partition_kernel as pk
    from synapseml_tpu.parallel.ring_attention import _block_attention

    interp = rehearsal            # the CPU can only interpret a TPU kernel
    C, B = (256, 256) if rehearsal else (2048, 256)
    out: dict = {}
    rng = np.random.default_rng(0)

    def close(name, got, want, tol):
        err = _max_err(got, want)
        _check(err <= tol, f"kernel {name}: max scaled error {err} > {tol}")
        out[name] = err

    for feats in (28, 136):
        FP, n = hk.features_padded(feats), 16 * C
        bT, g, h, m = hk._check_inputs(0, B, n, fp=FP)
        close(f"_hist_pallas[f={feats}]",
              hk._hist_pallas(bT, g, h, m, B, chunk=C, interpret=interp),
              hk._hist_xla(bT, g, h, m, B), 1e-4)
        start, length, size = 2 * C + C // 3, 4 * C + C // 2, 6 * C
        idx = np.arange(n)
        sel = jnp.asarray((idx >= start) & (idx < start + length),
                          jnp.float32)
        close(f"_hist_pallas_range[f={feats}]",
              hk._hist_pallas_range(bT, g, h, m, start, length, B, size,
                                    chunk=C, interpret=interp),
              hk._hist_xla(bT, g * sel, h * sel, m * sel, B), 1e-4)
        # the split step's stable partition of that window: exact
        part = pk._partition_check_inputs(0, B, n, FP, 2 * C, size, start,
                                          length)
        close(f"stable_partition_rows[f={feats}]",
              pk.partition_window(*part, B, C, interpret=interp),
              pk.partition_window_xla(*part), 0.0)
        # level kernel: 8 slots of 1-3 chunks, zero-valued tail padding
        caps = [2, 1, 3, 1, 2, 3, 1, 3]
        bT, g, h, m, starts, slot_row = hk._level_check_inputs(
            1, B, caps, C, fp=FP)
        close(f"_hist_pallas_level[f={feats}]",
              hk._hist_pallas_level(bT, g, h, m, starts, B, len(caps),
                                    chunk=C, interpret=interp),
              hk._hist_level_xla(bT, g, h, m, slot_row, B, len(caps)), 1e-4)

    # attention: block 128, D 64; references at full f32 matmul precision.
    # The kernels' own f32 matmuls run at the TPU default (a bf16 pass),
    # hence 2e-2 for f32 inputs too (measured 2.6e-3 to 9.2e-3 on a v5e)
    S, H = (256, 2) if rehearsal else (4096, 8)
    for tag, shape, dt, tol in (
            (f"bf16,S={S}", (1, S, H, 64), jnp.bfloat16, 2e-2),
            ("f32,S=300", (2, 300, 4, 64), jnp.float32, 2e-2)):
        q, k, v = (jnp.asarray(rng.normal(size=shape), dt) for _ in range(3))
        for causal in (False, True):
            got = ak._flash_forward(q, k, v, causal, 0.125, 128, 128, interp)
            with jax.default_matmul_precision("highest"):
                want = ak._xla_fallback(*(x.astype(jnp.float32)
                                          for x in (q, k, v)),
                                        causal, 0.125, 128)
            close(f"_flash_forward[{tag},causal={causal}]", got, want, tol)

    # the ring's per-step kernel, on carried state from a previous block
    Sb, Hb = (256, 2) if rehearsal else (2048, 12)
    for tag, sq, sk in ((f"S={Sb}", Sb, Sb), ("Sq=140", 140, 128)):
        q = jnp.asarray(rng.normal(size=(1, sq, Hb, 64)), jnp.float32)
        k1, v1, k2, v2 = (jnp.asarray(rng.normal(size=(1, sk, Hb, 64)),
                                      jnp.float32) for _ in range(4))
        m0 = jnp.full((1, Hb, sq), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((1, Hb, sq), jnp.float32)
        o0 = jnp.zeros((1, sq, Hb, 64), jnp.float32)
        for causal in (False, True):
            with jax.default_matmul_precision("highest"):
                st = _block_attention(q, k1, v1, m0, l0, o0, sk, 0, causal,
                                      0.125)
                want = _block_attention(q, k2, v2, *st, sk, sk, causal, 0.125)
            got = ak.flash_attention_block(
                q, k2, v2, *st, q_offset=sk, k_offset=sk, causal=causal,
                scale=0.125, interpret=interp)
            close(f"flash_attention_block[{tag},causal={causal}]",
                  ak.comparable_state(*got), ak.comparable_state(*want),
                  1e-2)
    return {"max_scaled_error": out}


# --------------------------------------------------------------------------
# booster / trainer / server
# --------------------------------------------------------------------------

def _higgs_like(n: int, seed: int):
    """Seeded dense table at HIGGS width (28 float features, binary label)."""
    import numpy as np

    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, 28)).astype(np.float32)
    margin = (X[:, 0] * X[:, 1] + 0.5 * X[:, 2] - 0.3 * np.abs(X[:, 3])
              + 0.5 * rng.normal(size=n).astype(np.float32))
    return X, (margin > 0).astype(np.float32)


def _mosaic_calls_in(ir_dir: str, module: str) -> int:
    """Mosaic custom calls in the module jax handed to the compiler."""
    n = 0
    for name in os.listdir(ir_dir):
        if module in name:
            with open(os.path.join(ir_dir, name)) as f:
                n += f.read().count("tpu_custom_call")
        os.remove(os.path.join(ir_dir, name))
    return n


AUC_FLOOR = 0.80   # measured 0.838 on a v5e at this seed/size (CHANGES.md, PR 21)


def phase_booster(rehearsal: bool, out_dir: str, state: dict) -> dict:
    import jax
    import numpy as np

    from synapseml_tpu.core import Table
    from synapseml_tpu.gbdt import METRICS
    from synapseml_tpu.models import LightGBMClassifier

    n, n_test = (20_000, 2_000) if rehearsal else (1_000_000, 100_000)
    X, y = _higgs_like(n + n_test, seed=21)
    ir_dir = os.path.join(out_dir, "ir")
    os.makedirs(ir_dir, exist_ok=True)
    jax.config.update("jax_dump_ir_to", ir_dir)
    t0 = time.perf_counter()
    model = LightGBMClassifier(numIterations=10).fit(
        Table({"features": X[:n], "label": y[:n]}))
    fit_s = time.perf_counter() - t0
    jax.config.update("jax_dump_ir_to", None)
    mosaic = _mosaic_calls_in(ir_dir, "jit_run_scan")
    if not rehearsal:
        _check(mosaic > 0, "no Mosaic custom call in the compiled training "
                           "program (jit_run_scan)")
    t0 = time.perf_counter()
    out = model.transform(Table({"features": X[n:]}))
    transform_s = time.perf_counter() - t0
    prob = np.asarray(out["probability"])
    _check(prob.shape == (n_test, 2) and bool(np.isfinite(prob).all()),
           f"probability column: shape {prob.shape}, finite "
           f"{np.isfinite(prob).all()}")
    auc = float(METRICS["auc"](y[n:], prob[:, 1]))
    _check(rehearsal or auc > AUC_FLOOR, f"AUC {auc} <= floor {AUC_FLOOR}")
    cfg = model.booster.config
    state.update(model=model, X_test=X[n:])
    return {"rows": n, "features": 28, "iterations": 10,
            "num_leaves": cfg.num_leaves, "max_bin": cfg.max_bin,
            "trees": len(model.booster.trees), "auc": round(auc, 4),
            "fit_seconds": round(fit_s, 2),
            "transform_rows": n_test,
            "transform_seconds": round(transform_s, 2),
            "mosaic_custom_calls_in_run_scan": mosaic}


def phase_trainer(rehearsal: bool) -> dict:
    import numpy as np

    from synapseml_tpu.core import Table
    from synapseml_tpu.dl import DeepVisionClassifier

    backbone, size, batch = (("resnet18", 32, 8) if rehearsal
                             else ("resnet50", 224, 64))
    n = 4 * batch                                 # four FlaxTrainer steps
    rng = np.random.default_rng(22)
    X = rng.uniform(size=(n, size, size, 3)).astype(np.float32)
    y = rng.integers(0, 10, size=n).astype(np.float32)
    t0 = time.perf_counter()
    model = DeepVisionClassifier(
        backbone=backbone, precision="bfloat16", batchSize=batch,
        additionalLayersToTrain=-1, maxEpochs=1).fit(
            Table({"image": X, "label": y}))
    fit_s = time.perf_counter() - t0
    hist = model.trainer.history
    _check(len(hist) == 1 and hist[0]["steps"] == 4
           and bool(np.isfinite(hist[0]["loss"])),
           f"trainer history {hist}")
    prob = np.asarray(model.transform(Table({"image": X[:batch]}))
                      ["probability"])
    _check(prob.shape[0] == batch and bool(np.isfinite(prob).all()),
           f"probability: shape {prob.shape}")
    return {"backbone": backbone, "image": size, "batch": batch,
            "steps": hist[0]["steps"], "mean_loss": round(hist[0]["loss"], 4),
            "fit_seconds": round(fit_s, 2),
            "epoch_seconds": round(hist[0]["seconds"], 2)}


def phase_server(rehearsal: bool, state: dict) -> dict:
    import jax
    import numpy as np

    from synapseml_tpu.core import Table
    from synapseml_tpu.io.serving import ServingServer
    from synapseml_tpu.io.serving_main import build_handler

    model, X = state["model"], state["X_test"]
    forest_platforms = {d.platform
                        for a in jax.tree.leaves(model.booster.forest())
                        if isinstance(a, jax.Array) for d in a.devices()}
    _check(rehearsal or forest_platforms == {"tpu"},
           f"forest arrays live on {forest_platforms}, not the TPU")
    n_clients, per_client = 4, 8
    want = np.asarray(model.transform(
        Table({"features": X[:n_clients * per_client]}))["probability"])
    server = ServingServer(build_handler(model, "probability"), port=0,
                           max_batch_size=8, max_batch_latency=0.005)
    server.start()
    replies: dict = {}

    def client(c: int):
        for i in range(c * per_client, (c + 1) * per_client):
            req = urllib.request.Request(
                server.url, method="POST",
                data=json.dumps({"features": X[i].tolist()}).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=120) as r:
                replies[i] = json.loads(r.read())

    try:
        threads = [threading.Thread(target=client, args=(c,))
                   for c in range(n_clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=600)
        _check(not any(t.is_alive() for t in threads), "a client hung")
    finally:
        server.stop()
    _check(len(replies) == n_clients * per_client,
           f"{len(replies)} replies of {n_clients * per_client}")
    got = np.asarray([replies[i] for i in range(len(replies))], np.float64)
    err = float(np.max(np.abs(got - want)))
    _check(err <= 1e-6, f"served reply differs from model.transform by {err}")
    return {"requests": len(replies), "clients": n_clients,
            "max_abs_diff_vs_transform": err,
            "forest_platforms": sorted(forest_platforms)}


# --------------------------------------------------------------------------
# four chips: distributed booster, ZeRO trainer, ring and Ulysses attention
# --------------------------------------------------------------------------

def _watch_row_shards(n_dev: int, stop: threading.Event, seen: dict):
    """While training runs, note the largest live array sharded over all
    ``n_dev`` devices and every device's bytes in use."""
    import jax

    while not stop.wait(0.5):
        for a in jax.live_arrays():
            devs = {s.device.id for s in a.addressable_shards}
            if len(devs) == n_dev and not a.is_fully_replicated \
                    and a.nbytes > seen.get("nbytes", 0):
                seen.update(nbytes=a.nbytes, shape=list(a.shape),
                            dtype=str(a.dtype), devices=sorted(devs),
                            shard_shape=list(
                                a.addressable_shards[0].data.shape))
        for d in jax.devices()[:n_dev]:
            used = (d.memory_stats() or {}).get("bytes_in_use", 0)
            seen.setdefault("bytes_in_use", {})
            seen["bytes_in_use"][d.id] = max(
                seen["bytes_in_use"].get(d.id, 0), used)


def phase_booster4(rehearsal: bool) -> dict:
    import numpy as np

    from synapseml_tpu.gbdt import BoosterConfig, train_booster
    from synapseml_tpu.parallel import make_mesh

    n, n_test = (20_000, 2_000) if rehearsal else (1_000_000, 100_000)
    X, y = _higgs_like(n + n_test, seed=21)
    mesh = make_mesh({"data": 4})
    stop, seen = threading.Event(), {}
    watcher = threading.Thread(target=_watch_row_shards,
                               args=(4, stop, seen), daemon=True)
    watcher.start()
    try:
        t0 = time.perf_counter()
        # tree_learner="data" is what the estimator passes by default
        # (parallelism=data_parallel); "auto" could route off row sharding
        b4 = train_booster(X[:n], y[:n], BoosterConfig(
            objective="binary", num_iterations=10, tree_learner="data"),
            mesh=mesh)
        p4 = np.asarray(b4.predict(X[n:]))
        fit4_s = time.perf_counter() - t0
    finally:
        stop.set()
        watcher.join(timeout=30)
    t0 = time.perf_counter()
    b1 = train_booster(X[:n], y[:n], BoosterConfig(
        objective="binary", num_iterations=10))
    p1 = np.asarray(b1.predict(X[n:]))
    fit1_s = time.perf_counter() - t0
    diff = float(np.max(np.abs(p4 - p1)))
    _check(diff <= 5e-3, f"4-chip vs 1-chip predictions differ by {diff}")
    _check(seen.get("devices") == sorted(d.id for d in mesh.devices.flat),
           f"no row-sharded array seen on all four devices: {seen}")
    used = seen.get("bytes_in_use", {})
    _check(rehearsal or (len(used) == 4 and all(v > 0 for v in used.values())),
           f"bytes_in_use per device: {used}")
    return {"rows": n, "mesh": dict(mesh.shape),
            "mesh_device_ids": [d.id for d in mesh.devices.flat],
            "max_abs_pred_diff_vs_one_chip": diff,
            "routing": b4.metadata.get("routing"),
            "fit4_seconds": round(fit4_s, 2), "fit1_seconds": round(fit1_s, 2),
            "largest_row_sharded_array": {k: v for k, v in seen.items()
                                          if k != "bytes_in_use"},
            "peak_bytes_in_use_seen": used}


def phase_trainer4(rehearsal: bool) -> dict:
    import jax.numpy as jnp
    import numpy as np

    from synapseml_tpu.dl import FlaxTrainer, TrainConfig, make_backbone
    from synapseml_tpu.parallel import make_mesh

    backbone, size, batch = (("resnet18", 32, 8) if rehearsal
                             else ("resnet50", 224, 64))
    rng = np.random.default_rng(22)
    X = rng.uniform(size=(4 * batch, size, size, 3)).astype(np.float32)
    y = rng.integers(0, 10, size=4 * batch)
    tr = FlaxTrainer(
        make_backbone(backbone, 10, dtype=jnp.bfloat16),
        TrainConfig(batch_size=batch, max_epochs=1, param_sharding="zero",
                    compute_dtype="bfloat16"),
        mesh=make_mesh({"data": 4}))
    tr.fit(X, y)
    ep = tr.history[0]
    _check(ep["steps"] == 4 and bool(np.isfinite(ep["loss"])),
           f"ZeRO trainer history {tr.history}")
    logits = np.asarray(tr.predict_logits(X[:batch]))
    _check(bool(np.isfinite(logits).all()), "non-finite logits after ZeRO fit")
    return {"backbone": backbone, "param_sharding": "zero",
            "steps": ep["steps"], "mean_loss": round(ep["loss"], 4),
            "epoch_seconds": round(ep["seconds"], 2),
            "state_bytes_per_device":
                tr.stats.get("state_bytes_per_device")}


def phase_attention4(rehearsal: bool) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from synapseml_tpu.parallel import make_mesh
    from synapseml_tpu.parallel.ring_attention import (attention_reference,
                                                       ring_self_attention)
    from synapseml_tpu.parallel.ulysses import ulysses_self_attention

    S, H = (512, 4) if rehearsal else (8192, 12)
    mesh = make_mesh({"data": 1, "seq": 4})
    rng = np.random.default_rng(23)
    sh = NamedSharding(mesh, P("data", "seq", None, None))
    q, k, v = (jax.device_put(jnp.asarray(rng.normal(size=(1, S, H, 64)),
                                          jnp.bfloat16), sh)
               for _ in range(3))
    out = {}
    # rehearsal: the CPU can only interpret the kernels
    kw = dict(use_flash=True, flash_interpret=True) if rehearsal else {}
    for causal in (False, True):
        with jax.default_matmul_precision("highest"):
            want = attention_reference(*(np.asarray(x, np.float32)
                                         for x in (q, k, v)), causal=causal)
        for name, fn in (("ring", ring_self_attention),
                         ("ulysses", ulysses_self_attention)):
            got = fn(q, k, v, mesh, causal=causal, **kw)
            _check(got.sharding.is_equivalent_to(sh, got.ndim),
                   f"{name} output sharding {got.sharding}")
            err = _max_err(got, want)
            _check(err <= 2e-2, f"{name} causal={causal}: error {err}")
            out[f"{name}[causal={causal}]"] = err
    return {"S": S, "heads": H, "D": 64, "dtype": "bfloat16",
            "mesh": dict(mesh.shape), "max_scaled_error": out}


# --------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--mesh4", action="store_true",
                    help="the four-chip phases (needs a four-chip host)")
    ap.add_argument("--rehearsal", action="store_true",
                    help="tiny CPU run to debug this script; never a pass")
    ap.add_argument("--out", default=os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "chiprun_out",
        "chip_smoke"))
    args = ap.parse_args(argv)
    if args.rehearsal:
        print("REHEARSAL: CPU, tiny sizes, interpreted kernels — not a result",
              flush=True)
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=4").strip()
    os.makedirs(args.out, exist_ok=True)
    # the library's probe cache and perf-model journal default to docs/;
    # this run writes only under --out (tests/conftest.py does the same)
    os.environ["SYNAPSEML_TPU_PROBE_CACHE"] = os.path.join(
        args.out, "probe_cache.json")
    os.environ["SYNAPSEML_TPU_PERF_ROWS"] = os.path.join(
        args.out, "perf_rows.jsonl")

    t_start = time.perf_counter()
    phases = Phases()
    r = args.rehearsal
    dev = phases.run("device", phase_device, r, 4 if args.mesh4 else 1)
    print("device: platform=%s device_kind=%r count=%d " % (
        dev["platform"], dev["device_kind"], dev["count"])
        + " ".join(f"{k}={v}" for k, v in dev["versions"].items()),
        flush=True)
    if args.mesh4:
        phases.run("booster4", phase_booster4, r)
        phases.run("trainer4", phase_trainer4, r)
        phases.run("attention4", phase_attention4, r)
    else:
        state: dict = {}
        phases.run("kernels", phase_kernels, r)
        phases.run("booster", phase_booster, r, args.out, state)
        phases.run("trainer", phase_trainer, r)
        phases.run("server", phase_server, r, state)
    summary = {"mode": "mesh4" if args.mesh4 else "one_chip",
               "total_seconds": round(time.perf_counter() - t_start, 2),
               "compile_seconds": round(sum(
                   p["compile_seconds"] for p in phases.rows.values()), 2),
               "phases": phases.rows, "claim": None}
    with open(os.path.join(args.out, "summary_%s.json" % summary["mode"]),
              "w") as f:
        json.dump(summary, f, indent=1)
    print("SUMMARY " + json.dumps({k: v for k, v in summary.items()
                                   if k != "phases"}), flush=True)
    if r:
        print("REHEARSAL finished: every phase ran; exit is non-zero by "
              "design", flush=True)
        return REHEARSAL_EXIT
    print(json.dumps({"ok": True, "device": {
        "platform": dev["platform"], "kind": dev["device_kind"],
        "count": dev["count"]}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
