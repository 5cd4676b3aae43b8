"""The span record (core/logging.InstrumentationMeasures) and its two users:
the booster fit (gbdt/boosting.train_booster, models/gbdt) and the trainer
loop (dl/trainer.FlaxTrainer, its step hook). A span closes when its work is
done; what the program reports is what the benchmark's per-layer metrics
read (benchmark/metrics/booster_*_ms.py, booster_unaccounted_share.py)."""

import hashlib
import json
import logging
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from synapseml_tpu.core.logging import InstrumentationMeasures, SpanRecord
from synapseml_tpu.ops.quantize import compute_bin_mapper

# sha256 of train_booster's model string on _booster_data(), 5 iterations,
# seed 7, recorded from the parent commit (3f00fef) before the spans moved
PARENT_MODEL_SHA = \
    "d9e6991e469cba03ffed0b350df3ceff2145dafcb138502f4ee22bf3f56de44d"
# FlaxTrainer on _tiny_encoder(), batch 8, 2 epochs, adamw, seed 3, at the
# parent commit: epoch losses and the sha256 of the parameter leaves' bytes
PARENT_EPOCH_LOSSES = [0.7821658253669739, 0.5635910704731941]
PARENT_PARAMS_SHA = \
    "09162ac9c38d168463f45ebd1cdd728750cd2bf295ef1677399c2b7cfc5065f6"

BOOSTER_SPANS = {
    "referenceDataset", "dataPreparation", "dataPreparation/copyToDevice",
    "dataPreparation/binning", "objectiveSetup", "trainingIterations",
    "trainingIterations/scanRun", "trainingIterations/treesReadback",
    "modelAssembly"}


def _top_level(report):
    return {k: v for k, v in report.items()
            if "/" not in k and not k.startswith("count:")}


# --------------------------------------------------------------------------
# the record
# --------------------------------------------------------------------------

def test_nesting_gives_parent_child_keys_and_records():
    m = InstrumentationMeasures()
    with m.span("outer"):
        with m.span("inner"):
            with m.span("leaf"):
                pass
        with m.span("inner"):
            pass
    with m.span("alone"):
        pass
    m.count("things", 3)
    m.count("things")
    r = m.report()
    assert set(r) == {"outer", "outer/inner", "inner/leaf", "alone",
                      "count:things"}
    assert r["count:things"] == 4
    assert m.occurrences == {"outer": 1, "outer/inner": 2, "inner/leaf": 1,
                             "alone": 1}
    # records are filed as spans close: children before their parent
    assert [(x.name, x.parent) for x in m.records] == [
        ("leaf", "inner"), ("inner", "outer"), ("inner", "outer"),
        ("outer", None), ("alone", None)]
    assert all(isinstance(x, SpanRecord) and x.end_ns >= x.start_ns
               for x in m.records)
    by = {(x.name, i): x for i, x in enumerate(m.records)}
    outer, leaf = by[("outer", 3)], by[("leaf", 0)]
    assert outer.start_ns <= leaf.start_ns and leaf.end_ns <= outer.end_ns


def test_self_seconds_takes_the_children_out():
    m = InstrumentationMeasures()
    with m.span("fit"):
        time.sleep(0.02)
        with m.span("part"):
            time.sleep(0.03)
        with m.span("part"):
            time.sleep(0.01)
    r, own = m.report(), m.self_seconds()
    assert set(own) == {"fit", "fit/part"}
    assert own["fit/part"] == pytest.approx(r["fit/part"])
    assert own["fit"] == pytest.approx(r["fit"] - r["fit/part"], abs=1e-6)
    assert 0.02 <= own["fit"] < r["fit"] and r["fit/part"] >= 0.04


def test_records_are_bounded_sums_are_not():
    m = InstrumentationMeasures()
    n = m.MAX_RECORDS + 904
    for i in range(n):
        with m.span("step", step_num=i):
            pass
    assert m.MAX_RECORDS == 4096 and len(m.records) == 4096
    assert m.occurrences["step"] == n
    newest = sum(x.end_ns - x.start_ns for x in m.records) / 1e9
    assert m.report()["step"] > newest > 0
    starts = [x.start_ns for x in m.records]
    assert starts == sorted(starts)          # the newest, in order


def test_report_keeps_the_old_keys_and_meaning():
    """What PR 24's readers saw: seconds summed over a name's occurrences
    under the bare name, counters under ``count:<name>``."""
    m = InstrumentationMeasures()
    for name in ("referenceDataset", "dataPreparation", "trainingIterations",
                 "checkpointSave", "trainingIterations"):
        with m.span(name):
            time.sleep(0.002)
    m.count("iterations", 8)
    r = m.report()
    assert set(r) == {"referenceDataset", "dataPreparation",
                      "trainingIterations", "checkpointSave",
                      "count:iterations"}
    assert r["count:iterations"] == 8
    assert all(isinstance(r[k], float) for k in _top_level(r))
    assert r["trainingIterations"] >= 0.004 > r["checkpointSave"] >= 0.002
    assert not hasattr(m, "merge")
    import synapseml_tpu.core as core
    assert not hasattr(core, "StopWatch")


def test_discarded_span_leaves_nothing_and_an_error_closes_the_span():
    m = InstrumentationMeasures()
    with m.span("epoch"):
        with m.span("step") as s:
            with m.span("wait") as w:
                w.discard()
            s.discard()
        with pytest.raises(RuntimeError):
            with m.span("step"):
                raise RuntimeError("boom")
        with m.span("after"):
            pass
    assert set(m.report()) == {"epoch", "epoch/step", "epoch/after"}
    assert m.occurrences["epoch/step"] == 1
    assert m.self_seconds()["epoch"] <= m.report()["epoch"]


def _host_events(trace_dir):
    from jax.profiler import ProfileData
    import glob
    import os

    path = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True), key=os.path.getmtime)[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                out.extend((e.name, e.start_ns, e.duration_ns)
                           for e in line.events)
    return out


def test_every_record_has_its_annotation_in_a_profiler_session(tmp_path):
    """One context manager opens the record and the TraceAnnotation, so the
    spans lie on the trace's clock: same names, same durations, same
    nesting."""
    m = InstrumentationMeasures()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with m.span("spans.fit"):
            with m.span("spans.prepare"):
                jax.block_until_ready(jnp.ones((256, 256)) @ jnp.ones((256, 256)))
            for i in range(3):
                with m.span("spans.step", step_num=i):
                    with m.span("spans.sync"):
                        time.sleep(0.004)
    finally:
        jax.profiler.stop_trace()
    events = _host_events(str(tmp_path))
    ours = [e for e in events if e[0].startswith("spans.")]
    assert len(ours) == len(m.records) == 8
    pairs = []           # (record, its annotation): the i-th of a name each
    for name in {r.name for r in m.records}:
        recs = sorted((r for r in m.records if r.name == name),
                      key=lambda r: r.start_ns)
        anns = sorted((e for e in ours if e[0] == name), key=lambda e: e[1])
        assert len(recs) == len(anns), name
        pairs.extend(zip(recs, anns))
    # one clock offset for all of them, and durations that agree within 1 ms
    offsets = [ann[1] - rec.start_ns for rec, ann in pairs]
    assert max(offsets) - min(offsets) < 1e6
    for rec, ann in pairs:
        assert abs(ann[2] - (rec.end_ns - rec.start_ns)) < 1e6, (rec, ann)
        if rec.parent is not None:
            # nested on the trace as in the record: inside an annotation of
            # the parent's name
            assert any(p[0] == rec.parent and p[1] <= ann[1]
                       and ann[1] + ann[2] <= p[1] + p[2] for p in ours), rec


def test_span_cost_with_no_profiler_session():
    """Four spans a trainer step (step, dataWait, dispatch, lossSync). The
    number reported in PERF.md is measured the same way; this only guards
    the order of magnitude on a loaded box."""
    m = InstrumentationMeasures()
    n = 2000
    t0 = time.perf_counter()
    for i in range(n):
        with m.span("trainer.step", step_num=i):
            with m.span("dataWait"):
                pass
            with m.span("dispatch"):
                pass
            with m.span("lossSync"):
                pass
    per_step_us = (time.perf_counter() - t0) / n * 1e6
    assert per_step_us < 200, per_step_us
    assert m.occurrences["trainer.step/lossSync"] == n


# --------------------------------------------------------------------------
# the booster fit
# --------------------------------------------------------------------------

def _booster_data(seed=1234, n=20000, f=12):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, f)).astype(np.float32)
    logit = X[:, 0] - 0.5 * X[:, 1] * X[:, 2] + 0.25 * np.sin(3 * X[:, 3])
    y = (logit + rng.normal(scale=0.5, size=n) > 0).astype(np.float32)
    return X, y


@pytest.fixture(scope="module")
def booster_fit():
    """(report, self seconds, records, wall seconds, model string) of the
    second of two identical fits, so that no compilation is inside it."""
    from synapseml_tpu.gbdt import BoosterConfig, train_booster

    X, y = _booster_data()
    for _ in range(2):
        m = InstrumentationMeasures()
        t0 = time.perf_counter()
        booster = train_booster(X, y, BoosterConfig(num_iterations=5, seed=7),
                                measures=m)
        wall = time.perf_counter() - t0
    return (m.report(), m.self_seconds(), list(m.records), wall,
            booster.model_string())


def test_booster_fit_names_its_spans(booster_fit):
    report, own, records, _, _ = booster_fit
    assert BOOSTER_SPANS <= set(report)
    assert report["count:iterations"] == 5
    assert "dataPreparation/shardRows" not in report       # no mesh
    assert report["dataPreparation"] >= (
        report["dataPreparation/copyToDevice"]
        + report["dataPreparation/binning"])
    assert report["trainingIterations"] >= (
        report["trainingIterations/scanRun"]
        + report["trainingIterations/treesReadback"])
    # nothing but its two children happens inside dataPreparation
    assert own["dataPreparation"] < 0.005
    parents = {r.name: r.parent for r in records}
    assert parents["binning"] == "dataPreparation"
    assert parents["scanRun"] == "trainingIterations"
    assert parents["referenceDataset"] is None


def test_booster_spans_cover_the_call(booster_fit):
    report, _, _, wall, _ = booster_fit
    covered = sum(_top_level(report).values())
    assert covered <= wall
    assert (wall - covered) / wall < 0.05, (wall, report)


def test_booster_model_is_the_parents(booster_fit):
    model_string = booster_fit[4]
    assert hashlib.sha256(model_string.encode()).hexdigest() == \
        PARENT_MODEL_SHA


def test_binning_spans_close_after_the_wait(monkeypatch):
    """A span may not close at dispatch: ``copyToDevice`` and ``binning``
    each wait for what they made before they close."""
    import types

    from synapseml_tpu.gbdt import boosting

    class Lazy:
        def __init__(self, made_by):
            self.made_by, self.waited = made_by, False

        def block_until_ready(self):
            self.waited = True
            return self

    made, waited_at_close = {}, {}

    class Spy(InstrumentationMeasures):
        def _close(self, span, end_ns, parent):
            waited_at_close[span.name] = made[span.name].waited
            super()._close(span, end_ns, parent)

    def make(name):
        def fn(*args):
            made[name] = Lazy(name)
            return made[name]
        return fn

    monkeypatch.setattr(boosting, "jnp", types.SimpleNamespace(
        asarray=make("copyToDevice"), float32=np.float32))
    monkeypatch.setattr(boosting, "apply_bins", make("binning"))
    m = Spy()
    X = np.zeros((4, 2), np.float32)
    out = boosting._bin_on_device(compute_bin_mapper(X), X, m)
    assert out is made["binning"]
    assert waited_at_close == {"copyToDevice": True, "binning": True}
    assert [r.name for r in m.records] == ["copyToDevice", "binning"]


@pytest.mark.parametrize("max_bin,key", [(255, "binnedValuesCompare"),
                                         (2048, "binnedValuesSearch")])
def test_fit_counts_the_values_binned_by_each_path(max_bin, key):
    """The path follows the mapper's boundary count (``max_bin`` - 1), and
    the fit's record says how many values went down it."""
    from synapseml_tpu.gbdt import BoosterConfig, train_booster

    X, y = _booster_data(n=1500)
    m = InstrumentationMeasures()
    train_booster(X, y, BoosterConfig(num_iterations=2, seed=7,
                                      max_bin=max_bin), measures=m)
    counted = {k: v for k, v in m.report().items()
               if k.startswith("count:binnedValues")}
    assert counted == {f"count:{key}": X.shape[0] * X.shape[1]}


def test_booster_on_a_mesh_times_the_row_placement():
    from synapseml_tpu.gbdt import BoosterConfig, train_booster
    from synapseml_tpu.parallel.mesh import make_mesh

    X, y = _booster_data(n=4000)
    m = InstrumentationMeasures()
    train_booster(X, y, BoosterConfig(num_iterations=2, seed=7),
                  mesh=make_mesh({"data": 4}, devices=jax.devices()[:4]),
                  measures=m)
    report = m.report()
    assert BOOSTER_SPANS | {"dataPreparation/shardRows"} <= set(report)
    assert report["dataPreparation"] >= (
        report["dataPreparation/copyToDevice"]
        + report["dataPreparation/binning"]
        + report["dataPreparation/shardRows"])


def test_fused_checkpoint_save_is_a_child_of_the_loop(tmp_path):
    from synapseml_tpu.gbdt import BoosterConfig, train_booster

    X, y = _booster_data(n=4000)
    m = InstrumentationMeasures()
    train_booster(X, y, BoosterConfig(num_iterations=4, seed=7), measures=m,
                  checkpoint_store=str(tmp_path / "ck"), checkpoint_every=2)
    report = m.report()
    assert m.occurrences["trainingIterations/checkpointSave"] == 2
    assert m.occurrences["trainingIterations/scanRun"] == 2
    assert report["count:iterations"] == 4
    assert m.self_seconds()["trainingIterations"] < report["trainingIterations"]


def test_streamed_fit_uses_the_same_loop_name():
    from synapseml_tpu.gbdt import BoosterConfig
    from synapseml_tpu.gbdt.stream import (StreamedDataset,
                                           train_booster_streamed)

    X, y = _booster_data(n=1024, f=6)
    m = InstrumentationMeasures()
    train_booster_streamed(
        StreamedDataset.from_arrays(X, y, chunk_rows=256),
        BoosterConfig(num_iterations=2, seed=7, num_leaves=7), measures=m)
    assert "trainingIterations" in m.report()
    assert "trainingIteration" not in m.report()
    assert "streamIngest" in m.report()


class _Catch(logging.Handler):
    def __init__(self, method):
        super().__init__(level=logging.DEBUG)
        self.method, self.payloads = method, []

    def emit(self, record):
        try:
            payload = json.loads(record.getMessage())
        except ValueError:
            return
        if payload.get("method") == self.method:
            self.payloads.append(payload)


@pytest.fixture
def training_measures():
    log = logging.getLogger("synapseml_tpu")
    handler, level = _Catch("trainingMeasures"), log.level
    log.addHandler(handler)
    log.setLevel(logging.DEBUG)
    try:
        yield handler.payloads
    finally:
        log.removeHandler(handler)
        log.setLevel(level)


def test_estimator_logs_one_record_with_its_own_span(training_measures):
    from synapseml_tpu.core import Table
    from synapseml_tpu.models import LightGBMClassifier, LightGBMRegressor

    X, y = _booster_data(n=4000)
    table = Table({"features": X, "label": y})
    t0 = time.perf_counter()
    LightGBMClassifier(numIterations=3).fit(table)
    wall = time.perf_counter() - t0
    LightGBMRegressor(numIterations=3).fit(table)
    assert len(training_measures) == 2
    for record in training_measures:
        assert BOOSTER_SPANS | {"tablePreparation"} <= set(record)
        assert record["count:iterations"] == 3
    spans = {k: v for k, v in training_measures[0].items()
             if isinstance(v, (int, float))}
    assert sum(_top_level(spans).values()) <= wall


# --------------------------------------------------------------------------
# the trainer
# --------------------------------------------------------------------------

def _tiny_encoder():
    from synapseml_tpu.dl.text import TransformerEncoder

    rng = np.random.default_rng(5)
    ids = rng.integers(1, 64, size=(64, 8)).astype(np.int32)
    y = (ids[:, 0] % 2).astype(np.int64)
    model = TransformerEncoder(vocab_size=64, num_layers=2, num_heads=2,
                               hidden=16, max_len=8, num_classes=2,
                               dtype=jnp.float32)
    return model, ids, y


def _tiny_fit(step_fn=None, **cfg):
    from synapseml_tpu.dl.trainer import FlaxTrainer, TrainConfig

    model, ids, y = _tiny_encoder()
    settings = dict(batch_size=8, max_epochs=2, optimizer="adamw", seed=3)
    settings.update(cfg)
    trainer = FlaxTrainer(model, TrainConfig(**settings))
    return trainer.fit(ids, y, step_fn=step_fn)


def _params_sha(tree):
    h = hashlib.sha256()
    for leaf in jax.tree.leaves(tree):
        h.update(np.asarray(leaf).tobytes())
    return h.hexdigest()


@pytest.fixture(scope="module")
def plain_fit():
    return _tiny_fit()


def test_trainer_counts_and_times_its_steps(plain_fit):
    tr = plain_fit
    report, m = tr.stats["measures"], tr.measures
    assert report == m.report()
    assert report["count:steps"] == 16 == sum(e["steps"] for e in tr.history)
    assert report["count:samples"] == 16 * 8
    assert "count:skipped" not in report and "count:rolledBack" not in report
    assert m.occurrences["trainer.epoch"] == 2
    assert m.occurrences["trainer.epoch/trainer.step"] == 16
    parts = (report["trainer.step/dataWait"] + report["trainer.step/dispatch"]
             + report["trainer.step/lossSync"])
    assert 0 < parts <= report["trainer.epoch/trainer.step"] \
        <= report["trainer.epoch"]
    assert "trainer.step/stepFn" not in report
    for entry in tr.history:
        assert entry["step_ms_p50"] > 0
        assert 0 <= entry["data_wait_s"] + entry["dispatch_s"] \
            + entry["loss_sync_s"] <= entry["seconds"]
    assert sum(e["dispatch_s"] for e in tr.history) == pytest.approx(
        report["trainer.step/dispatch"])


def test_fit_without_a_hook_is_the_parents(plain_fit):
    tr = plain_fit
    assert [e["loss"] for e in tr.history] == PARENT_EPOCH_LOSSES
    assert _params_sha(tr.params) == PARENT_PARAMS_SHA
    assert tr.stats["measures"]["count:compiles"] == 1
    assert tr.stats["compile_steps"] == [0]


def test_hook_sees_every_accepted_step_once(plain_fit):
    seen = []
    tr = _tiny_fit(lambda i, loss, p, bs, o: seen.append((i, float(loss))))
    assert [i for i, _ in seen] == list(range(16))
    by_epoch = [np.mean([l for i, l in seen if i // 8 == e]) for e in (0, 1)]
    assert [float(v) for v in by_epoch] == [e["loss"] for e in tr.history]
    # a hook that does nothing changes nothing: same bits, one compilation
    assert _params_sha(tr.params) == _params_sha(plain_fit.params)
    assert tr.stats["measures"]["count:compiles"] == 1
    assert tr.measures.occurrences["trainer.step/stepFn"] == 16


def test_hook_gets_device_arrays_and_its_copies_repeat():
    """What ``bert_base_fit``'s check will read: the optimizer state after
    step 1 and the parameters after step 3 of the timed fit, copied inside
    the call because the next step is given the buffers."""
    def keeper():
        kept = {"losses": [], "kinds": set()}

        def hook(step_idx, loss, params, batch_stats, opt_state):
            kept["kinds"].add(type(loss).__name__)
            if step_idx < 3:
                kept["losses"].append(loss)      # a device scalar, not waited for
            if step_idx == 0:
                kept["opt_state"] = jax.device_get(opt_state)
            if step_idx == 2:
                kept["params"] = jax.device_get(params)
        return kept, hook

    first, hook1 = keeper()
    second, hook2 = keeper()
    _tiny_fit(hook1)
    _tiny_fit(hook2)
    assert all(isinstance(l, jax.Array) for l in first["losses"])
    assert [float(l) for l in first["losses"]] == \
        [float(l) for l in second["losses"]]
    for key in ("opt_state", "params"):
        a, b = jax.tree.leaves(first[key]), jax.tree.leaves(second[key])
        assert len(a) == len(b) > 0
        for x, z in zip(a, b):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(z))
    # after step 1 adam has counted one update
    counts = [np.asarray(x) for x in jax.tree.leaves(first["opt_state"])
              if np.asarray(x).ndim == 0 and np.asarray(x).dtype.kind == "i"]
    assert counts and all(int(c) == 1 for c in counts)


def test_skipped_step_is_counted_and_not_handed_to_the_hook():
    from synapseml_tpu.dl import FlaxTrainer, TrainConfig, make_backbone
    from synapseml_tpu.testing.chaos import chaos_nan_batches

    rng = np.random.default_rng(7)
    X = rng.normal(size=(64, 8, 8, 3)).astype(np.float32)
    y = (np.arange(64) % 2).astype(np.float32)
    seen = []
    with chaos_nan_batches(at_steps=[1]):
        tr = FlaxTrainer(make_backbone("tiny", 2),
                         TrainConfig(batch_size=16, seed=1, max_epochs=1,
                                     nonfinite_policy="skip"))
        tr.fit(X, y, step_fn=lambda i, *rest: seen.append(i))
    report = tr.stats["measures"]
    assert seen == [0, 2, 3]
    assert report["count:skipped"] == 1 and report["count:steps"] == 3
    assert tr.measures.occurrences["trainer.epoch/trainer.step"] == 4


def test_validation_and_checkpoint_are_spans_of_their_own(tmp_path):
    from synapseml_tpu.dl.trainer import FlaxTrainer, TrainConfig

    model, ids, y = _tiny_encoder()
    tr = FlaxTrainer(model, TrainConfig(batch_size=8, max_epochs=2, seed=3,
                                        checkpoint_dir=str(tmp_path / "ck")))
    tr.fit(ids, y, valid=(ids[:16], y[:16]))
    m = tr.measures
    assert m.occurrences["trainer.validation"] == 2
    assert m.occurrences["trainer.checkpointSave"] == 2
    assert all("val_acc" in e for e in tr.history)


def test_pipeline_refuses_a_hook_instead_of_ignoring_it():
    from synapseml_tpu.dl.trainer import FlaxTrainer, TrainConfig

    model, ids, y = _tiny_encoder()
    tr = FlaxTrainer(model, TrainConfig(batch_size=8,
                                        param_sharding="pipeline"))
    with pytest.raises(NotImplementedError, match="step_fn"):
        tr.fit(ids, y, step_fn=lambda *a: None)


def test_text_estimator_hands_the_hook_through_and_logs(training_measures):
    from synapseml_tpu.core import Table
    from synapseml_tpu.dl import DeepTextClassifier

    texts = [f"tok{i % 7} tok{i % 3} word{i % 5}" for i in range(32)]
    labels = np.asarray([i % 2 for i in range(32)])
    seen = []
    est = DeepTextClassifier(batchSize=8, maxEpochs=1, numLayers=1,
                             numHeads=2, hiddenSize=16, maxTokenLen=8,
                             vocabSize=64,
                             stepFn=lambda i, *rest: seen.append(i))
    est.fit(Table({"text": texts, "label": labels}))
    assert seen == [0, 1, 2, 3]
    assert len(training_measures) == 1
    record = training_measures[0]
    assert record["count:steps"] == 4
    assert {"trainer.epoch", "trainer.step/dispatch",
            "trainer.step/stepFn"} <= set(record)
