"""core/perfmodel — the learned performance model behind every auto-config
knob (arXiv:2008.01040 in miniature).

Pins the prediction ladder (matched replay > least-squares fit > analytic
prior > none), the choose() fallback discipline (hand-tuned default wins
unless a CONFIDENT rival beats a CONFIDENT fallback prediction by the
hysteresis margin), the kill switch, journal/backfill mechanics, and each
suggestion helper's contract with its call site.

Every test journals into its own tmp file (conftest already points
``SYNAPSEML_TPU_PERF_ROWS`` away from the committed docs/measurements.jsonl;
these tests re-point it per-test for full isolation).
"""

import json
import math

import numpy as np
import pytest

from synapseml_tpu.core import perfmodel


@pytest.fixture
def journal(tmp_path, monkeypatch):
    """Per-test training-row journal; rows written via append_training_row
    with no explicit path land here and only here."""
    p = tmp_path / "rows.jsonl"
    monkeypatch.setenv("SYNAPSEML_TPU_PERF_ROWS", str(p))
    return p


def _row(kind, arm, feats, obs, **kw):
    return perfmodel.append_training_row(kind, arm, feats, obs,
                                         platform="cpu", **kw)


# ---------------------------------------------------------------------------
# featurizer
# ---------------------------------------------------------------------------

def test_featurize_shapes_dtypes_and_extras():
    f = perfmodel.featurize(shape_like=(100, 20, 3), dtype="f32",
                            wire_dtype="int8", chunk_rows=4096, depth=2,
                            rows_extra=7)
    assert f["rows"] == 100.0
    assert f["cols"] == 60.0
    assert f["dtype_bytes"] == 4.0
    assert f["wire_bytes"] == 2.0       # int8 ships value+count planes
    assert f["chunk_rows"] == 4096.0
    assert f["depth"] == 2.0
    assert f["rows_extra"] == 7.0
    # bf16 wire is 8/3 effective bytes; None extras are dropped
    g = perfmodel.featurize(wire_dtype="bf16", maybe=None)
    assert g == {"wire_bytes": pytest.approx(8.0 / 3.0)}


def test_feature_distance_log_space_and_missing_keys():
    d = perfmodel._feature_distance({"rows": 100.0}, {"rows": 100.0})
    assert d == 0.0
    # missing keys on either side count as infinitely far
    assert math.isinf(perfmodel._feature_distance({"rows": 1.0}, {}))
    assert math.isinf(perfmodel._feature_distance(
        {"rows": 1.0}, {"rows": 1.0, "cols": 2.0}))
    near = perfmodel._feature_distance({"rows": 100.0}, {"rows": 110.0})
    far = perfmodel._feature_distance({"rows": 100.0}, {"rows": 1000.0})
    assert 0 < near < perfmodel.MATCH_DISTANCE < far


# ---------------------------------------------------------------------------
# journal mechanics
# ---------------------------------------------------------------------------

def test_append_and_read_rows_platform_keyed(journal):
    _row("fam", "a", {"rows": 10.0}, 0.5)
    perfmodel.append_training_row("fam", "b", {"rows": 10.0}, 0.7,
                                  platform="tpu")
    assert [r["arm"] for r in perfmodel.training_rows("fam", "cpu")] == ["a"]
    assert [r["arm"] for r in perfmodel.training_rows("fam", "tpu")] == ["b"]
    # cpu rows can never train the tpu model and vice versa
    assert perfmodel.training_rows("fam", "gpu") == []


def test_corrupt_journal_lines_skipped(journal):
    _row("fam", "a", {"rows": 10.0}, 0.5)
    with open(journal, "a") as fh:
        fh.write("{not json\n")
        fh.write(json.dumps({"no": "perf_row marker"}) + "\n")
        fh.write(json.dumps({"perf_row": 1, "kind": "fam", "arm": "x",
                             "features": {}, "observed_s": -1.0,
                             "platform": "cpu"}) + "\n")  # non-positive
        fh.write(json.dumps({"perf_row": 1, "kind": "fam", "arm": "y",
                             "features": "bogus", "observed_s": 1.0,
                             "platform": "cpu"}) + "\n")  # bad features
    rows = perfmodel.training_rows("fam", "cpu")
    assert [r["arm"] for r in rows] == ["a"]


def test_backfill_is_idempotent(tmp_path, journal):
    legacy = tmp_path / "measurements.json"
    legacy.write_text(json.dumps([
        {"metric": "gbdt_train_row_iters_per_sec_per_chip",
         "platform": "cpu-sim", "captured_at": "2026-01-01T00:00:00",
         "variants": {"partition_sort": 100.0, "masked": 50.0}},
        {"metric": "gbdt_voting_vs_data_parallel_speedup",
         "platform": "cpu-mesh-8", "captured_at": "2026-01-01T00:00:00",
         "unit": "speedup (voting 3856 r-i/s vs data-parallel 26600 r-i/s, "
                 "2000 cols)"},
        {"metric": "unrelated_metric", "value": 1.0},
    ]))
    added = perfmodel.backfill_training_rows(str(legacy), str(journal))
    # voting + data; the retired kernel-variant sweep's record feeds no
    # picker and is passed over
    assert added == 2
    rows = perfmodel.training_rows(path=str(journal))
    assert {r["kind"] for r in rows} == {"gbdt_tree_learner"}
    tl = {r["arm"]: r for r in rows if r["kind"] == "gbdt_tree_learner"}
    assert tl["voting"]["observed_s"] == pytest.approx(1 / 3856)
    assert tl["data"]["features"] == {"workers": 8.0, "nfeat": 2000.0}
    # second run appends nothing (backfilled_from dedup)
    assert perfmodel.backfill_training_rows(str(legacy), str(journal)) == 0
    assert len(perfmodel.training_rows(path=str(journal))) == 2


# ---------------------------------------------------------------------------
# the prediction ladder
# ---------------------------------------------------------------------------

def test_predict_matched_replay(journal):
    for obs in (1.0, 1.2):
        _row("fam", "a", {"rows": 100.0}, obs)
    p = perfmodel.predict(perfmodel.Candidate("fam", "a", {"rows": 100.0}),
                          platform="cpu")
    assert p.source == "matched"
    assert p.seconds == pytest.approx(1.1)   # distance-0 rows average
    assert p.confidence == pytest.approx(0.92)
    assert p.detail["rows_matched"] == 2


def test_predict_fitted_when_no_match(journal):
    # perfectly log-linear rows far from the candidate -> least-squares fit
    for rows, obs in ((100.0, 1.0), (1000.0, 2.0), (10000.0, 4.0)):
        _row("fam", "a", {"rows": rows}, obs)
    p = perfmodel.predict(perfmodel.Candidate("fam", "a", {"rows": 3000.0}),
                          platform="cpu")
    assert p.source == "fitted"
    assert p.detail["r2"] > 0.99
    assert 1.0 < p.seconds < 4.0             # interpolates the envelope
    assert p.confidence == pytest.approx(0.75)
    # extrapolating far past the training envelope is a guess
    px = perfmodel.predict(perfmodel.Candidate("fam", "a", {"rows": 1e9}),
                           platform="cpu")
    assert px.source == "fitted"
    assert px.confidence == pytest.approx(perfmodel.ANALYTIC_CONFIDENCE)


def test_predict_analytic_then_none(journal):
    p = perfmodel.predict(perfmodel.Candidate("fam", "a", {"rows": 1.0},
                                              analytic_s=0.25),
                          platform="cpu")
    assert (p.source, p.seconds) == ("analytic", 0.25)
    assert p.confidence == perfmodel.ANALYTIC_CONFIDENCE < \
        perfmodel.MIN_CONFIDENCE   # an analytic prior alone can never win
    q = perfmodel.predict(perfmodel.Candidate("fam", "a", {"rows": 1.0}),
                          platform="cpu")
    assert q.source == "none" and math.isinf(q.seconds)


# ---------------------------------------------------------------------------
# choose(): the fallback discipline
# ---------------------------------------------------------------------------

def _pair():
    return [perfmodel.Candidate("fam", "f32", {"rows": 64.0}, config="f32"),
            perfmodel.Candidate("fam", "int8", {"rows": 64.0}, config="int8")]


def test_choose_falls_back_without_evidence(journal):
    dec = perfmodel.choose(_pair(), fallback_arm="f32", platform="cpu")
    assert dec.used_fallback and dec.arm == "f32"
    assert dec.source == "fallback"
    assert dec.predicted_s is None
    # provenance is JSON-safe and names every candidate
    rec = dec.provenance()
    json.dumps(rec)
    assert {c["arm"] for c in rec["candidates"]} == {"f32", "int8"}


def test_choose_displaces_on_confident_clear_win(journal):
    _row("fam", "f32", {"rows": 64.0}, 1.0)
    _row("fam", "int8", {"rows": 64.0}, 0.5)
    dec = perfmodel.choose(_pair(), fallback_arm="f32", platform="cpu")
    assert not dec.used_fallback
    assert dec.arm == "int8" and dec.config == "int8"
    assert dec.source == "matched"
    aud = dec.audit(observed_s=0.5)
    assert aud["predicted_over_observed"] == pytest.approx(1.0)


def test_choose_hysteresis_keeps_fallback(journal):
    # rival only 3% faster: inside the 5% hysteresis band, fallback holds
    _row("fam", "f32", {"rows": 64.0}, 1.0)
    _row("fam", "int8", {"rows": 64.0}, 0.97)
    dec = perfmodel.choose(_pair(), fallback_arm="f32", platform="cpu")
    assert dec.used_fallback and dec.arm == "f32"


def test_choose_needs_confident_fallback_to_displace(journal):
    """A matched rival cannot displace a fallback the model cannot price —
    the comparison needs BOTH sides confident (this is why every bench A/B
    records the hand-tuned default arm too)."""
    _row("fam", "int8", {"rows": 64.0}, 0.1)
    dec = perfmodel.choose(_pair(), fallback_arm="f32", platform="cpu")
    assert dec.used_fallback and dec.arm == "f32"


def test_choose_kill_switch(journal, monkeypatch):
    _row("fam", "int8", {"rows": 64.0}, 0.1)
    _row("fam", "f32", {"rows": 64.0}, 1.0)
    monkeypatch.setenv("SYNAPSEML_TPU_PERFMODEL", "0")
    dec = perfmodel.choose(_pair(), fallback_arm="f32", platform="cpu")
    assert dec.used_fallback and dec.arm == "f32"
    assert dec.source == "disabled"


def test_choose_confirms_fallback_when_it_wins(journal):
    _row("fam", "f32", {"rows": 64.0}, 0.4)
    _row("fam", "int8", {"rows": 64.0}, 0.9)
    dec = perfmodel.choose(_pair(), fallback_arm="f32", platform="cpu")
    assert dec.arm == "f32"
    assert not dec.used_fallback         # chosen on evidence, not by default
    assert dec.source == "matched"


# ---------------------------------------------------------------------------
# suggestion helpers
# ---------------------------------------------------------------------------

def test_suggest_wire_dtype_analytic_alone_keeps_f32(journal):
    wd, dec = perfmodel.suggest_wire_dtype(
        n_rows=1e5, nfeat=100, workers=8, max_bin=64, num_leaves=31,
        link_bps=1e9, platform="cpu")
    assert wd == "f32" and dec.used_fallback
    # every arm got an analytic price in the provenance
    assert all(c["source"] == "analytic" for c in dec.candidates)


def test_suggest_wire_dtype_matched_rows_flip_to_int8(journal):
    for wd, obs in (("f32", 1.0), ("int8", 0.4)):
        _row("gbdt_wire_dtype", wd,
             perfmodel.featurize(wire_dtype=wd, rows=1e5, nfeat=100,
                                 workers=8, max_bin=64, num_leaves=31), obs)
    wd, dec = perfmodel.suggest_wire_dtype(
        n_rows=1e5, nfeat=100, workers=8, max_bin=64, num_leaves=31,
        link_bps=None, platform="cpu")
    assert wd == "int8" and not dec.used_fallback


def test_suggest_bucket_growth(journal):
    g, dec = perfmodel.suggest_bucket_growth(48, platform="cpu")
    assert g == 2.0 and dec.used_fallback
    feats = perfmodel.featurize(max_batch_size=48)
    _row("serving_bucket_growth", "g2.0", feats, 1.0)
    _row("serving_bucket_growth", "g4.0", feats, 0.5)
    g, dec = perfmodel.suggest_bucket_growth(48, platform="cpu")
    assert g == 4.0 and not dec.used_fallback
    # a different ladder size shares no matched rows -> fallback again
    g, _ = perfmodel.suggest_bucket_growth(512, platform="cpu")
    assert g == 2.0


def test_suggest_accum_steps_fallback_and_divisors(journal):
    k, dec = perfmodel.suggest_accum_steps(batch=16, param_bytes=1e6,
                                           state_budget_bytes=None,
                                           platform="cpu")
    assert k == 1 and dec.used_fallback    # analytic alone never displaces
    arms = {c["arm"] for c in dec.candidates}
    assert arms == {"a1", "a2", "a4", "a8"}
    # non-divisible batch prunes the arm list
    _, dec = perfmodel.suggest_accum_steps(batch=6, param_bytes=1e6,
                                           state_budget_bytes=None,
                                           platform="cpu")
    assert {c["arm"] for c in dec.candidates} == {"a1", "a2"}


def test_suggest_pipeline_schedule(journal):
    s, dec = perfmodel.suggest_pipeline_schedule(2, 2, platform="cpu")
    assert s == "fill_drain" and dec.used_fallback
    feats = perfmodel.featurize(stages=2, microbatches=2)
    _row("dl_pipeline_schedule", "fill_drain", feats, 1.0)
    _row("dl_pipeline_schedule", "overlap", feats, 0.7)
    s, dec = perfmodel.suggest_pipeline_schedule(2, 2, platform="cpu")
    assert s == "overlap" and not dec.used_fallback


def test_suggest_stage_cuts_cost_balanced():
    sizes, dec = perfmodel.suggest_stage_cuts([10, 1, 1, 1, 1, 1], 2)
    assert sizes == [1, 5]                 # min-max beats count-balanced
    assert not dec.used_fallback
    assert dec.predicted_s == pytest.approx(10.0)   # the heaviest stage
    # even costs land on the count-balanced split
    sizes, dec = perfmodel.suggest_stage_cuts([1.0] * 6, 3)
    assert sizes == [2, 2, 2] and dec.used_fallback
    # degenerate costs: count-balanced fallback
    sizes, dec = perfmodel.suggest_stage_cuts([0.0] * 5, 2)
    assert sizes == [3, 2] and dec.used_fallback and dec.source == "fallback"


def test_suggest_chunk_rows_formula_is_identity_without_rows(journal):
    rows, dec = perfmodel.suggest_chunk_rows(148, 2, 65536, h2d_bps=1e9,
                                             platform="cpu")
    assert rows == 65536 and dec.used_fallback
    # ladder stays within [fallback/4, 4*fallback]
    arms = {c["arm"] for c in dec.candidates}
    assert f"c{65536}" in arms
    assert all(16384 <= int(a[1:]) <= 262144 for a in arms)


def test_suggest_chunk_rows_matched_rows_displace(journal):
    for cr, obs in ((65536, 2e-7), (131072, 1e-7)):
        _row("io_chunk_rows", f"c{cr}",
             perfmodel.featurize(row_bytes=148, depth=2, chunk_rows=cr), obs)
    rows, dec = perfmodel.suggest_chunk_rows(148, 2, 65536, platform="cpu")
    assert rows == 131072 and not dec.used_fallback


def test_suggest_sketch_second_pass_budget_rule(journal, monkeypatch):
    # predicted pass cost 0.1s vs 10s of training: inside the 10% budget
    take, dec = perfmodel.suggest_sketch_second_pass(
        100.0, 20.0, rows_per_s=1000.0, train_s_estimate=10.0,
        platform="cpu")
    assert take is True and dec.arm == "exact"
    assert dec.candidates[0]["budget_s"] == pytest.approx(1.0)
    # same cost vs 0.5s of training: over budget, skip
    take, dec = perfmodel.suggest_sketch_second_pass(
        100.0, 20.0, rows_per_s=1000.0, train_s_estimate=0.5, platform="cpu")
    assert take is False and dec.arm == "skip"
    # unknown cost: never take the pass
    take, _ = perfmodel.suggest_sketch_second_pass(
        100.0, 20.0, rows_per_s=None, train_s_estimate=10.0, platform="cpu")
    assert take is False
    monkeypatch.setenv("SYNAPSEML_TPU_PERFMODEL", "0")
    take, dec = perfmodel.suggest_sketch_second_pass(
        100.0, 20.0, rows_per_s=1000.0, train_s_estimate=10.0,
        platform="cpu")
    assert take is False and dec.source == "disabled"


# ---------------------------------------------------------------------------
# call-site integration (the pickers keep bypass + provenance)
# ---------------------------------------------------------------------------

def test_partition_stages_cost_balanced_cuts():
    from synapseml_tpu.dl.backbones import partition_stages

    units = [object() for _ in range(6)]
    st = partition_stages(units, 2, unit_costs=[10, 1, 1, 1, 1, 1])
    assert [len(g.units) for g in st.stages] == [1, 5]
    even = partition_stages(units, 2)
    assert [len(g.units) for g in even.stages] == [3, 3]
    with pytest.raises(ValueError, match="unit_costs has 2 entries"):
        partition_stages(units, 2, unit_costs=[1, 2])


def test_ingest_chunk_decision_provenance(journal, monkeypatch):
    from synapseml_tpu.io import ingest

    # probe branch -> a decision is recorded (identity without matched rows)
    rows = ingest.stream_chunk_rows(148)
    dec = ingest.last_chunk_decision()
    assert dec is not None and dec["kind"] == "io_chunk_rows"
    assert dec["arm"] == f"c{rows}" and dec["used_fallback"]
    # explicit bypass: the model never runs and stale provenance is cleared
    assert ingest.stream_chunk_rows(148, explicit=4096) == 4096
    assert ingest.last_chunk_decision() is None
    monkeypatch.setenv("SYNAPSEML_TPU_STREAM_CHUNK_ROWS", "8192")
    assert ingest.stream_chunk_rows(148) == 8192
    assert ingest.last_chunk_decision() is None


def test_bucketed_runner_auto_growth(journal):
    from synapseml_tpu.core.inference import BucketedRunner, bucket_ladder

    r = BucketedRunner(lambda x: x + 1, max_batch_size=64)
    assert r.buckets == bucket_ladder(64, 2.0)   # hand-tuned default holds
    assert r.stats()["autoconfig"]["used_fallback"] is True
    feats = perfmodel.featurize(max_batch_size=64)
    _row("serving_bucket_growth", "g2.0", feats, 1.0)
    _row("serving_bucket_growth", "g4.0", feats, 0.5)
    r2 = BucketedRunner(lambda x: x + 1, max_batch_size=64)
    assert r2.buckets == bucket_ladder(64, 4.0)
    assert r2.stats()["autoconfig"]["used_fallback"] is False
    # explicit growth bypasses the model: no autoconfig record
    r3 = BucketedRunner(lambda x: x + 1, max_batch_size=64, growth=1.5)
    assert r3.buckets == bucket_ladder(64, 1.5)
    assert "autoconfig" not in r3.stats()


def test_trainer_auto_sentinels_resolve_with_provenance(journal):
    from synapseml_tpu import dl

    rng = np.random.default_rng(0)
    X = rng.normal(size=(16, 8, 8, 3)).astype(np.float32)
    y = rng.integers(0, 2, size=16)
    cfg = dl.TrainConfig(batch_size=8, max_epochs=1, param_sharding="auto",
                         accum_steps=0, seed=0)
    tr = dl.FlaxTrainer(dl.make_backbone("tiny", 2), cfg)
    tr.fit(X, y)
    # sentinels resolved to the hand-tuned defaults (no rows -> fallback)
    assert cfg.param_sharding == "replicated"
    assert cfg.accum_steps == 1
    auto = tr.stats["autoconfig"]
    assert auto["param_sharding"]["used_fallback"] is True
    assert auto["accum_steps"]["used_fallback"] is True
    # predicted-vs-observed audit trail lands after the fit
    assert auto["observed_fit_s"] > 0


# ---------------------------------------------------------------------------
# calibration drift: bad audits demote a family to its fallback
# ---------------------------------------------------------------------------

@pytest.fixture
def drift_clean():
    perfmodel.reset_drift()
    yield
    perfmodel.reset_drift()


def _audited_decision(kind, predicted_s):
    return perfmodel.Decision(kind, "a", None, predicted_s, 0.9, False,
                              "a", "matched")


def test_drift_demotes_after_bad_audit_median(journal, drift_clean):
    kind = "fam_drift"
    # healthy audits: ratio ~1, no demotion
    for _ in range(perfmodel.DRIFT_MIN_AUDITS):
        _audited_decision(kind, 1.0).audit(observed_s=1.05)
    assert perfmodel.drift_demoted(kind, "cpu") is False
    # the window fills with 3x-off audits; crossing warns by name once
    with pytest.warns(perfmodel.PerfModelDriftWarning, match=kind):
        for _ in range(perfmodel.DRIFT_WINDOW):
            _audited_decision(kind, 3.0).audit(observed_s=1.0)
    assert perfmodel.drift_demoted(kind, "cpu") is True
    # choose() now returns the fallback unconditionally, tagged by source
    cands = [perfmodel.Candidate(kind, "a", {}),
             perfmodel.Candidate(kind, "b", {})]
    dec = perfmodel.choose(cands, fallback_arm="b", platform="cpu")
    assert dec.arm == "b" and dec.used_fallback is True
    assert dec.source == "drift_demoted"
    # other families are untouched
    other = perfmodel.choose([perfmodel.Candidate("fam_ok", "a", {})],
                             fallback_arm="a", platform="cpu")
    assert other.source != "drift_demoted"
    # the warning fires once per family per process
    import warnings as _w

    with _w.catch_warnings():
        _w.simplefilter("error")
        _audited_decision(kind, 3.0).audit(observed_s=1.0)


def test_drift_needs_min_audits_and_both_directions(journal, drift_clean):
    # under-prediction (model says fast, reality slow) also counts
    kind = "fam_slowside"
    for i in range(perfmodel.DRIFT_MIN_AUDITS - 1):
        perfmodel.record_audit(kind, 0.2, platform="cpu")
    assert perfmodel.drift_demoted(kind, "cpu") is False   # too few
    with pytest.warns(perfmodel.PerfModelDriftWarning):
        perfmodel.record_audit(kind, 0.2, platform="cpu")
    assert perfmodel.drift_demoted(kind, "cpu") is True
    # reset clears state
    perfmodel.reset_drift()
    assert perfmodel.drift_demoted(kind, "cpu") is False
    # garbage ratios are ignored
    perfmodel.record_audit(kind, float("inf"), platform="cpu")
    perfmodel.record_audit(kind, 0.0, platform="cpu")
    assert perfmodel.drift_demoted(kind, "cpu") is False
