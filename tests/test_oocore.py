"""Out-of-core GBDT + shared ingestion layer (PR 11, ROADMAP item 2).

Five property groups:

* **Chunk geometry** — explicit > env > probe resolution, the
  ``SYNAPSEML_TPU_STREAM_MEM_BUDGET`` cap, depth resolution.
* **ChunkPump** — order/count preservation in both drive modes, producer
  thread joined on every exit path (including early break and source death),
  source errors surfacing as ``ChunkStreamError``.
* **Parity** — the contract docs/out-of-core.md states precisely: sketch
  boundaries bit-equal to ``compute_bin_mapper`` while the stream fits the
  buffer; streamed == resident-mode trees bit for bit (pump transparency);
  sparse (CSR) == dense ingestion bit for bit; cross-path AUC vs the classic
  resident ``train_booster`` within 1e-3 on breast-cancer; steady state
  compiles each streamed program exactly once.
* **Chaos** — ``chaos_chunk_stream`` delay/truncate/kill through the shared
  hook; kill→resume bit-for-bit through the PR 2 CheckpointStore at phase
  ``gbdt.stream.chunk``.
* **Shared-layer regressions** — the dl trainer's ``_batches`` epoch-tail
  drop survived the ``_prefetch`` move onto ChunkPump; ``pump_polling``
  keeps the online drain semantics (Exception absorbed, BaseException
  propagates).
"""

import threading

import numpy as np
import pytest

from synapseml_tpu.core.checkpoint import CheckpointStore, PreemptionError
from synapseml_tpu.gbdt import (BoosterConfig, StreamedDataset,
                                predict_streamed, train_booster,
                                train_booster_streamed)
from synapseml_tpu.io.ingest import (ChunkPump, ChunkStreamError,
                                     pump_polling, stream_chunk_rows,
                                     stream_depth)
from synapseml_tpu.ops.quantize import (StreamingQuantileSketch, apply_bins,
                                        compute_bin_mapper)
from synapseml_tpu.testing import ChaosPreemption, chaos_chunk_stream


def _auc(y, s):
    from sklearn.metrics import roc_auc_score

    return roc_auc_score(y, s)


def _no_pump_threads():
    return [t.name for t in threading.enumerate()
            if t.name.startswith("chunk-pump.")] == []


def _mk_cfg(**kw):
    kw.setdefault("objective", "binary")
    kw.setdefault("num_iterations", 5)
    kw.setdefault("num_leaves", 8)
    return BoosterConfig(**kw)


# ---------------------------------------------------------------------------
# chunk geometry resolution
# ---------------------------------------------------------------------------

class TestChunkGeometry:
    def test_explicit_override_wins_as_given(self):
        # below the probe clamp's minimum: operator intent is honored
        assert stream_chunk_rows(50, explicit=128) == 128
        assert stream_chunk_rows(50, explicit=1 << 22) == 1 << 22

    def test_env_override(self, monkeypatch):
        monkeypatch.setenv("SYNAPSEML_TPU_STREAM_CHUNK_ROWS", "777")
        assert stream_chunk_rows(50) == 777

    def test_mem_budget_caps_chunk_rows(self, monkeypatch):
        row_bytes, depth = 100, 2
        monkeypatch.setenv("SYNAPSEML_TPU_STREAM_MEM_BUDGET",
                           str(row_bytes * (depth + 1) * 50))
        assert stream_chunk_rows(row_bytes, explicit=4096, depth=depth) == 50
        # budget smaller than one row still yields a workable chunk
        monkeypatch.setenv("SYNAPSEML_TPU_STREAM_MEM_BUDGET", "1")
        assert stream_chunk_rows(row_bytes, explicit=4096, depth=depth) == 1

    def test_depth_resolution(self, monkeypatch):
        assert stream_depth(5) == 5
        monkeypatch.setenv("SYNAPSEML_TPU_STREAM_DEPTH", "7")
        assert stream_depth() == 7
        monkeypatch.delenv("SYNAPSEML_TPU_STREAM_DEPTH")
        assert stream_depth() >= 1


# ---------------------------------------------------------------------------
# the shared pump
# ---------------------------------------------------------------------------

class TestChunkPump:
    @pytest.mark.parametrize("threaded", [False, True])
    def test_order_count_and_join(self, threaded):
        chunks = [np.full(4, i) for i in range(13)]
        out = list(ChunkPump(iter(chunks), depth=3, threaded=threaded,
                             name="t"))
        assert [int(c[0]) for c in out] == list(range(13))
        assert _no_pump_threads()

    def test_place_applied_ahead(self):
        placed = []
        pump = ChunkPump(iter(range(6)), place=lambda c: placed.append(c) or c,
                         depth=2, threaded=False, name="t")
        it = iter(pump)
        next(it)
        # lookahead: with depth 2 the pump has placed strictly ahead of
        # what the consumer has seen
        assert len(placed) >= 2
        assert list(it) == [1, 2, 3, 4, 5]

    def test_early_break_joins_producer(self):
        pump = ChunkPump(iter(range(100)), depth=2, threaded=True, name="t")
        for c in pump:
            break
        assert _no_pump_threads()
        # idempotent close
        pump.close()

    def test_source_error_surfaces_and_joins(self):
        def bad():
            yield 0
            yield 1
            raise ValueError("source died")

        with pytest.raises(ChunkStreamError, match="died"):
            list(ChunkPump(bad(), depth=2, threaded=True, name="t"))
        assert _no_pump_threads()

    def test_pump_polling_error_and_stop_semantics(self):
        stop = threading.Event()
        calls, errs = [], []

        def step():
            calls.append(1)
            if len(calls) == 2:
                raise ValueError("poisoned batch")
            if len(calls) >= 4:
                stop.set()
            return True

        pump_polling(step, stop, 0.001, on_error=errs.append)
        assert len(calls) == 4 and len(errs) == 1
        assert isinstance(errs[0], ValueError)

        # BaseException (PreemptionError) must NOT be absorbed
        stop2 = threading.Event()

        def dying_step():
            raise PreemptionError("chaos")

        with pytest.raises(PreemptionError):
            pump_polling(dying_step, stop2, 0.001, on_error=errs.append)
        assert len(errs) == 1          # on_error never saw it


# ---------------------------------------------------------------------------
# streaming quantile sketch parity
# ---------------------------------------------------------------------------

class TestSketchParity:
    def test_exact_regime_bit_equal_boundaries(self):
        rng = np.random.default_rng(0)
        X = rng.normal(size=(500, 6)).astype(np.float32)
        X[rng.random(X.shape) < 0.05] = np.nan          # NaN routing
        X[:, 4] = rng.integers(0, 7, size=500)           # categorical
        X[:, 5] = rng.integers(0, 3, size=500)
        ref = compute_bin_mapper(X, max_bin=63, sample_count=10_000,
                                 categorical_features=[4, 5], seed=0)
        sk = StreamingQuantileSketch(6, 63, 10_000, [4, 5], seed=0)
        for i in range(0, 500, 111):                     # ragged chunks
            sk.update(X[i:i + 111])
        assert sk.exact
        got = sk.finalize()
        np.testing.assert_array_equal(ref.boundaries, got.boundaries)
        np.testing.assert_array_equal(ref.num_bins, got.num_bins)
        np.testing.assert_array_equal(ref.nan_bins, got.nan_bins)
        np.testing.assert_array_equal(ref.is_categorical, got.is_categorical)
        np.testing.assert_array_equal(ref.cat_counts, got.cat_counts)

    def test_reservoir_regime_still_valid(self):
        rng = np.random.default_rng(1)
        X = rng.normal(size=(2000, 3)).astype(np.float32)
        sk = StreamingQuantileSketch(3, 31, 256, None, seed=0)
        for i in range(0, 2000, 333):
            sk.update(X[i:i + 333])
        assert not sk.exact
        m = sk.finalize()
        assert (np.asarray(m.num_bins) >= 2).all()
        b = np.asarray(m.boundaries)
        for j in range(3):
            fin = b[j][np.isfinite(b[j])]
            assert (np.diff(fin) >= 0).all()
        # the binned result still covers the data sensibly
        binned = np.asarray(apply_bins(m, X))
        assert binned.min() >= 0 and binned.max() < 31


# ---------------------------------------------------------------------------
# streamed training parity
# ---------------------------------------------------------------------------

class TestStreamedParity:
    def test_streamed_equals_resident_mode_bitwise(self, binary_data):
        Xtr, Xte, ytr, _ = binary_data
        cfg = _mk_cfg()
        ds = StreamedDataset.from_arrays(Xtr, ytr, source_chunk=150,
                                         chunk_rows=128)
        b_stream = train_booster_streamed(ds, cfg)
        b_res = train_booster_streamed(ds, cfg, resident=True)
        assert b_stream.metadata["streamed"]["resident"] is False
        assert b_res.metadata["streamed"]["resident"] is True
        for ts, tr in zip(b_stream.trees, b_res.trees):
            for a, b in zip(ts, tr):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_array_equal(b_stream.raw_score(Xte),
                                      b_res.raw_score(Xte))
        assert _no_pump_threads()

    def test_auc_parity_vs_classic_resident(self, binary_data):
        Xtr, Xte, ytr, yte = binary_data
        cfg = _mk_cfg(num_iterations=10)
        classic = train_booster(Xtr, ytr, cfg)
        ds = StreamedDataset.from_arrays(Xtr, ytr, source_chunk=200,
                                         chunk_rows=128)
        streamed = train_booster_streamed(ds, cfg)
        assert streamed.metadata["streamed"]["sketch_exact"] is True
        a_classic = _auc(yte, classic.predict(Xte))
        a_stream = _auc(yte, streamed.predict(Xte))
        assert abs(a_classic - a_stream) <= 1e-3

    def test_sparse_csr_equals_dense_bitwise(self):
        sp = pytest.importorskip("scipy.sparse")
        rng = np.random.default_rng(2)
        Xd = rng.normal(size=(300, 8)).astype(np.float32)
        Xd[rng.random(Xd.shape) < 0.7] = 0.0             # mostly sparse
        y = (Xd[:, 0] + 0.1 * rng.normal(size=300) > 0).astype(np.float32)
        Xs = sp.csr_matrix(Xd)
        cfg = _mk_cfg(num_iterations=4)

        def sparse_batches():
            for i in range(0, 300, 90):
                yield Xs[i:i + 90], y[i:i + 90]

        ds_d = StreamedDataset.from_arrays(Xd, y, source_chunk=90,
                                           chunk_rows=64)
        ds_s = StreamedDataset(sparse_batches, chunk_rows=64)
        b_d = train_booster_streamed(ds_d, cfg)
        b_s = train_booster_streamed(ds_s, cfg)
        np.testing.assert_array_equal(b_d.raw_score(Xd), b_s.raw_score(Xd))
        # streamed prediction over sparse chunks matches in-memory predict
        chunks = [Xs[i:i + 90] for i in range(0, 300, 90)]
        got = np.concatenate(list(predict_streamed(b_s, chunks)))
        np.testing.assert_allclose(got, b_s.predict(Xd), rtol=1e-6)

    def test_train_booster_routes_streamed_dataset(self, binary_data):
        Xtr, Xte, ytr, _ = binary_data
        cfg = _mk_cfg(num_iterations=3)
        ds = StreamedDataset.from_arrays(Xtr, ytr, chunk_rows=128)
        b = train_booster(ds, None, cfg)
        assert "streamed" in b.metadata
        ds2 = StreamedDataset.from_arrays(Xtr, ytr, chunk_rows=128)
        with pytest.raises(NotImplementedError, match="does not take"):
            train_booster(ds2, ytr, cfg)

    def test_unsupported_configs_raise(self, binary_data):
        Xtr, _, ytr, _ = binary_data
        ds = StreamedDataset.from_arrays(Xtr, ytr, chunk_rows=128)
        for bad in (dict(boosting_type="dart"),
                    dict(boosting_type="rf", bagging_fraction=0.5,
                         bagging_freq=1),
                    dict(objective="multiclass", num_class=3),
                    # early stopping without a held-out stream
                    dict(early_stopping_round=2)):
            with pytest.raises(NotImplementedError):
                train_booster_streamed(ds, _mk_cfg(**bad))

    def test_both_growth_policies_stream(self, binary_data):
        # leafwise (the resident default) streams natively; depthwise stays
        # level-synchronous — each bitwise against its own resident mode
        Xtr, Xte, ytr, _ = binary_data
        ds = StreamedDataset.from_arrays(Xtr, ytr, chunk_rows=128)
        for policy in ("leafwise", "depthwise"):
            cfg = _mk_cfg(num_iterations=3, growth_policy=policy)
            b_s = train_booster_streamed(ds, cfg)
            b_r = train_booster_streamed(ds, cfg, resident=True)
            np.testing.assert_array_equal(b_s.raw_score(Xte),
                                          b_r.raw_score(Xte))
            assert b_s.metadata["streamed"]["growth_policy"] == policy
        assert _no_pump_threads()

    def test_dataset_api_contracts(self):
        with pytest.raises(TypeError, match="CALLABLE"):
            StreamedDataset(iter([np.zeros((2, 2))]))
        with pytest.raises(ValueError, match="no rows"):
            StreamedDataset(lambda: iter([])).prepare(_mk_cfg())
        # re-preparing under different binning must refuse
        X = np.random.default_rng(0).normal(size=(64, 3)).astype(np.float32)
        ds = StreamedDataset.from_arrays(X, np.zeros(64, np.float32),
                                         chunk_rows=32)
        ds.prepare(_mk_cfg(max_bin=63))
        ds.prepare(_mk_cfg(max_bin=63))            # idempotent
        with pytest.raises(ValueError, match="already prepared"):
            ds.prepare(_mk_cfg(max_bin=31))

    def test_explicit_chunk_rows_honored_in_metadata(self, binary_data):
        Xtr, _, ytr, _ = binary_data
        ds = StreamedDataset.from_arrays(Xtr, ytr, chunk_rows=96)
        b = train_booster_streamed(ds, _mk_cfg(num_iterations=1))
        md = b.metadata["streamed"]
        assert md["chunk_rows"] == 96
        assert md["num_chunks"] == -(-len(Xtr) // 96)
        assert md["rows"] == len(Xtr)

    def test_predict_streamed_matches_resident_predict(self, binary_data):
        Xtr, Xte, ytr, _ = binary_data
        ds = StreamedDataset.from_arrays(Xtr, ytr, chunk_rows=128)
        b = train_booster_streamed(ds, _mk_cfg(num_iterations=3))
        chunks = [Xte[i:i + 50] for i in range(0, len(Xte), 50)]
        got = np.concatenate(list(predict_streamed(b, chunks)))
        np.testing.assert_allclose(got, b.predict(Xte), rtol=1e-6)

    def test_no_steady_state_recompiles(self, binary_data):
        from synapseml_tpu.gbdt.stream import _stream_programs

        Xtr, _, ytr, _ = binary_data
        cfg = _mk_cfg(num_iterations=2)
        ds = StreamedDataset.from_arrays(Xtr, ytr, chunk_rows=128)
        train_booster_streamed(ds, cfg)
        info1 = _stream_programs.cache_info()
        # more trees over the same geometry: no new program set, and each
        # program holds at most ONE compiled executable
        train_booster_streamed(ds, _mk_cfg(num_iterations=6))
        info2 = _stream_programs.cache_info()
        assert info2.currsize == info1.currsize
        assert info2.hits > info1.hits
        # each cached program holds at most ONE compiled executable — more
        # trees never re-trace (the mapper vectors are arguments, not
        # closed-over constants)
        import gc

        from synapseml_tpu.gbdt.stream import _Programs

        for obj in gc.get_objects():
            if isinstance(obj, _Programs):
                assert all(v <= 1 for v in obj.cache_sizes().values()), \
                    obj.cache_sizes()


# ---------------------------------------------------------------------------
# chaos: the chunk stream as a failure surface
# ---------------------------------------------------------------------------

class TestChunkStreamChaos:
    def test_delay_is_absorbed_bitwise(self, binary_data):
        Xtr, Xte, ytr, _ = binary_data
        cfg = _mk_cfg(num_iterations=2)
        ds = StreamedDataset.from_arrays(Xtr, ytr, chunk_rows=128)
        ref = train_booster_streamed(ds, cfg)
        with chaos_chunk_stream(delay={0: 0.05, 2: 0.05}) as cc:
            slow = train_booster_streamed(ds, cfg)
        assert ("delay", 0) in cc.faults
        np.testing.assert_array_equal(ref.raw_score(Xte),
                                      slow.raw_score(Xte))
        assert _no_pump_threads()

    def test_killed_producer_surfaces_and_joins(self, binary_data):
        Xtr, _, ytr, _ = binary_data
        ds = StreamedDataset.from_arrays(Xtr, ytr, chunk_rows=128)
        with chaos_chunk_stream(kill_at=1) as cc:
            with pytest.raises(ChunkStreamError):
                train_booster_streamed(ds, _mk_cfg(num_iterations=2))
        assert ("kill", 1) in cc.faults
        assert _no_pump_threads()

    def test_truncated_chunks_observed_at_pump_level(self):
        chunks = [np.full((8, 2), i, np.float32) for i in range(5)]
        with chaos_chunk_stream(truncate_at=3, truncate_rows=0) as cc:
            out = list(ChunkPump(iter(chunks), depth=2, threaded=True,
                                 name="t"))
        assert [c.shape[0] for c in out] == [8, 8, 8, 0, 0]
        assert [f for f, _ in cc.faults] == ["truncate", "truncate"]
        assert cc.seen[0] == (0, 8)
        assert _no_pump_threads()

    def test_chaos_hook_does_not_nest(self):
        with chaos_chunk_stream():
            with pytest.raises(RuntimeError, match="nest"):
                with chaos_chunk_stream():
                    pass


class TestKillResume:
    def test_kill_resume_bit_for_bit(self, tmp_path, binary_data):
        Xtr, Xte, ytr, _ = binary_data
        cfg = _mk_cfg(num_iterations=6)
        ds = StreamedDataset.from_arrays(Xtr, ytr, chunk_rows=128)
        ref = train_booster_streamed(ds, cfg)
        nchunks = len(ds.chunks)
        d = str(tmp_path / "ck")
        # kill at a chunk boundary well into training (boundary steps are
        # globally monotonic, so this index is visited exactly once)
        kill_step = nchunks * 3 * (2 + 2)      # ~tree 3-4 territory
        with pytest.raises(PreemptionError):
            with ChaosPreemption(at={"gbdt.stream.chunk": [kill_step]}) as cp:
                train_booster_streamed(ds, cfg, checkpoint_store=d,
                                       checkpoint_every=1)
        assert cp.kills, "the kill step was never visited — adjust kill_step"
        assert _no_pump_threads()
        store = CheckpointStore(d)
        assert store.steps(), "no snapshot landed before the kill"
        resumed = train_booster_streamed(ds, cfg, checkpoint_store=d,
                                         checkpoint_every=1)
        np.testing.assert_array_equal(ref.raw_score(Xte),
                                      resumed.raw_score(Xte))
        for ts, tr in zip(ref.trees, resumed.trees):
            for a, b in zip(ts, tr):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_resume_ignores_mismatched_geometry(self, tmp_path, binary_data):
        # chunk geometry is part of the resume fingerprint: snapshots taken
        # under a different chunk_rows must NOT be adopted
        Xtr, _, ytr, _ = binary_data
        cfg = _mk_cfg(num_iterations=2)
        d = str(tmp_path / "ck")
        ds1 = StreamedDataset.from_arrays(Xtr, ytr, chunk_rows=128)
        train_booster_streamed(ds1, cfg, checkpoint_store=d,
                               checkpoint_every=1)
        ds2 = StreamedDataset.from_arrays(Xtr, ytr, chunk_rows=96)
        ref = train_booster_streamed(ds2, cfg)
        resumed = train_booster_streamed(ds2, cfg, checkpoint_store=d,
                                         checkpoint_every=1)
        np.testing.assert_array_equal(ref.raw_score(Xtr),
                                      resumed.raw_score(Xtr))


# ---------------------------------------------------------------------------
# streamed sampling: bagging / GOSS / feature sampling (ISSUE 15)
# ---------------------------------------------------------------------------

class TestStreamedSampling:
    def _resume_roundtrip(self, tmp_path, ds, cfg, Xte):
        """Train, kill mid-stream, resume; return (ref, resumed) scores."""
        ref = train_booster_streamed(ds, cfg)
        nchunks = len(ds.chunks)
        d = str(tmp_path / "ck")
        kill_step = nchunks * 3 * (2 + 2)
        with pytest.raises(PreemptionError):
            with ChaosPreemption(at={"gbdt.stream.chunk": [kill_step]}) as cp:
                train_booster_streamed(ds, cfg, checkpoint_store=d,
                                       checkpoint_every=1)
        assert cp.kills, "kill step never visited — adjust kill_step"
        assert _no_pump_threads()
        resumed = train_booster_streamed(ds, cfg, checkpoint_store=d,
                                         checkpoint_every=1)
        return ref.raw_score(Xte), resumed.raw_score(Xte)

    def test_bagging_deterministic_and_resumes_bit_for_bit(self, tmp_path,
                                                           binary_data):
        Xtr, Xte, ytr, _ = binary_data
        cfg = _mk_cfg(num_iterations=6, bagging_fraction=0.6, bagging_freq=2)
        ds = StreamedDataset.from_arrays(Xtr, ytr, chunk_rows=128)
        # masks are derived from global row offsets: two fresh runs agree
        a = train_booster_streamed(ds, cfg).raw_score(Xte)
        b = train_booster_streamed(ds, cfg).raw_score(Xte)
        np.testing.assert_array_equal(a, b)
        # kill -> resume replays the identical per-iteration bagging masks
        ref, resumed = self._resume_roundtrip(tmp_path, ds, cfg, Xte)
        np.testing.assert_array_equal(ref, resumed)

    def test_bagging_matches_resident_mode_bitwise(self, binary_data):
        Xtr, Xte, ytr, _ = binary_data
        cfg = _mk_cfg(num_iterations=4, bagging_fraction=0.5, bagging_freq=1)
        ds = StreamedDataset.from_arrays(Xtr, ytr, chunk_rows=128)
        b_s = train_booster_streamed(ds, cfg)
        b_r = train_booster_streamed(ds, cfg, resident=True)
        np.testing.assert_array_equal(b_s.raw_score(Xte), b_r.raw_score(Xte))

    def test_goss_resumes_bit_for_bit(self, tmp_path, binary_data):
        Xtr, Xte, ytr, yte = binary_data
        cfg = _mk_cfg(num_iterations=6, boosting_type="goss")
        ds = StreamedDataset.from_arrays(Xtr, ytr, chunk_rows=128)
        ref, resumed = self._resume_roundtrip(tmp_path, ds, cfg, Xte)
        np.testing.assert_array_equal(ref, resumed)
        assert _auc(yte, 1.0 / (1.0 + np.exp(-ref))) > 0.9

    def test_goss_matches_classic_auc(self, binary_data):
        Xtr, Xte, ytr, yte = binary_data
        cfg = _mk_cfg(num_iterations=8, boosting_type="goss")
        classic = train_booster(Xtr, ytr, cfg)
        ds = StreamedDataset.from_arrays(Xtr, ytr, chunk_rows=128)
        streamed = train_booster_streamed(ds, cfg)
        a_c = _auc(yte, classic.predict(Xte))
        a_s = _auc(yte, streamed.predict(Xte))
        assert abs(a_c - a_s) <= 5e-3

    def test_feature_sampling_streams_bitwise(self, binary_data):
        Xtr, Xte, ytr, yte = binary_data
        cfg = _mk_cfg(num_iterations=4, feature_fraction=0.6,
                      feature_fraction_bynode=0.8)
        ds = StreamedDataset.from_arrays(Xtr, ytr, chunk_rows=128)
        b_s = train_booster_streamed(ds, cfg)
        b_r = train_booster_streamed(ds, cfg, resident=True)
        np.testing.assert_array_equal(b_s.raw_score(Xte), b_r.raw_score(Xte))
        assert _auc(yte, b_s.predict(Xte)) > 0.9


# ---------------------------------------------------------------------------
# held-out-stream early stopping (ISSUE 15)
# ---------------------------------------------------------------------------

class TestStreamedEarlyStop:
    def test_heldout_stream_early_stop(self, binary_data):
        Xtr, Xte, ytr, yte = binary_data
        mk = lambda: _mk_cfg(num_iterations=40, early_stopping_round=3)
        ds = StreamedDataset.from_arrays(Xtr, ytr, chunk_rows=128)
        streamed = train_booster_streamed(ds, mk(), valid_data=(Xte, yte))
        # streamed == resident-mode streaming: identical programs, so the
        # metric sequence — and hence the stopping point — is bit-identical
        ds2 = StreamedDataset.from_arrays(Xtr, ytr, chunk_rows=128)
        res = train_booster_streamed(ds2, mk(), valid_data=(Xte, yte),
                                     resident=True)
        assert streamed.best_iteration == res.best_iteration
        assert len(streamed.trees) == len(res.trees)
        np.testing.assert_array_equal(streamed.raw_score(Xte),
                                      res.raw_score(Xte))
        # and it matches the classic resident early-stop contract on the
        # same fixture: stops early, truncates to best, comparable score
        classic = train_booster(Xtr, ytr, mk(), valid=(Xte, yte))
        assert len(classic.trees) < 40 and len(streamed.trees) < 40
        assert streamed.best_iteration >= 0
        assert len(streamed.trees) == streamed.best_iteration + 1
        assert abs(streamed.best_score - classic.best_score) <= 1e-3
        assert streamed.metadata["streamed"]["stopped_early"] in (True, False)
        assert _no_pump_threads()

    def test_valid_stream_without_early_stop_records_best(self, binary_data):
        Xtr, Xte, ytr, yte = binary_data
        cfg = _mk_cfg(num_iterations=5)
        ds = StreamedDataset.from_arrays(Xtr, ytr, chunk_rows=128)
        b = train_booster_streamed(ds, cfg, valid_data=(Xte, yte))
        assert len(b.trees) == 5                  # no truncation
        assert b.best_score is not None and 0.5 < b.best_score <= 1.0
        assert 0 <= b.best_iteration < 5


# ---------------------------------------------------------------------------
# mesh-streamed training (ISSUE 15 tentpole)
# ---------------------------------------------------------------------------

class TestMeshStreamed:
    @pytest.fixture()
    def mesh4(self, eight_devices):
        from synapseml_tpu.parallel.mesh import make_mesh

        return make_mesh({"data": 4}, devices=eight_devices[:4])

    def test_mesh_streamed_equals_mesh_resident_bitwise(self, mesh4,
                                                        binary_data):
        Xtr, Xte, ytr, yte = binary_data
        cfg = _mk_cfg(num_iterations=3)
        ds = StreamedDataset.from_arrays(Xtr, ytr, chunk_rows=128)
        b_s = train_booster_streamed(ds, cfg, mesh=mesh4)
        b_r = train_booster_streamed(ds, cfg, mesh=mesh4, resident=True)
        for ts, tr in zip(b_s.trees, b_r.trees):
            for a, b in zip(ts, tr):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_array_equal(b_s.raw_score(Xte), b_r.raw_score(Xte))
        md = b_s.metadata["streamed"]
        assert md["workers"] == 4
        assert _auc(yte, b_s.predict(Xte)) > 0.95
        assert _no_pump_threads()

    @pytest.mark.parametrize("wire", ["bf16", "int8"])
    def test_mesh_wire_ladder_auc(self, mesh4, binary_data, wire):
        Xtr, Xte, ytr, yte = binary_data
        cfg = _mk_cfg(num_iterations=5, hist_allreduce_dtype=wire)
        ds = StreamedDataset.from_arrays(Xtr, ytr, chunk_rows=128)
        b = train_booster_streamed(ds, cfg, mesh=mesh4)
        assert _auc(yte, b.predict(Xte)) > 0.95

    def test_mesh_auto_config_prices_streamed(self, mesh4, binary_data):
        Xtr, _, ytr, _ = binary_data
        cfg = _mk_cfg(num_iterations=1, tree_learner="auto",
                      hist_allreduce_dtype="auto")
        ds = StreamedDataset.from_arrays(Xtr, ytr, chunk_rows=128)
        b = train_booster_streamed(ds, cfg, mesh=mesh4)
        assert cfg.hist_allreduce_dtype in ("f32", "bf16", "int8")
        assert cfg.tree_learner == "data"
        assert b.metadata["routing"]["tree_learner"] == "data"
        assert b.metadata["routing"]["router"] == "streamed_data_plane"
        assert "wire_dtype" in b.metadata["autoconfig"]

    def test_mesh_kill_resume_bit_for_bit(self, tmp_path, mesh4,
                                          binary_data):
        Xtr, Xte, ytr, _ = binary_data
        cfg = _mk_cfg(num_iterations=5)
        ds = StreamedDataset.from_arrays(Xtr, ytr, chunk_rows=128)
        ref = train_booster_streamed(ds, cfg, mesh=mesh4)
        nchunks = len(ds.chunks)
        d = str(tmp_path / "ck")
        kill_step = nchunks * 3 * (2 + 2)
        with pytest.raises(PreemptionError):
            with ChaosPreemption(at={"gbdt.stream.chunk": [kill_step]}) as cp:
                train_booster_streamed(ds, cfg, mesh=mesh4,
                                       checkpoint_store=d,
                                       checkpoint_every=1)
        assert cp.kills
        assert _no_pump_threads()
        resumed = train_booster_streamed(ds, cfg, mesh=mesh4,
                                         checkpoint_store=d,
                                         checkpoint_every=1)
        np.testing.assert_array_equal(ref.raw_score(Xte),
                                      resumed.raw_score(Xte))
        for ts, tr in zip(ref.trees, resumed.trees):
            for a, b in zip(ts, tr):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_mesh_bagging_and_valid(self, mesh4, binary_data):
        Xtr, Xte, ytr, yte = binary_data
        cfg = _mk_cfg(num_iterations=6, bagging_fraction=0.6, bagging_freq=1,
                      early_stopping_round=3)
        ds = StreamedDataset.from_arrays(Xtr, ytr, chunk_rows=128)
        b = train_booster_streamed(ds, cfg, mesh=mesh4,
                                   valid_data=(Xte, yte))
        assert b.best_score is not None
        assert _auc(yte, b.predict(Xte)) > 0.9

    def test_chunk_rows_rounded_to_worker_multiple(self, eight_devices):
        from synapseml_tpu.parallel.mesh import make_mesh

        mesh = make_mesh({"data": 8}, devices=eight_devices)
        rng = np.random.default_rng(3)
        X = rng.normal(size=(400, 4)).astype(np.float32)
        y = (X[:, 0] > 0).astype(np.float32)
        ds = StreamedDataset.from_arrays(X, y, chunk_rows=50)
        train_booster_streamed(ds, _mk_cfg(num_iterations=1), mesh=mesh)
        assert ds.chunk_rows % 8 == 0          # 50 -> 56


# ---------------------------------------------------------------------------
# disk-backed chunk source + cache_dir spill (ISSUE 15)
# ---------------------------------------------------------------------------

class TestDiskChunkSource:
    def test_npy_source_roundtrip_and_training_parity(self, tmp_path,
                                                      binary_data):
        from synapseml_tpu.io.ingest import DiskChunkSource

        Xtr, Xte, ytr, _ = binary_data
        p = str(tmp_path / "X.npy")
        np.save(p, Xtr)
        src = DiskChunkSource(p, rows_per_chunk=100, labels=ytr)
        assert src.n_rows == len(Xtr)
        assert src.num_features == Xtr.shape[1]
        assert src.read_bytes_per_s > 0
        got = np.concatenate([c[0] for c in src()])
        np.testing.assert_array_equal(got, Xtr)
        # training from disk == training from RAM, bit for bit
        cfg = _mk_cfg(num_iterations=3)
        b_disk = train_booster_streamed(StreamedDataset(src, chunk_rows=128),
                                        cfg)
        b_ram = train_booster_streamed(
            StreamedDataset.from_arrays(Xtr, ytr, chunk_rows=128), cfg)
        np.testing.assert_array_equal(b_disk.raw_score(Xte),
                                      b_ram.raw_score(Xte))
        assert _no_pump_threads()

    def test_raw_uint8_source(self, tmp_path):
        from synapseml_tpu.io.ingest import DiskChunkSource

        rng = np.random.default_rng(0)
        arr = rng.integers(0, 255, size=(64, 5), dtype=np.uint8)
        p = str(tmp_path / "X.u8")
        arr.tofile(p)
        src = DiskChunkSource(p, rows_per_chunk=20, raw=True, num_features=5)
        assert src.n_rows == 64
        chunks = [c[0] for c in src()]
        assert [c.shape[0] for c in chunks] == [20, 20, 20, 4]
        np.testing.assert_array_equal(np.concatenate(chunks), arr)

    def test_cache_dir_spills_and_stays_bitwise(self, tmp_path, binary_data):
        Xtr, Xte, ytr, _ = binary_data
        cfg = _mk_cfg(num_iterations=3)
        spill = tmp_path / "spill"
        ds_ram = StreamedDataset.from_arrays(Xtr, ytr, chunk_rows=128)
        ds_spill = StreamedDataset.from_arrays(Xtr, ytr, chunk_rows=128,
                                               cache_dir=str(spill))
        b_ram = train_booster_streamed(ds_ram, cfg)
        b_spill = train_booster_streamed(ds_spill, cfg)
        np.testing.assert_array_equal(b_ram.raw_score(Xte),
                                      b_spill.raw_score(Xte))
        # chunks actually live on disk, not in host RAM
        assert all("bT" not in ch and "bT_path" in ch
                   for ch in ds_spill.chunks)
        assert len(list(spill.glob("chunk*.npy"))) == len(ds_spill.chunks)

    def test_disk_eio_mid_stream_surfaces(self, tmp_path, binary_data):
        Xtr, _, ytr, _ = binary_data
        cfg = _mk_cfg(num_iterations=2)
        ds = StreamedDataset.from_arrays(Xtr, ytr, chunk_rows=128,
                                         cache_dir=str(tmp_path / "s"))
        train_booster_streamed(ds, cfg)            # prepare + warm
        # the fault fires inside the pump's producer thread, so it reaches
        # the consumer wrapped as ChunkStreamError with the message intact
        with chaos_chunk_stream(disk_eio_at=1) as cc:
            with pytest.raises(ChunkStreamError, match="EIO"):
                train_booster_streamed(ds, cfg)
        assert ("disk_eio", 1) in cc.faults
        assert _no_pump_threads()

    def test_disk_torn_read_detected(self, tmp_path, binary_data):
        Xtr, _, ytr, _ = binary_data
        cfg = _mk_cfg(num_iterations=2)
        ds = StreamedDataset.from_arrays(Xtr, ytr, chunk_rows=128,
                                         cache_dir=str(tmp_path / "s"))
        train_booster_streamed(ds, cfg)
        with chaos_chunk_stream(disk_truncate_at=1, disk_truncate_rows=7) \
                as cc:
            with pytest.raises(ChunkStreamError, match="torn read"):
                train_booster_streamed(ds, cfg)
        assert ("disk_torn", 1) in cc.faults
        assert _no_pump_threads()


# ---------------------------------------------------------------------------
# shared-layer regressions: dl prefetch + online drain
# ---------------------------------------------------------------------------

class TestDlSharedLayer:
    def _trainer(self, bs, shuffle=False, steps_per_epoch=None):
        from synapseml_tpu.dl.trainer import FlaxTrainer, TrainConfig

        return FlaxTrainer(None, TrainConfig(batch_size=bs, shuffle=shuffle,
                                             steps_per_epoch=steps_per_epoch))

    def test_batches_tail_drop_regression(self):
        t = self._trainer(bs=4)
        X = np.arange(10, dtype=np.float32).reshape(10, 1)
        y = np.arange(10, dtype=np.float32)
        rng = np.random.default_rng(0)
        out = list(t._batches(X, y, rng))
        # 10 rows, bs=4: two full batches, tail rows 8-9 DROPPED
        assert len(out) == 2
        np.testing.assert_array_equal(out[0][0][:, 0], [0, 1, 2, 3])
        np.testing.assert_array_equal(out[1][0][:, 0], [4, 5, 6, 7])

    def test_batches_smaller_than_batchsize_yields_all(self):
        t = self._trainer(bs=8)
        X = np.arange(3, dtype=np.float32).reshape(3, 1)
        out = list(t._batches(X, np.zeros(3, np.float32),
                              np.random.default_rng(0)))
        assert len(out) == 1 and out[0][0].shape[0] == 3

    def test_batches_steps_per_epoch_limit(self):
        t = self._trainer(bs=2, steps_per_epoch=3)
        X = np.arange(20, dtype=np.float32).reshape(20, 1)
        out = list(t._batches(X, np.zeros(20, np.float32),
                              np.random.default_rng(0)))
        assert len(out) == 3

    def test_prefetch_preserves_order_count_and_devices(self):
        import jax.numpy as jnp

        t = self._trainer(bs=4)
        X = np.arange(12, dtype=np.float32).reshape(12, 1)
        y = np.arange(12, dtype=np.float32)
        out = list(t._prefetch(t._batches(X, y, np.random.default_rng(0))))
        assert len(out) == 3
        assert all(isinstance(xb, jnp.ndarray) for xb, _ in out)
        np.testing.assert_array_equal(
            np.concatenate([np.asarray(xb)[:, 0] for xb, _ in out]),
            np.arange(12, dtype=np.float32))
