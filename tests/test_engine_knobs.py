"""Where the engine's knobs are decided, now that one place is left for each.

``BoosterConfig`` is a plain dataclass: it reads no environment and no file,
and the arguments that chose among the grower's retired designs are refused.
The histogram kernels' two knobs are an environment variable, else a
constant; the stream geometry is explicit, else environment, else the probe.
``core/tuned.py`` keeps the platform query and the measurement store with
its probe cache.
"""

import dataclasses
import json

import numpy as np
import pytest

from synapseml_tpu.core import tuned
from synapseml_tpu.gbdt import BoosterConfig, train_booster
from synapseml_tpu.ops import hist_kernel
from synapseml_tpu.ops.hist_kernel import default_chunk


def _small_fit(cfg=None):
    rng = np.random.default_rng(5)
    X = rng.normal(size=(600, 5)).astype(np.float32)
    X[::9, 2] = np.nan
    y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(np.float32)
    cfg = cfg or BoosterConfig(objective="binary", num_iterations=3,
                               num_leaves=7, seed=7)
    return train_booster(X, y, cfg)


def _trees(bst):
    return [[np.asarray(a).tolist() for a in t] for t in bst.trees]


# ---------------------------------------------------------------------------
# BoosterConfig: no environment, no file, no hidden state
# ---------------------------------------------------------------------------

RETIRED_VARIABLES = ["SYNAPSEML_TPU_ROW_LAYOUT", "SYNAPSEML_TPU_PARTITION_IMPL",
                     "SYNAPSEML_TPU_ALIGN_WINDOWS",
                     "SYNAPSEML_TPU_TUNED_DEFAULTS"]


@pytest.mark.parametrize("var", RETIRED_VARIABLES)
def test_booster_config_reads_no_environment(monkeypatch, tmp_path, var):
    """Junk in a retired variable changes nothing: the same grower
    configuration, the same trees."""
    kw = dict(objective="binary", num_iterations=3, num_leaves=7, seed=7)
    want_cfg = BoosterConfig(**kw)
    want = _small_fit(BoosterConfig(**kw))
    junk = tmp_path / "junk.json"
    junk.write_text('{"row_layout": "masked", "hist_chunk": 17}')
    monkeypatch.setenv(var, str(junk) if var.endswith("TUNED_DEFAULTS")
                       else "0" if var.endswith("ALIGN_WINDOWS")
                       else "columnar")
    cfg = BoosterConfig(**kw)
    assert cfg == want_cfg and cfg.grower() == want_cfg.grower()
    got = _small_fit(cfg)
    assert _trees(got) == _trees(want)


@pytest.mark.parametrize("name", ["row_layout", "partition_impl",
                                  "use_segmented"])
def test_retired_arguments_are_refused(name):
    from synapseml_tpu.gbdt.grower import GrowerConfig

    value = {"row_layout": "partition", "partition_impl": "sort",
             "use_segmented": None}[name]
    for cls in (BoosterConfig, GrowerConfig):
        with pytest.raises(TypeError, match=name):
            cls(**{name: value})
    assert name not in GrowerConfig._fields
    assert name not in {f.name for f in dataclasses.fields(BoosterConfig)}


def test_booster_config_validates_explicit_args():
    with pytest.raises(ValueError, match="growth_policy"):
        BoosterConfig(growth_policy="breadthfirst")
    with pytest.raises(ValueError, match="tree_learner"):
        BoosterConfig(tree_learner="columnar")
    with pytest.raises(ValueError, match="hist_allreduce_dtype"):
        BoosterConfig(hist_allreduce_dtype="fp4")


def test_two_constructions_are_one_configuration():
    """What ``window_compiles`` 0 rests on: a second ``BoosterConfig`` is
    the first one, and its grower configuration keys the same compiled
    program."""
    a, b = BoosterConfig(num_leaves=15), BoosterConfig(num_leaves=15)
    assert a == b
    ga, gb = a.grower(has_categorical=True), b.grower(has_categorical=True)
    assert ga == gb and hash(ga) == hash(gb)
    assert {ga: 1}[gb] == 1
    assert a == b                       # grower() resolved nothing in place


def test_replace_carries_every_field_and_no_hidden_state():
    cfg = BoosterConfig(objective="binary", num_iterations=4, max_bin=63,
                        growth_policy="depthwise", tree_learner="data",
                        hist_allreduce_dtype="bf16", seed=11)
    cfg.grower()
    new = dataclasses.replace(cfg, num_leaves=7)
    for f in dataclasses.fields(BoosterConfig):
        want = 7 if f.name == "num_leaves" else getattr(cfg, f.name)
        assert getattr(new, f.name) == want, f.name
    names = {f.name for f in dataclasses.fields(BoosterConfig)}
    assert set(vars(cfg)) == names and set(vars(new)) == names


def test_autoconfig_metadata_names_no_kernel_variant():
    """A fit that delegated nothing carries no ``autoconfig`` record; one
    that delegated the wire's width carries that decision and the observed
    fit time, and nothing about a kernel variant."""
    bst = _small_fit()
    assert "autoconfig" not in bst.metadata
    auto = _small_fit(BoosterConfig(objective="binary", num_iterations=2,
                                       num_leaves=7,
                                       hist_allreduce_dtype="auto"))
    assert set(auto.metadata["autoconfig"]) == {"wire_dtype",
                                                "observed_fit_s"}


# ---------------------------------------------------------------------------
# the histogram kernels' knobs: the environment, else the constant
# ---------------------------------------------------------------------------

def test_default_chunk_from_the_env_else_the_constant(monkeypatch):
    monkeypatch.delenv("SYNAPSEML_TPU_HIST_CHUNK", raising=False)
    assert default_chunk() == 2048
    monkeypatch.setenv("SYNAPSEML_TPU_HIST_CHUNK", "")
    assert default_chunk() == 2048      # empty means unset
    monkeypatch.setenv("SYNAPSEML_TPU_HIST_CHUNK", "1024")
    assert default_chunk() == 1024


def test_default_chunk_rejects_malformed_env(monkeypatch):
    monkeypatch.setenv("SYNAPSEML_TPU_HIST_CHUNK", "0")
    with pytest.raises(ValueError, match="SYNAPSEML_TPU_HIST_CHUNK"):
        default_chunk()
    monkeypatch.setenv("SYNAPSEML_TPU_HIST_CHUNK", "2O48")
    with pytest.raises(ValueError, match="SYNAPSEML_TPU_HIST_CHUNK"):
        default_chunk()


def test_hist_pack_from_the_argument_else_the_env_else_the_tile(monkeypatch):
    monkeypatch.delenv("SYNAPSEML_TPU_HIST_PACK", raising=False)
    K1, FB = 256 // 8, 8
    assert hist_kernel._pack_for(K1, FB, None) == 4     # 128 // K1
    monkeypatch.setenv("SYNAPSEML_TPU_HIST_PACK", "2")
    assert hist_kernel._pack_for(K1, FB, None) == 2
    assert hist_kernel._pack_for(K1, FB, 1) == 1        # the argument wins
    monkeypatch.setenv("SYNAPSEML_TPU_HIST_PACK", "64")
    assert hist_kernel._pack_for(K1, FB, None) == 4     # clamped to the tile
    assert hist_kernel._pack_for(1024 // 8, FB, None) == 1


def test_stream_geometry_is_explicit_else_env_else_probe(monkeypatch):
    from synapseml_tpu.io import ingest

    for v in ("SYNAPSEML_TPU_STREAM_CHUNK_ROWS", "SYNAPSEML_TPU_STREAM_DEPTH",
              "SYNAPSEML_TPU_STREAM_MEM_BUDGET"):
        monkeypatch.delenv(v, raising=False)
    probed = []
    monkeypatch.setattr(tuned, "initialized_platform", lambda: "cpu")
    monkeypatch.setattr(tuned, "measured_or",
                        lambda key, compute: probed.append(key) or 1e9)
    monkeypatch.setattr(ingest, "_perfmodel_chunk_rows",
                        lambda row_bytes, depth, rows, bw: rows)
    # the probe: 8 ms of a 1 GB/s link over 100-byte rows
    assert ingest.stream_chunk_rows(100) == 80_000
    assert probed == [("h2d_bytes_per_s", "cpu")]
    monkeypatch.setenv("SYNAPSEML_TPU_STREAM_CHUNK_ROWS", "4096")
    assert ingest.stream_chunk_rows(100) == 4096
    assert ingest.stream_chunk_rows(100, explicit=512) == 512
    assert len(probed) == 1             # neither asked the probe
    assert ingest.stream_depth() == 2
    monkeypatch.setenv("SYNAPSEML_TPU_STREAM_DEPTH", "3")
    assert ingest.stream_depth() == 3
    assert ingest.stream_depth(5) == 5


# ---------------------------------------------------------------------------
# probe-cache persistence (measured_or -> docs/probe_cache.json analog)
# ---------------------------------------------------------------------------

@pytest.fixture
def probe_cache(tmp_path, monkeypatch):
    path = tmp_path / "probe_cache.json"
    monkeypatch.setenv("SYNAPSEML_TPU_PROBE_CACHE", str(path))
    monkeypatch.setattr(tuned, "_MEASUREMENTS", {})
    return path


def test_measured_or_persists_and_short_circuits(probe_cache, monkeypatch):
    calls = []
    key = ("link_bytes_per_s", ("data", 8), "cpu:0")
    v = tuned.measured_or(key, lambda: calls.append(1) or 123.5)
    assert v == 123.5 and calls == [1]
    # in-process cache hit: no recompute
    assert tuned.measured_or(key, lambda: calls.append(1) or -1) == 123.5
    assert calls == [1]
    # simulate a fresh process: in-memory store empty, disk cache serves
    monkeypatch.setattr(tuned, "_MEASUREMENTS", {})
    assert tuned.measured_or(key, lambda: calls.append(1) or -1) == 123.5
    assert calls == [1]
    entry = json.loads(probe_cache.read_text())[tuned._key_str(key)]
    assert entry["value"] == 123.5 and entry["ts"] > 0


def test_probe_cache_ttl_expires(probe_cache, monkeypatch):
    tuned.measured_or("k", lambda: 1.0)
    monkeypatch.setattr(tuned, "_MEASUREMENTS", {})
    monkeypatch.setenv("SYNAPSEML_TPU_PROBE_CACHE_TTL_S", "0")
    # stale entry: the probe really re-runs
    assert tuned.measured_or("k", lambda: 2.0) == 2.0


def test_put_measurement_never_persists(probe_cache, monkeypatch):
    """put_measurement is the test-injection hook: an injected fake must not
    leak across processes via the disk cache."""
    tuned.put_measurement("fake", 42.0)
    assert tuned.get_measurement("fake") == 42.0
    assert not probe_cache.exists()
    monkeypatch.setattr(tuned, "_MEASUREMENTS", {})
    # a later measured_or on the same key recomputes (nothing on disk)
    assert tuned.measured_or("fake", lambda: 7.0) == 7.0


def test_clear_measurements_removes_disk_cache(probe_cache, monkeypatch):
    calls = []
    tuned.measured_or("k", lambda: calls.append(1) or 1.0)
    assert probe_cache.exists()
    tuned.clear_measurements()
    assert not probe_cache.exists()
    # "clear" means the next probe really runs, not a disk re-read
    tuned.measured_or("k", lambda: calls.append(1) or 3.0)
    assert calls == [1, 1]


def test_probe_cache_disabled_by_sentinel(tmp_path, monkeypatch):
    monkeypatch.setenv("SYNAPSEML_TPU_PROBE_CACHE", "0")
    monkeypatch.setattr(tuned, "_MEASUREMENTS", {})
    tuned.measured_or("k", lambda: 5.0)
    assert tuned._probe_cache_path() is None
    monkeypatch.setattr(tuned, "_MEASUREMENTS", {})
    assert tuned.measured_or("k", lambda: 6.0) == 6.0  # nothing persisted


def test_probe_cache_skips_unserializable_values(probe_cache, monkeypatch):
    tuned.measured_or("k", lambda: object())   # not JSON-representable
    assert not probe_cache.exists()            # in-process cache still holds
    assert isinstance(tuned.get_measurement("k"), object)
