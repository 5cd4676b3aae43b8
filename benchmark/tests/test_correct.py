"""``correct`` has been shown to fail: the control (the reference in the next
lower precision, put in the program's place) and the planted faults come out
as not correct at a size a test run can hold, and a sound run comes out
correct. The fault tests skip the harness's look for a chip (``--rehearsal``)
and drive the rest of a run with the timed path broken underneath."""

import json

import numpy as np
import pytest

from benchmark import run as harness

BENCH = harness._load_json(harness.ROOT, "BENCHMARK.json")
BOOSTER_CELLS = [w["name"] for w in BENCH["workloads"]
                 if harness._load_json(harness.HERE, "traffic",
                                       w["traffic"] + ".json")["entry"]
                 == "booster_fit"]


def _run(capsys, workload, trace=0):
    rc = harness.main(["--workload", workload, "--seed", "2147483659",
                       "--seconds", "0.5", "--trace", str(trace),
                       "--rehearsal"])
    out = capsys.readouterr()
    line = json.loads(out.out.strip().splitlines()[-1])
    return rc, line, out


def _break(monkeypatch, how):
    """Break the fit underneath the entry: both ways into it (the estimator's
    and the engine's own call) go through ``train_booster``."""
    import synapseml_tpu.gbdt as gbdt
    import synapseml_tpu.models.gbdt as est
    from synapseml_tpu.ops.quantize import compute_bin_mapper

    real = gbdt.train_booster

    def broken(X, y, cfg, **kw):
        if how in ("half_rows", "one_shard"):
            part = len(y) // (2 if how == "half_rows" else 4)
            kw["mapper"] = compute_bin_mapper(
                X, cfg.max_bin, cfg.bin_sample_count, None, cfg.seed,
                min_data_in_bin=cfg.min_data_in_bin)
            return real(X[:part], y[:part], cfg, **kw)
        b = real(X, y, cfg, **kw)
        if how == "state_unchanged":
            # a boosting step that hands its scores on unchanged grows the
            # same tree again
            b.trees = [b.trees[0]] * len(b.trees)
        elif how == "answer_altered":
            t = b.trees[1]
            b.trees[1] = t._replace(
                leaf_value=np.asarray(t.leaf_value) * np.where(
                    np.arange(len(t.leaf_value)) == 3, 1.01, 1.0))
        return b

    monkeypatch.setattr(gbdt, "train_booster", broken)
    monkeypatch.setattr(est, "train_booster", broken)


@pytest.mark.parametrize("workload", BOOSTER_CELLS)
def test_sound_run_is_correct(capsys, workload):
    rc, line, out = _run(capsys, workload)
    assert rc == harness.REHEARSAL_EXIT
    assert line["correct"] is True and line["rehearsal"] is True
    assert line["device"]["platform"] == "cpu"
    assert list(line)[-1] == "compared"
    for name, c in line["compared"].items():
        assert c["value"] <= c["limit"], name
        assert f"compared {name}:" in out.err
    assert "compile events in the window: 0" in out.out


@pytest.mark.parametrize("how", ["state_unchanged", "half_rows",
                                 "answer_altered"])
@pytest.mark.parametrize("workload", BOOSTER_CELLS)
def test_planted_fault_is_not_correct(capsys, monkeypatch, workload, how):
    _break(monkeypatch, how)
    _, line, _ = _run(capsys, workload)
    assert line["correct"] is False
    over = [k for k, c in line["compared"].items() if c["value"] > c["limit"]]
    assert over


@pytest.mark.parametrize("workload", [w for w in BOOSTER_CELLS if any(
    c["name"] == w and c["chips"] > 1 for c in BENCH["workloads"])])
def test_exchange_left_out_is_not_correct(capsys, monkeypatch, workload):
    _break(monkeypatch, "one_shard")
    _, line, _ = _run(capsys, workload)
    assert line["correct"] is False


def test_control_is_not_correct():
    """The reference with float8 histogram values in the program's place."""
    _, _, config, traffic = harness.load_cell(BOOSTER_CELLS[0], True)
    entry = harness._load_module("entries", "booster_fit").Entry(
        config, traffic, 5, 1)
    entry.setup_data()
    entry.unit()
    inputs = entry.check_inputs()
    entry.release()
    ref_mod = harness._load_module("references", "lgbm_higgs")
    lower = ref_mod.LOWER[config["params"]["histogram_values"]]
    assert lower == "float8_e4m3fn"
    limits = config["limits"]
    sound = ref_mod.check(config, inputs)
    assert all(sound[k] <= limits[k] for k in sound)
    control = ref_mod.check(config, inputs, {"value_type": lower})
    assert any(control[k] > limits[k] for k in control)
    # it fails by the numbers a change of precision moves, not by the exact
    # ones: the rows and the grid are the program's own
    assert control["count_gap"] == 0 and control["grid_gap"] == 0
    assert control["leaf_gap"] > 3 * limits["leaf_gap"]
