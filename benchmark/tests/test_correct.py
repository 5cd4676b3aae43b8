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


def _cells(entry):
    return [w["name"] for w in BENCH["workloads"]
            if harness._load_json(harness.HERE, "traffic",
                                  w["traffic"] + ".json")["entry"] == entry]


BOOSTER_CELLS = _cells("booster_fit")


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


# -- the trainer's cells ----------------------------------------------------------

TRAINER_CELLS = _cells("trainer_fit")
# each planted fault, and the number that is meant to catch it
TRAINER_FAULTS = {"state_unchanged": "change_gap", "half_batch": "grad_gap",
                  "mask_dropped": "grad_difference"}


def _break_trainer(monkeypatch, how):
    """Break the training step underneath the estimator."""
    import jax
    import jax.numpy as jnp
    import optax

    import synapseml_tpu.dl.text as text
    import synapseml_tpu.dl.trainer as trainer

    real_tx = trainer._make_tx
    real_ce = optax.softmax_cross_entropy_with_integer_labels

    def make_tx(cfg, total_steps, mask=None):
        tx = real_tx(cfg, total_steps, mask)
        if how == "state_unchanged":
            # a step that hands parameters and moments on as they came
            return optax.GradientTransformation(
                tx.init, lambda g, s, p=None: (
                    jax.tree.map(jnp.zeros_like, g), s))
        return tx

    monkeypatch.setattr(trainer, "_make_tx", make_tx)
    if how == "half_batch":
        # half of the batch left out, the mean taken over the rest
        monkeypatch.setattr(
            optax, "softmax_cross_entropy_with_integer_labels",
            lambda logits, labels: real_ce(logits, labels)[
                : labels.shape[0] // 2])
    if how == "mask_dropped":
        monkeypatch.setattr(text, "PAD_ID", -1)   # no id is padding


@pytest.mark.parametrize("workload", TRAINER_CELLS)
def test_sound_trainer_run_is_correct(capsys, workload):
    rc, line, out = _run(capsys, workload)
    assert rc == harness.REHEARSAL_EXIT
    assert line["correct"] is True and line["rehearsal"] is True
    assert list(line)[-1] == "compared"
    limits = harness.load_cell(workload, True)[2]["limits"]
    assert set(line["compared"]) == set(limits) | {"window_compiles"}
    assert {"grad_gap", "grad_difference", "change_gap",
            "step_count_gap"} <= set(limits)
    assert {"loss_gap", "first_loss_gap"} & set(limits)
    for name, c in line["compared"].items():
        assert c["value"] <= c["limit"], name
        assert f"compared {name}:" in out.err
    assert "compile events in the window: 0" in out.out
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert set(line["metrics"]) == {"setup_s", "train_samples_per_s_chip"}


@pytest.mark.parametrize("how", sorted(TRAINER_FAULTS))
@pytest.mark.parametrize("workload", TRAINER_CELLS)
def test_planted_trainer_fault_is_not_correct(capsys, monkeypatch, workload,
                                              how):
    _break_trainer(monkeypatch, how)
    _, line, _ = _run(capsys, workload)
    assert line["correct"] is False
    caught = line["compared"][TRAINER_FAULTS[how]]
    assert caught["value"] > caught["limit"]
    # the optimizer's own count stands still with its state, and only then
    assert (line["compared"]["step_count_gap"]["value"] > 0) == (
        how == "state_unchanged")


def test_trainer_control_and_stand_ins_are_not_correct():
    """The reference with float8 operands in the program's place, and every
    fault planted in the reference, at the rehearsal's size."""
    _, _, config, traffic = harness.load_cell(TRAINER_CELLS[0], True)
    ref_mod = harness._load_module("references", "bert_base_ft")
    made = harness._load_module("entries", "trainer_fit")
    entry = made.Entry(config, traffic, 2147483659, 1)
    entry.setup()
    entry.unit()
    inputs = entry.check_inputs()
    entry.release()
    limits = config["limits"]
    plans = ref_mod.stand_in_plans(config)
    assert plans["control"] == {"value_type": "float8_e4m3fn"}
    ref = ref_mod.Reference(config, inputs["texts"], inputs["labels"],
                            inputs["seed"], inputs["batch"], inputs["steps"])
    sound = ref_mod.check(config, inputs, reference=ref)
    assert all(sound[k] <= limits[k] for k in sound)
    for name, how in plans.items():
        numbers = ref_mod.check(config, inputs, how, reference=ref)
        assert any(numbers[k] > limits[k] for k in numbers), name
    unchanged = ref_mod.check(config, inputs, plans["state_unchanged"],
                              reference=ref)
    assert unchanged["grad_gap"] == 1.0 and unchanged["change_gap"] == 1.0
