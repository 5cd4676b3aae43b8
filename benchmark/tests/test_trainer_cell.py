"""The trainer cells: their texts, their count function, their readers, and
the plain reference against the program at a size a test run can hold."""

import json
import types

import numpy as np
import pytest

from benchmark import counts, texts
from benchmark import run as harness

BENCH = harness._load_json(harness.ROOT, "BENCHMARK.json")
TRAINER_CELLS = [w["name"] for w in BENCH["workloads"]
                 if harness._load_json(harness.HERE, "traffic",
                                       w["traffic"] + ".json")["entry"]
                 == "trainer_fit"]
READERS = ("train_step_mfu", "trainer_step_ms_p50",
           "trainer_data_wait_share", "trainer_dispatch_share")
# the trainer's record of one epoch of 32 steps
EPOCH = {"epoch": 1, "steps": 32, "seconds": 6.4, "data_wait_s": 0.064,
         "dispatch_s": 0.32, "loss_sync_s": 5.9, "step_ms_p50": 199.5}


def _cell(name, rehearsal=True):
    return harness.load_cell(name, rehearsal)


# -- texts --------------------------------------------------------------------

def test_texts_are_the_seeds_and_shaped_as_the_traffic_says():
    spec = _cell(TRAINER_CELLS[0], False)[3]["texts"]
    a, ya = texts.make(spec, 2147483659)
    b, yb = texts.make(spec, 2147483659)
    c, _ = texts.make(spec, 2147483660)
    assert a == b and (ya == yb).all() and a != c
    assert len(a) == spec["count"] and len(set(a)) == len(a)
    lengths = np.array([len(t.split()) for t in a])
    assert lengths.min() >= spec["length"]["min"]
    assert lengths.max() <= spec["length"]["max"]
    assert abs(np.median(lengths) - spec["length"]["median"]) < 15
    # two balanced labels; a few rows are longer than the encoder's positions
    assert np.bincount(ya).tolist() == [spec["count"] // 2] * 2
    assert 0.01 < (lengths >= 511).mean() < 0.12


def test_word_list_is_fixed_and_distinct():
    w = texts.word_list(500, 11)
    assert len(set(w)) == 500 and (w == texts.word_list(500, 11)).all()
    assert all(t.isalpha() and t.islower() for t in w)


# -- the count function ---------------------------------------------------------

def test_encoder_forward_flops_by_hand():
    # hidden 4, feed-forward 8, 3 keys: (4*16 + 2*32) weights, two
    # operations each, + 2*3*4 for the scores + 2*3*4 for the weighted sum
    assert counts.encoder_forward_flops_per_token_layer(3, 4, 8) == 256 + 48


def test_encoder_train_flops_is_the_sum_of_its_parts():
    s, h, inter, layers, rows, classes = 512, 768, 3072, 12, 64, 2
    projections = 4 * 2 * h * h
    feed_forward = 2 * 2 * h * inter
    attention = 2 * s * h + 2 * s * h
    forward = rows * (s * layers * (projections + feed_forward + attention)
                      + 2 * h * classes)
    assert counts.encoder_train_flops(rows, s, h, layers, inter,
                                      classes) == 3 * forward
    # BERT-base at 512 positions: 566 MFLOP a token, 18.6 TFLOP a step of 64
    per_token = 3 * forward / (rows * s)
    assert per_token == pytest.approx(566.2e6, rel=1e-3)
    # one row of one position and one layer, by hand
    assert counts.encoder_train_flops(1, 1, 2, 1, 4, 3) == 3 * (
        2 * (16 + 16) + 4 * 2 + 2 * 2 * 3)


def test_encoder_train_flops_grows_with_the_positions_squared_part_only():
    a = counts.encoder_train_flops(8, 128, 768, 12, 3072, 2)
    b = counts.encoder_train_flops(2, 512, 768, 12, 3072, 2)
    # the same tokens: the difference is the scores' and the head's
    assert b - a == 3 * (1024 * 12 * 4 * 768 * (512 - 128)
                         - 6 * 2 * 768 * 2)


# -- the readers ----------------------------------------------------------------

def _ctx(epoch):
    entry = types.SimpleNamespace(traced_epoch=lambda: epoch, batch=64,
                                  classes=2)
    config = _cell(TRAINER_CELLS[0], False)[2]
    return {"entry": entry, "config": config, "chips": 1, "traced_s": 6.4,
            "device_kind": "TPU v5 lite"}


def test_readers_on_a_worked_epoch_record():
    read = {n: harness._load_module("metrics", n).read(_ctx(EPOCH))
            for n in READERS}
    assert read["trainer_step_ms_p50"] == 199.5
    assert read["trainer_data_wait_share"] == pytest.approx(1.0)
    assert read["trainer_dispatch_share"] == pytest.approx(5.0)
    # 32 steps of 64 rows: 32 * 18.55 TFLOP in 6.4 s of 197 TFLOP/s
    flops = counts.encoder_train_flops(2048, 512, 768, 12, 3072, 2)
    assert read["train_step_mfu"] == pytest.approx(
        100 * flops / 197e12 / 6.4)
    assert 46 < read["train_step_mfu"] < 48


@pytest.mark.parametrize("name", READERS)
def test_reader_reads_nothing_without_its_record(name):
    reader = harness._load_module("metrics", name)
    assert reader.read(_ctx(None)) is None
    booster = {"entry": types.SimpleNamespace(spans=[]), "config": {},
               "chips": 1, "traced_s": 1.0, "device_kind": "TPU v5 lite"}
    assert reader.read(booster) is None


@pytest.mark.parametrize("name,key", [
    ("trainer_step_ms_p50", "step_ms_p50"),
    ("trainer_data_wait_share", "data_wait_s"),
    ("trainer_dispatch_share", "dispatch_s")])
def test_span_reader_reads_nothing_without_its_key(name, key):
    reader = harness._load_module("metrics", name)
    without = {k: v for k, v in EPOCH.items() if k != key}
    assert reader.read(_ctx(without)) is None


# -- the reference against the program ------------------------------------------

@pytest.fixture(scope="module")
def small():
    _, _, config, traffic = _cell(TRAINER_CELLS[0])
    ref_mod = harness._load_module("references", "bert_base_ft")
    return config, traffic, ref_mod


def _program_model(config, dtype):
    import jax.numpy as jnp
    from synapseml_tpu.dl.text import TransformerEncoder

    return TransformerEncoder(
        vocab_size=config["vocab_size"],
        num_layers=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        hidden=config["hidden_size"],
        max_len=config["max_position_embeddings"], num_classes=2,
        dtype=jnp.dtype(dtype))


def test_reference_tokenizes_as_the_program_does(small):
    from synapseml_tpu.dl.text import hash_tokenize

    config, traffic, ref_mod = small
    made, _ = texts.make(traffic["texts"], 5)
    made[0] = "It's 9 o'clock -- Don't PANIC, " + made[0]
    ours = ref_mod.tokenize(made, config["vocab_size"],
                            config["max_position_embeddings"])
    assert (ours == hash_tokenize(made, config["vocab_size"],
                                  config["max_position_embeddings"])).all()
    assert (ours[:, 0] == 1).all() and (ours == 0).any()


def test_reference_initial_parameters_are_the_programs(small):
    import jax
    import jax.numpy as jnp

    config, _, ref_mod = small
    ours = ref_mod.flatten(ref_mod.initial_parameters(config, 77, 2))
    theirs = ref_mod.flatten(_program_model(config, "float32").init(
        jax.random.PRNGKey(77), jnp.zeros((1, 8), jnp.int32),
        train=False)["params"])
    assert set(ours) == set(theirs)
    for k in ours:
        assert ours[k].shape == theirs[k].shape
        assert (np.asarray(ours[k]) == np.asarray(theirs[k])).all(), k


def test_reference_forward_and_gradient_match_the_program_in_float32(small):
    """Dropout masks included: the program's model under a dropout key
    against the plain function under the masks the reference draws."""
    import jax
    import jax.numpy as jnp

    config, traffic, ref_mod = small
    made, labels = texts.make(traffic["texts"], 9)
    ids = jnp.asarray(ref_mod.tokenize(
        made[:8], config["vocab_size"], config["max_position_embeddings"]))
    y = jnp.asarray(labels[:8], jnp.int32)
    params = ref_mod.initial_parameters(config, 3, 2)
    model = _program_model(config, "float32")
    seed, step = 3, 2
    key = jax.random.fold_in(jax.random.PRNGKey(seed), step)
    keep = ref_mod.keep_masks(seed, step, config["num_hidden_layers"],
                              ids.shape[1])
    assert 0.8 < float(keep.mean()) < 0.97 and not bool(keep.all())

    def theirs(p):
        logits = model.apply({"params": p}, ids, train=True,
                             rngs={"dropout": key})
        logp = jax.nn.log_softmax(logits)
        return -jnp.take_along_axis(logp, y[:, None], -1).sum()

    def ours(p):
        return ref_mod.loss_sum(p, ids, y, keep, config, "float32", True)

    with jax.default_matmul_precision("highest"):
        lt, gt = jax.value_and_grad(theirs)(params)
        lo, go = jax.value_and_grad(ours)(params)
        no_dropout = ref_mod.loss_sum(params, ids, y, jnp.ones_like(keep),
                                      config, "float32", True)
    assert float(lo) == pytest.approx(float(lt), rel=2e-5)
    assert abs(float(no_dropout) - float(lt)) > 1e-3 * float(lt)
    gt, go = ref_mod.flatten(gt), ref_mod.flatten(go)
    for k in gt:
        scale = max(float(jnp.linalg.norm(gt[k])), 1e-6)
        assert float(jnp.linalg.norm(go[k] - gt[k])) <= 2e-4 * scale + 1e-6, k


def test_reference_adam_is_optax_adamw(small):
    """One leaf, three steps, by optax and by the reference's formula."""
    import jax.numpy as jnp
    import optax

    _, _, ref_mod = small
    a = ref_mod.ADAM
    tx = optax.adamw(1e-4, weight_decay=a["weight_decay"])
    p = jnp.asarray([0.3, -0.2, 0.05], jnp.float32)
    state = tx.init(p)
    q, mu, nu = p, jnp.zeros(3), jnp.zeros(3)
    for step, g in enumerate(([1.0, -2.0, 0.0], [0.5, 0.1, 1e-3],
                              [-1.0, 2.0, 3.0])):
        g = jnp.asarray(g, jnp.float32)
        u, state = tx.update(g, state, p)
        p = optax.apply_updates(p, u)
        mu = a["b1"] * mu + (1 - a["b1"]) * g
        nu = a["b2"] * nu + (1 - a["b2"]) * g * g
        c1, c2 = 1 - a["b1"] ** (step + 1), 1 - a["b2"] ** (step + 1)
        q = q - 1e-4 * ((mu / c1) / (jnp.sqrt(nu / c2) + a["eps"])
                        + a["weight_decay"] * q)
    assert np.allclose(np.asarray(p), np.asarray(q), rtol=0, atol=1e-9)


# -- the harness on the cell ------------------------------------------------------

@pytest.mark.parametrize("workload", TRAINER_CELLS)
def test_every_trainer_metric_is_read_from_the_recorded_trace(capsys,
                                                              workload):
    rc = harness.main(["--workload", workload, "--seed", "2147483659",
                       "--seconds", "0.5", "--trace", "1", "--rehearsal"])
    assert rc == harness.REHEARSAL_EXIT
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True
    assert line["compared"]["window_compiles"]["value"] == 0
    want = {m["name"] for m in BENCH["per_layer"]
            if harness._applies(m, workload, {"setup_s",
                                              "train_samples_per_s_chip"})}
    assert {"trainer_step_ms_p50", "trainer_data_wait_share",
            "trainer_dispatch_share", "device_idle_share.trainer",
            "compile_s"} <= want
    # the dense encoder's count is read only where its entry lists the cell
    listed = {m["name"]: m for m in BENCH["per_layer"]}["train_step_mfu"]
    assert ("train_step_mfu" in want) == (workload in listed["workloads"])
    assert set(line["metrics"]) == want
    assert 0 <= line["metrics"]["device_idle_share.trainer"]["value"] < 100


# -- bert_base_fit's limits against what the chip read ------------------------

# the compared numbers of the program on every seed of the sweep, and of the
# control and the planted faults on the seeds they were read on (PERF.md
# section 4)
BERT_READ = harness._load_json(harness.HERE, "data",
                               "readings_bert_base_fit.json")["seeds"]
# each stand-in, and the number that is there to catch it
BERT_CAUGHT_BY = {"control": "grad_difference", "half_batch": "change_gap",
                  "mask_dropped": "grad_gap", "moment_stale": "change_gap",
                  "state_unchanged": "change_gap"}


@pytest.mark.parametrize("seed", sorted(BERT_READ, key=int))
def test_every_recorded_bert_sound_run_lies_well_under_every_limit(seed):
    limits = _cell("bert_base_fit", False)[2]["limits"]
    numbers = BERT_READ[seed]["program"]
    assert set(numbers) == set(limits)
    over = {k: v for k, v in numbers.items() if v > limits[k] / 1.5}
    assert not over, over


@pytest.mark.parametrize("seed, who", [
    (int(seed), who) for seed in sorted(BERT_READ, key=int)
    for who in sorted(BERT_READ[seed]) if who != "program"])
def test_recorded_bert_stand_in_is_caught_by_its_number(seed, who):
    limits = _cell("bert_base_fit", False)[2]["limits"]
    caught = BERT_CAUGHT_BY[who]
    assert BERT_READ[str(seed)][who][caught] >= 1.2 * limits[caught]
