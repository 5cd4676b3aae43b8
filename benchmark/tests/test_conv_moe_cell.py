"""The short-convolution / expert backbone's cell: its configuration's file,
its count function by hand, its readers, and ``correct`` shown to fail: the
control and every planted fault at the rehearsal's size in the reference, the
new mechanisms broken underneath the estimator in a run driven through the
harness, and the readings recorded on the chip held to the limits."""

import json
import types

import pytest

from benchmark import counts_conv_moe
from benchmark import run as harness

CELL = "lfm2_24b_a2b_fit"
BENCH = harness._load_json(harness.ROOT, "BENCHMARK.json")
READERS = ("train_step_mfu.conv_moe", "moe_pairs_per_token.conv_moe",
           "moe_expert_load_max_over_mean.conv_moe",
           "trainer_pad_token_share.conv_moe")
# the trainer's record of one epoch of 32 steps of 2 rows of 8,192 positions
EPOCH = {"epoch": 1, "steps": 32, "seconds": 14.0, "step_ms_p50": 437.0,
         "counters": {"tokens": 524288, "padTokens": 131072,
                      "routedPairs": 1048576,
                      "expertTokens": [[32768] * 8, [65536, 0] + [32768] * 6,
                                       [32768] * 8, [32768] * 8]}}


def _config(rehearsal=False):
    return harness.load_cell(CELL, rehearsal)[2]


def _ctx(epoch):
    entry = types.SimpleNamespace(traced_epoch=lambda: epoch, batch=2,
                                  classes=2)
    return {"entry": entry, "config": _config(), "chips": 1, "traced_s": 14.0,
            "device_kind": "TPU v5 lite"}


# -- the configuration's file ---------------------------------------------------

@pytest.mark.parametrize("rehearsal", [False, True])
def test_the_estimator_reads_what_the_file_states(rehearsal):
    config = _config(rehearsal)
    arch = config["estimator"]["architecture"]
    for key, value in arch.items():
        if key == "num_experts":
            assert value == config["router_experts"]
        elif key == "layer_types":
            assert value == config[key][:config["num_hidden_layers"]]
        elif key not in ("held_experts", "expert_bias_std"):
            assert config[key] == value, key
    assert arch["held_experts"] == config["held_experts"]
    assert config["num_experts"] == len(config["held_experts"])


def test_the_cut_is_what_reduced_lists():
    config = _config()
    assert config["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size", "max_position_embeddings"]
    assert config["published"] == {"num_hidden_layers": 40, "num_experts": 64,
                                   "vocab_size": 65536,
                                   "max_position_embeddings": 128000}
    # the published layer list, whole; this chip holds its first six layers
    assert len(config["layer_types"]) == 40
    assert config["held_layers"] == list(range(config["num_hidden_layers"]))
    assert config["layer_types"][:6] == ["conv", "conv", "full_attention",
                                         "conv", "conv", "conv"]
    assert config["router_experts"] == 64 == 8 * config["num_experts"]
    assert config["expert_parallel_chips"] == 8
    assert config["vocab_size"] * 8 == config["published"]["vocab_size"]
    # no width is cut
    assert (config["hidden_size"], config["intermediate_size"],
            config["moe_intermediate_size"], config["num_attention_heads"],
            config["num_key_value_heads"], config["conv_L_cache"],
            config["num_experts_per_tok"]) == (2048, 11776, 1536, 32, 8, 3, 4)


def _parameters(c, experts, vocab, layers):
    h, hd = c["hidden_size"], c["hidden_size"] // c["num_attention_heads"]
    conv = h + 3 * h * h + c["conv_L_cache"] * h + h * h
    attention = h + 2 * h * h + 2 * h * hd * c["num_key_value_heads"] + 2 * hd
    dense = h + 3 * h * c["intermediate_size"]
    moe = (h + h * c["router_experts"] + c["router_experts"]
           + experts * 3 * h * c["moe_intermediate_size"])
    ops = {"conv": conv, "full_attention": attention}
    return (sum(ops[t] + (dense if i < c["num_dense_layers"] else moe)
                for i, t in enumerate(layers))
            + vocab * h + h + 2 * h)


def test_parameters_held_by_hand():
    c = _config()
    held = _parameters(c, c["num_experts"], c["vocab_size"],
                       c["layer_types"][:c["num_hidden_layers"]])
    assert held == 558_428_544                   # x 16 B = 8.93 GB
    # the whole model: 64 experts, the whole vocabulary, 40 layers, the
    # head over the vocabulary tied to the embedding
    whole = _parameters(c, 64, 65536, c["layer_types"])
    assert round(whole / 1e9, 2) == 23.84


# -- the count, by hand -----------------------------------------------------------

def test_forward_flops_of_every_kind_by_hand():
    # conv: 2 x (2048 x 6144 + 2048 x 2048)
    assert counts_conv_moe.conv_forward_flops_per_token(2048) == 33_554_432
    # attention: 2 x 2048 x 64 x (2 x 32 + 2 x 8), and 4,096 earlier keys x
    # 4 x 32 x 64
    assert counts_conv_moe.attention_forward_flops_per_token(
        2048, 32, 8, 8192) == 20_971_520 + 33_554_432
    # dense: 2 x 3 x 2048 x 11776
    assert counts_conv_moe.dense_forward_flops_per_token(
        2048, 11776) == 144_703_488
    # experts: router 2 x 2048 x 64; half a pair a token x 2 x 3 x 2048 x 1536
    assert counts_conv_moe.experts_forward_flops_per_token(
        2048, 64, 1536, 0.5) == 262_144 + 9_437_184
    # five conv, two dense, one attention, four expert sublayers
    assert counts_conv_moe.sublayers(_config()) == [
        "conv", "dense", "conv", "dense", "attention", "experts", "conv",
        "experts", "conv", "experts", "conv", "experts"]
    assert counts_conv_moe.conv_moe_forward_flops_per_token(
        _config(), 8192, 0.5) == (5 * 33_554_432 + 2 * 144_703_488
                                  + 54_525_952 + 4 * 9_699_328)
    assert round(counts_conv_moe.conv_moe_forward_flops_per_token(
        _config(), 8192, 0.5) / 1e6, 1) == 550.5


def test_train_flops_take_the_pairs_from_the_counter():
    config = _config()
    even = counts_conv_moe.conv_moe_train_flops(config, 2, 8192,
                                                4 * 8192, 2)
    assert even == 3 * (16384 * 550_502_400 + 2 * 2 * 2048 * 2)
    assert round(even / 1e12, 1) == 27.1
    more = counts_conv_moe.conv_moe_train_flops(config, 2, 8192,
                                                4 * 8192 + 1000, 2)
    assert more - even == pytest.approx(3 * 1000 * 6 * 2048 * 1536)


# -- the readers ---------------------------------------------------------------------

def test_readers_on_a_worked_epoch_record():
    read = {n: harness._load_module("metrics", n).read(_ctx(EPOCH))
            for n in READERS}
    assert read["moe_pairs_per_token.conv_moe"] == pytest.approx(0.5)
    assert read["moe_expert_load_max_over_mean.conv_moe"] == pytest.approx(2.0)
    assert read["trainer_pad_token_share.conv_moe"] == pytest.approx(25.0)
    # 64 rows: 32 steps x 27.1 TFLOP in 14 s of 197 TFLOP/s
    flops = counts_conv_moe.conv_moe_train_flops(_config(), 64, 8192,
                                                 1048576, 2)
    assert read["train_step_mfu.conv_moe"] == pytest.approx(
        100 * flops / 197e12 / 14.0)
    assert 31 < read["train_step_mfu.conv_moe"] < 32


@pytest.mark.parametrize("name", READERS)
def test_reader_reads_nothing_without_its_counters(name):
    reader = harness._load_module("metrics", name)
    assert reader.read(_ctx(None)) is None
    # the parent's record: an epoch without counters
    assert reader.read(_ctx({k: v for k, v in EPOCH.items()
                             if k != "counters"})) is None


def test_the_metrics_read_in_the_cell():
    reported = {"setup_s", "train_samples_per_s_chip"}
    want = {m["name"] for m in BENCH["per_layer"]
            if harness._applies(m, CELL, reported)}
    assert want == set(READERS) | {
        "compile_s", "trainer_step_ms_p50", "trainer_data_wait_share",
        "trainer_dispatch_share", "device_idle_share.trainer"}
    rate = {m["name"]: m for m in BENCH["end_to_end"]}[
        "train_samples_per_s_chip"]
    assert CELL in rate["workloads"]


# -- the harness on the cell -------------------------------------------------------

def _run(capsys, trace=0):
    rc = harness.main(["--workload", CELL, "--seed", "2147483659",
                       "--seconds", "0.3", "--trace", str(trace),
                       "--rehearsal"])
    out = capsys.readouterr()
    return rc, json.loads(out.out.strip().splitlines()[-1]), out


def test_traced_rehearsal_prints_every_metric_of_the_cell(capsys):
    rc, line, out = _run(capsys, trace=1)
    assert rc == harness.REHEARSAL_EXIT
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["compared"]) == set(_config()["limits"]) | {
        "window_compiles"}
    assert "compile events in the window: 0" in out.out
    assert set(READERS) | {"trainer_step_ms_p50", "trainer_data_wait_share",
                           "trainer_dispatch_share",
                           "device_idle_share.trainer",
                           "compile_s"} == set(line["metrics"])
    assert 0 < line["metrics"]["moe_pairs_per_token.conv_moe"]["value"] <= 4


# the control and every fault the reference plants, each a case of its own
STAND_INS = ("control", "bias_in_weights", "bias_dropped", "conv_ungated",
             "conv_acausal", "rope_dropped", "qk_norm_dropped",
             "experts_unrouted", "half_batch", "moment_stale",
             "state_unchanged")


@pytest.fixture(scope="module")
def rehearsed():
    """One rehearsal fit through the entry, and the reference of its seed."""
    _, _, config, traffic = harness.load_cell(CELL, True)
    ref_mod = harness._load_module("references", "lfm2_24b_a2b_ft")
    entry = harness._load_module("entries", "trainer_fit").Entry(
        config, traffic, 2147483659, 1)
    entry.setup()
    entry.unit()
    inputs = entry.check_inputs()
    entry.release()
    ref = ref_mod.Reference(config, inputs["texts"], inputs["labels"],
                            inputs["seed"], inputs["batch"], inputs["steps"])
    return config, inputs, ref_mod, ref


def test_the_rehearsals_sound_run_is_correct(rehearsed):
    config, inputs, ref_mod, ref = rehearsed
    limits = config["limits"]
    sound = ref_mod.check(config, inputs, reference=ref)
    assert set(sound) == set(limits)
    assert all(sound[k] <= limits[k] for k in sound), sound
    assert ref_mod.stand_in_plans(_config())["control"] == {
        "value_type": "float8_e4m3fn"}
    assert ref_mod.stand_in_plans(config)["control"] == {
        "value_type": "bfloat16"}
    assert set(ref_mod.stand_in_plans(config)) == set(STAND_INS)


@pytest.mark.parametrize("name", STAND_INS)
def test_control_and_stand_ins_are_not_correct(rehearsed, name):
    """The reference with the step-down precision in the program's place, or
    with one fault planted, at the rehearsal's size."""
    config, inputs, ref_mod, ref = rehearsed
    limits = config["limits"]
    numbers = ref_mod.check(config, inputs,
                            ref_mod.stand_in_plans(config)[name],
                            reference=ref)
    assert any(numbers[k] > limits[k] for k in numbers), numbers


def _break_backbone(monkeypatch, how):
    """Break one mechanism of the backbone underneath the estimator."""
    import jax.numpy as jnp

    from synapseml_tpu.dl import hybrid

    real_route, real_conv = hybrid.route, hybrid._causal_depthwise_conv
    if how == "bias_dropped":
        monkeypatch.setattr(hybrid, "route",
                            lambda x, r, arch, bias=None: real_route(x, r,
                                                                     arch))
    elif how == "rope_dropped":
        monkeypatch.setattr(hybrid, "rotary", lambda x, theta: x)
    elif how == "conv_acausal":
        monkeypatch.setattr(
            hybrid, "_causal_depthwise_conv",
            lambda x, kernel, bias=None: real_conv(
                jnp.pad(x[:, 1:], ((0, 0), (0, 1), (0, 0))), kernel, bias))
    elif how == "experts_unrouted":
        monkeypatch.setattr(
            hybrid, "held_experts_part",
            lambda x, ids, w, up, down, held, experts: (
                jnp.zeros(x.shape, jnp.float32),
                jnp.zeros(len(held), jnp.int32)))


@pytest.mark.parametrize("how", ["bias_dropped", "rope_dropped",
                                 "conv_acausal", "experts_unrouted"])
def test_fault_planted_in_the_backbone_is_not_correct(capsys, monkeypatch,
                                                      how):
    _break_backbone(monkeypatch, how)
    _, line, _ = _run(capsys)
    assert line["correct"] is False
    assert line["compared"]["step_count_gap"]["value"] == 0


# -- the committed limits on readings recorded on the chip ----------------------

# what the readings read on the chip (PERF.md section 4): for each seed the
# reference's per-leaf rows' norms, and for the program and for each stand-in
# read on that seed the loss gaps and per-leaf norms of the first gradient and
# of the change, as the reference's ``judge`` takes them
RECORDED = harness._load_json(harness.HERE, "data",
                              "readings_lfm2_24b_a2b_fit.json")["seeds"]
# each stand-in, and the number that is there to catch it
CAUGHT_BY = harness._load_json(harness.HERE, "data",
                               "readings_lfm2_24b_a2b_fit.json")["caught_by"]
# a sound reading lies this far under every limit, a stand-in this far over
# the limit of the number that is there to catch it
ROOM_BELOW, ROOM_ABOVE = 1.5, 1.2


def _judged(seed, who):
    ref_mod = harness._load_module("references", "lfm2_24b_a2b_ft")
    r = RECORDED[str(seed)]["readings"][who]
    return ref_mod.judge(_config(), r["loss_gaps"], r["grad"], r["change"],
                         RECORDED[str(seed)]["rows_norms"], candidates=True)


def test_at_least_eight_seeds_are_recorded():
    assert len(RECORDED) >= 8


@pytest.mark.parametrize("seed", sorted(RECORDED, key=int))
def test_every_recorded_sound_run_lies_well_under_every_limit(seed):
    limits = _config()["limits"]
    numbers = _judged(seed, "program")
    over = {k: numbers[k] for k in limits
            if k in numbers and numbers[k] > limits[k] / ROOM_BELOW}
    assert not over, over


@pytest.mark.parametrize("seed, who", [
    (int(seed), who) for seed in sorted(RECORDED, key=int)
    for who in sorted(RECORDED[seed]["readings"]) if who != "program"])
def test_recorded_stand_in_is_caught_by_its_number(seed, who):
    limits = _config()["limits"]
    numbers = _judged(seed, who)
    caught = CAUGHT_BY[who]
    assert numbers[caught] >= ROOM_ABOVE * limits[caught], (caught,
                                                           numbers[caught])


# the float8 control is the upper reading of every number on which it reads at
# least this many times the largest sound reading; there the limit lies
# between the two
CONTROL_OVER_SOUND = 3.0


@pytest.mark.parametrize("seed", [int(s) for s in sorted(RECORDED, key=int)
                                  if "control" in RECORDED[s]["readings"]])
def test_recorded_control_is_caught_by_every_number_it_moves(seed):
    limits = _config()["limits"]
    control = _judged(seed, "control")
    sound = {k: max(_judged(s, "program")[k] for s in RECORDED)
             for k in limits if k in control}
    moved = [k for k in sound
             if control[k] >= CONTROL_OVER_SOUND * sound[k] > 0]
    assert {"first_loss_gap", "grad_gap", "grad_difference"} <= set(moved)
    under = {k: (control[k], limits[k]) for k in moved
             if control[k] < ROOM_ABOVE * limits[k]}
    assert not under, under


def test_stand_ins_are_recorded_on_two_seeds():
    """Every stand-in on one seed; on a second, the three that lay nearest
    their limits on the first (PERF.md section 4)."""
    read = {s: set(RECORDED[s]["readings"]) - {"program"} for s in RECORDED}
    assert sorted(len(v) for v in read.values() if v) == [3, len(STAND_INS)]
    assert {"control", "bias_in_weights", "bias_dropped"} in read.values()
    assert set(STAND_INS) in read.values()
    assert set(CAUGHT_BY) == set(STAND_INS)
