"""The hybrid backbone's cell: its configuration's file, its count function
by hand, its readers, and ``correct`` shown to fail: the control and every
planted fault at the rehearsal's size, in the reference and (the four faults
this cell brings) under the estimator with a run driven through the harness."""

import dataclasses
import json
import types

import numpy as np
import pytest

from benchmark import counts_hybrid
from benchmark import run as harness

CELL = "nemotron3_nano_fit"
BENCH = harness._load_json(harness.ROOT, "BENCHMARK.json")
READERS = ("train_step_mfu.ssm_moe", "moe_pairs_per_token",
           "moe_expert_load_max_over_mean", "trainer_pad_token_share")
# the trainer's record of one epoch of 24 steps of 4 rows of 4,096 positions
EPOCH = {"epoch": 1, "steps": 24, "seconds": 12.0, "step_ms_p50": 499.0,
         "counters": {"tokens": 393216, "padTokens": 147456,
                      "routedPairs": 589824,
                      "expertTokens": [[18432] * 8, [36864, 0] + [18432] * 6,
                                       [18432] * 8, [18432] * 8]}}


def _config(rehearsal=False):
    return harness.load_cell(CELL, rehearsal)[2]


def _ctx(epoch):
    entry = types.SimpleNamespace(traced_epoch=lambda: epoch, batch=4,
                                  classes=2)
    return {"entry": entry, "config": _config(), "chips": 1, "traced_s": 12.0,
            "device_kind": "TPU v5 lite"}


# -- the configuration's file ---------------------------------------------------

@pytest.mark.parametrize("rehearsal", [False, True])
def test_the_estimator_reads_what_the_file_states(rehearsal):
    config = _config(rehearsal)
    arch = config["estimator"]["architecture"]
    for key, value in arch.items():
        if key == "n_routed_experts":
            assert value == config["router_experts"]
        else:
            assert config[key] == value, key
    assert config["n_routed_experts"] == len(config["held_experts"])
    assert len(config["hybrid_override_pattern"]) == config["num_hidden_layers"]


def test_the_cut_is_what_reduced_lists():
    config = _config()
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                 "vocab_size", "max_position_embeddings"]
    assert set(config["published"]) == set(config["reduced"]) | {
        "hybrid_override_pattern"}
    assert config["published"]["hybrid_override_pattern"].startswith(
        config["hybrid_override_pattern"])
    assert config["published"]["n_routed_experts"] == config[
        "router_experts"] == 16 * config["n_routed_experts"]
    assert config["expert_parallel_chips"] == 16


def test_parameters_held_by_hand():
    c = _config()
    h = c["hidden_size"]
    d_inner = c["mamba_num_heads"] * c["mamba_head_dim"]
    conv_dim = d_inner + 2 * c["n_groups"] * c["ssm_state_size"]
    mamba = (h + h * (d_inner + conv_dim + c["mamba_num_heads"])
             + (c["conv_kernel"] + 1) * conv_dim + 3 * c["mamba_num_heads"]
             + d_inner + d_inner * h)
    attention = h + h * c["head_dim"] * 2 * (
        c["num_attention_heads"] + c["num_key_value_heads"])
    experts = (h + h * c["router_experts"]
               + 2 * h * c["moe_shared_expert_intermediate_size"]
               + c["n_routed_experts"] * 2 * h * c["moe_intermediate_size"])
    kinds = {"M": mamba, "*": attention, "E": experts}
    total = (sum(kinds[k] for k in c["hybrid_override_pattern"])
             + c["vocab_size"] * h + h + 2 * h)
    assert round(mamba / 1e6, 2) == 38.74
    assert round(attention / 1e6, 2) == 23.40
    assert round(experts / 1e6, 2) == 100.13
    assert total == 622_928_128                 # x 16 B = 9.97 GB


# -- the count, by hand -----------------------------------------------------------

def test_forward_flops_of_every_kind_by_hand():
    # M: 2 x (2688 x 10304 + 4096 x 2688) for the projections; inside a chunk
    # 64 earlier positions x (8 groups x 2 x 128 + 64 heads x 2 x 64); the
    # state written and read, 2 x (2 x 64 x 64 x 128)
    assert counts_hybrid.mamba_forward_flops_per_token(
        2688, 64, 64, 8, 128, 128) == 77_414_400 + 655_360 + 2_097_152
    # *: 2 x 2688 x 128 x (2 x 32 + 2 x 2), and 2,048 earlier keys x 4 x 4096
    assert counts_hybrid.attention_forward_flops_per_token(
        2688, 32, 2, 128, 4096) == 46_792_704 + 33_554_432
    # E: router 2 x 2688 x 128; shared 4 x 2688 x 3712; 0.375 pairs a token
    assert counts_hybrid.experts_forward_flops_per_token(
        2688, 128, 1856, 3712, 0.375) == 688_128 + 39_911_424 + 7_483_392
    assert counts_hybrid.hybrid_forward_flops_per_token(
        _config(), 4096, 0.375) == 593_346_560


def test_train_flops_take_the_pairs_from_the_counter():
    config = _config()
    even = counts_hybrid.hybrid_train_flops(config, 4, 4096, 4 * 6144, 2)
    assert even == 3 * (16384 * 593_346_560 + 4 * 2 * 2688 * 2)
    assert round(even / 1e12, 1) == 29.2
    more = counts_hybrid.hybrid_train_flops(config, 4, 4096, 4 * 6144 + 1000,
                                            2)
    assert more - even == pytest.approx(3 * 1000 * 4 * 2688 * 1856)


# -- the readers ---------------------------------------------------------------------

def test_readers_on_a_worked_epoch_record():
    read = {n: harness._load_module("metrics", n).read(_ctx(EPOCH))
            for n in READERS}
    assert read["moe_pairs_per_token"] == pytest.approx(0.375)
    assert read["moe_expert_load_max_over_mean"] == pytest.approx(2.0)
    assert read["trainer_pad_token_share"] == pytest.approx(37.5)
    # 96 rows: 24 x 29.17 TFLOP in 12 s of 197 TFLOP/s
    flops = counts_hybrid.hybrid_train_flops(_config(), 96, 4096, 589824, 2)
    assert read["train_step_mfu.ssm_moe"] == pytest.approx(
        100 * flops / 197e12 / 12.0)
    assert 29 < read["train_step_mfu.ssm_moe"] < 30


@pytest.mark.parametrize("name", READERS)
def test_reader_reads_nothing_without_its_counters(name):
    reader = harness._load_module("metrics", name)
    assert reader.read(_ctx(None)) is None
    # the parent's record: an epoch without counters
    assert reader.read(_ctx({k: v for k, v in EPOCH.items()
                             if k != "counters"})) is None
    booster = {"entry": types.SimpleNamespace(spans=[]), "config": {},
               "chips": 1, "traced_s": 1.0, "device_kind": "TPU v5 lite"}
    assert reader.read(booster) is None


def test_the_dense_encoders_count_is_read_in_its_own_cell_only():
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    assert by_name["train_step_mfu"]["workloads"] == ["bert_base_fit"]
    reported = {"setup_s", "train_samples_per_s_chip"}
    want = {m["name"] for m in BENCH["per_layer"]
            if harness._applies(m, CELL, reported)}
    assert want == set(READERS) | {
        "compile_s", "trainer_step_ms_p50", "trainer_data_wait_share",
        "trainer_dispatch_share", "device_idle_share.trainer"}


# -- the harness on the cell -------------------------------------------------------

def _run(capsys, trace=0):
    rc = harness.main(["--workload", CELL, "--seed", "2147483659",
                       "--seconds", "0.5", "--trace", str(trace),
                       "--rehearsal"])
    out = capsys.readouterr()
    return rc, json.loads(out.out.strip().splitlines()[-1]), out


def test_traced_rehearsal_prints_every_metric_of_the_cell(capsys):
    rc, line, out = _run(capsys, trace=1)
    assert rc == harness.REHEARSAL_EXIT
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["compared"]) == {
        "first_loss_gap", "grad_gap", "routed_gap", "grad_difference",
        "leaf_difference", "change_gap", "step_count_gap", "window_compiles"}
    assert "compile events in the window: 0" in out.out
    assert set(READERS) | {"trainer_step_ms_p50", "trainer_data_wait_share",
                           "trainer_dispatch_share",
                           "device_idle_share.trainer",
                           "compile_s"} == set(line["metrics"])
    assert 0 < line["metrics"]["moe_pairs_per_token"]["value"] <= 3
    assert 0 < line["metrics"]["trainer_pad_token_share"]["value"] < 100


def test_reference_tokenizes_as_the_program_does():
    from synapseml_tpu.dl.text import hash_tokenize

    ref = harness._load_module("references", "nemotron3_nano_ft")
    texts = ["One two, THREE", "it's " * 70, ""]
    assert np.array_equal(ref.tokenize(texts, 512, 48),
                          hash_tokenize(texts, 512, 48))


# the control and every fault the reference plants, each a case of its own
STAND_INS = ("control", "topk_altered", "scaling_dropped", "state_reset",
             "experts_unrouted", "mask_dropped", "half_batch", "moment_stale",
             "state_unchanged")


@pytest.fixture(scope="module")
def rehearsed():
    """One rehearsal fit through the entry, and the reference of its seed."""
    _, _, config, traffic = harness.load_cell(CELL, True)
    ref_mod = harness._load_module("references", "nemotron3_nano_ft")
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
    # the step below the stated precision: the cell states bfloat16, the
    # rehearsal float32 (its file says why)
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
    if name == "state_unchanged":
        assert numbers["change_gap"] > 0.99


# -- the committed form on readings recorded on the chip -----------------------

# what the sweep read on the chip (PERF.md section 4): for each seed the
# reference's per-leaf rows' norms, and for the program and for each stand-in
# read on that seed the loss gaps and per-leaf norms of the first gradient and
# of the change, as the reference's ``judge`` takes them
RECORDED = harness._load_json(harness.HERE, "data",
                              "readings_nemotron3_nano_fit.json")["seeds"]
# each stand-in, and the number that is there to catch it
CAUGHT_BY = {"control": "grad_gap", "topk_altered": "routed_gap",
             "scaling_dropped": "routed_gap", "state_reset": "leaf_difference",
             "experts_unrouted": "routed_gap", "mask_dropped": "first_loss_gap",
             "half_batch": "grad_difference", "moment_stale": "change_gap",
             "state_unchanged": "change_gap"}
# a sound reading lies this far under every limit, a stand-in this far over
# the limit of the number that is there to catch it
ROOM_BELOW, ROOM_ABOVE = 1.5, 1.2


def _judged(seed, who):
    ref_mod = harness._load_module("references", "nemotron3_nano_ft")
    r = RECORDED[str(seed)]["readings"][who]
    return ref_mod.judge(_config(), r["loss_gaps"], r["grad"], r["change"],
                         RECORDED[str(seed)]["rows_norms"], candidates=True)


def test_the_recorded_sound_run_of_seed_134403365_is_correct():
    """A sound run of the committed program whose worst gradient leaf (the
    last expert layer's shared expert) reads 0.0449, because its first
    batch's rows cancel in the mean (PERF.md section 4): the median leaf and
    the rows' norms are steady there."""
    limits = _config()["limits"]
    numbers = _judged(134403365, "program")
    assert all(numbers[k] <= limits[k] / ROOM_BELOW
               for k in limits if k in numbers)
    assert numbers["grad_gap_worst_leaf"] > 0.04
    assert numbers["where"]["grad_gap_worst_leaf"] == "layer_8/shared_down"
    assert numbers["gradient_norm"] < 0.2 * numbers["rows_norm"]


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


def test_stand_ins_are_recorded_on_two_seeds():
    read = [s for s in RECORDED if len(RECORDED[s]["readings"]) > 1]
    assert len(read) >= 2
    for s in read:
        assert set(RECORDED[s]["readings"]) == set(CAUGHT_BY) | {"program"}


def _break_backbone(monkeypatch, how):
    """Break one mechanism of the backbone underneath the estimator."""
    import jax.numpy as jnp

    from synapseml_tpu.dl import hybrid

    real_route, real_scan = hybrid.route, hybrid.ssd_chunked
    if how == "experts_unrouted":
        monkeypatch.setattr(
            hybrid, "held_experts_part",
            lambda x, ids, w, up, down, held, experts: (
                jnp.zeros(x.shape, jnp.float32),
                jnp.zeros(len(held), jnp.int32)))
    elif how == "topk_altered":
        monkeypatch.setattr(hybrid, "route", lambda x, r, arch: real_route(
            x, r, dataclasses.replace(arch, top_k=arch.top_k - 1)))
    elif how == "scaling_dropped":
        monkeypatch.setattr(hybrid, "route", lambda x, r, arch: real_route(
            x, r, dataclasses.replace(arch, scaling=1.0)))
    elif how == "state_reset":
        def every_chunk_alone(x, dt, a, b, c, chunk):
            rows, length = x.shape[:2]
            if length % chunk:       # initialisation: two positions
                return real_scan(x, dt, a, b, c, chunk)
            cut = lambda t: t.reshape((rows * length // chunk, chunk)
                                      + t.shape[2:])
            return real_scan(cut(x), cut(dt), a, cut(b), cut(c),
                             chunk).reshape(x.shape)

        monkeypatch.setattr(hybrid, "ssd_chunked", every_chunk_alone)


@pytest.mark.parametrize("how", ["experts_unrouted", "topk_altered",
                                 "state_reset", "scaling_dropped"])
def test_fault_planted_in_the_backbone_is_not_correct(capsys, monkeypatch,
                                                      how):
    _break_backbone(monkeypatch, how)
    _, line, _ = _run(capsys)
    assert line["correct"] is False
    assert line["compared"]["step_count_gap"]["value"] == 0
