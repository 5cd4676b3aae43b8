"""The readers of the program's own spans (PR 25): each on a hand-made
``trainingMeasures`` record with a worked value, nothing where its key is
absent, and all of them on the traced rehearsal's line."""

import json
import types

import pytest

from benchmark import run as harness

# one fit of 30 s: the top-level spans cover 29.7 s of it
SPANS = {
    "tablePreparation": 0.06,
    "referenceDataset": 1.35,
    "dataPreparation": 9.0,
    "dataPreparation/copyToDevice": 0.25,
    "dataPreparation/binning": 8.7,
    "objectiveSetup": 0.04,
    "trainingIterations": 19.2,
    "trainingIterations/scanRun": 19.15,
    "trainingIterations/treesReadback": 0.045,
    "modelAssembly": 0.05,
    "count:iterations": 8,
}
WORKED = {
    "booster_bin_boundaries_ms": ("referenceDataset", 1350.0),
    "booster_h2d_ms": ("dataPreparation/copyToDevice", 250.0),
    "booster_binning_ms": ("dataPreparation/binning", 8700.0),
    "booster_readback_ms": ("trainingIterations/treesReadback", 45.0),
    # 100 * (30 - (0.06 + 1.35 + 9.0 + 0.04 + 19.2 + 0.05)) / 30
    "booster_unaccounted_share": ("objectiveSetup", 1.0),
}


def _ctx(spans, wall=30.0):
    entry = types.SimpleNamespace(spans=[spans] if spans is not None else [],
                                  fit_seconds=[wall])
    return {"entry": entry}


@pytest.mark.parametrize("name", sorted(WORKED))
def test_reader_on_a_worked_record(name):
    reader = harness._load_module("metrics", name)
    assert reader.read(_ctx(SPANS)) == pytest.approx(WORKED[name][1])


@pytest.mark.parametrize("name", sorted(WORKED))
def test_reader_reads_nothing_without_its_key(name):
    reader = harness._load_module("metrics", name)
    without = {k: v for k, v in SPANS.items() if k != WORKED[name][0]}
    assert reader.read(_ctx(without)) is None
    assert reader.read(_ctx({})) is None
    assert reader.read(_ctx(None)) is None


def test_the_parents_record_reads_no_unaccounted_share():
    """PR 24's program has four sums and no children: the share is left
    out of its line, the bin boundaries are read."""
    parent = {"referenceDataset": 1.35, "dataPreparation": 0.005,
              "trainingIterations": 20.7, "count:iterations": 8}
    read = lambda n: harness._load_module("metrics", n).read(_ctx(parent))
    assert read("booster_unaccounted_share") is None
    assert read("booster_h2d_ms") is None
    assert read("booster_bin_boundaries_ms") == pytest.approx(1350.0)


def test_entries_in_benchmark_json():
    bench = harness._load_json(harness.ROOT, "BENCHMARK.json")
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in WORKED:
        m = by_name[name]
        assert m["source"] == "program_span"
        assert m["moves"] == "train_row_iters_per_s_chip"
        assert m["workloads"] == ["higgs_fit"]


def test_traced_rehearsal_lists_the_span_metrics(capsys):
    rc = harness.main(["--workload", "higgs_fit", "--seed", "78",
                       "--seconds", "0.5", "--trace", "1", "--rehearsal"])
    assert rc == harness.REHEARSAL_EXIT
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert set(WORKED) <= set(line["metrics"])
    for name in WORKED:
        assert line["metrics"][name]["value"] >= 0
    # the rehearsal's fit is small and on the CPU: no device number, but the
    # spans have to cover the call all the same
    assert line["metrics"]["booster_unaccounted_share"]["value"] < 10
