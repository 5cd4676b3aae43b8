"""The harness is driven by data: every name in BENCHMARK.json leads to its
files, and nothing in the harness's code names a cell."""

import json
import os
import re

import pytest

from benchmark import run as harness

BENCH = harness._load_json(harness.ROOT, "BENCHMARK.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def _rate(workload: dict) -> str:
    return harness._load_json(harness.HERE, "traffic",
                              workload["traffic"] + ".json")["rate_metric"]


def test_every_cell_finds_its_files():
    for w in BENCH["workloads"]:
        cfg = harness._load_json(harness.HERE, "configs",
                                 w["config"] + ".json")
        traffic = harness._load_json(harness.HERE, "traffic",
                                     w["traffic"] + ".json")
        assert harness._load_module("entries", traffic["entry"]) is not None
        ref = harness._load_module("references", w["config"])
        assert hasattr(ref, "check")
        # what each entry's reference compares, beside the exact numbers
        compared = {"booster_fit": {"gain_gap", "leaf_gap", "loss_gap"},
                    "trainer_fit": {"grad_gap", "grad_difference",
                                    "change_gap", "step_count_gap"}}
        assert set(cfg["limits"]) >= compared[traffic["entry"]]
        # a loss: the worst judged step's, or the first step's
        assert any(k.endswith("loss_gap") for k in cfg["limits"])
        assert all(v >= 0 for v in cfg["limits"].values())
        assert os.path.exists(os.path.join(harness.HERE, "data",
                                           cfg["rehearsal_trace"]))
        assert traffic["rate_metric"] in {m["name"]
                                          for m in BENCH["end_to_end"]}


def test_every_per_layer_metric_has_a_reader():
    for m in BENCH["per_layer"]:
        assert hasattr(harness._load_module("metrics", m["name"]), "read")


def test_names_units_and_bounds():
    names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(names) == len(set(names))
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    cells = {w["name"] for w in BENCH["workloads"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        assert set(m.get("workloads", cells)) <= cells
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= max(
        1, len(BENCH["workloads"]) // 4)
    for c in BENCH["configs"]:
        held = harness._load_json(harness.ROOT, c["file"])
        assert set(c["reduced"]) == set(held["reduced"])


@pytest.mark.parametrize("values, far", [
    ([5.796, 5.807, 5.808, 5.793, 5.692, 5.789], 4),
    ([1.0, 1.1, 1.2, 1.3, 5.0], 4),
])
def test_rate_spread_is_the_checks_quartile_spread(values, far):
    """``benchmark.tools.rates`` reads a spread as the check does: the
    quartile distance of ``statistics.quantiles(n=4)`` over the median, and
    again with the run farthest from the median left out."""
    import statistics

    from benchmark.tools import rates

    def by_hand(v):
        q1, _, q3 = statistics.quantiles(v, n=4)
        return (q3 - q1) / statistics.median(v)

    out = rates.spreads(values)
    assert out["median"] == statistics.median(values)
    assert out["spread"] == pytest.approx(by_hand(values))
    rest = values[:far] + values[far + 1:]
    assert out["spread_without_farthest"] == pytest.approx(by_hand(rest))
    assert out["spread_without_farthest"] < out["spread"]


def test_no_cell_is_named_in_the_harness_code():
    words = {w["name"] for w in BENCH["workloads"]} | {
        c["name"] for c in BENCH["configs"]} | {
        w["traffic"] for w in BENCH["workloads"]}
    for name in ("run.py", "trace.py", "counts.py", "kernels.py",
                 "tables.py", "texts.py", "trainer_record.py", "peaks.py"):
        with open(os.path.join(harness.HERE, name)) as f:
            code = f.read()
        for w in words:
            assert w not in code, (name, w)


def test_applies():
    m = {"name": "x", "moves": "a", "workloads": ["c1"]}
    assert harness._applies(m, "c1", {"a"})
    assert not harness._applies(m, "c2", {"a"})
    assert harness._applies({"name": "y", "moves": "a"}, "c2", {"a", "b"})
    assert not harness._applies({"name": "y", "moves": "z"}, "c2", {"a"})
    bench, cell, config, traffic = harness.load_cell(
        BENCH["workloads"][0]["name"], rehearsal=True)
    assert config["numIterations"] == config["rehearsal"]["numIterations"]
    assert config["table"]["features"] == 28      # merged, not replaced
    # a per-layer metric without a list is read in every cell that reports
    # the end-to-end metric it moves, and in no other
    listless = [m for m in BENCH["per_layer"] if "workloads" not in m]
    for w in BENCH["workloads"]:
        rate = _rate(w)
        for m in listless:
            assert harness._applies(m, w["name"], {"setup_s", rate}) == (
                m["moves"] in ("setup_s", rate))


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_traced_rehearsal_reads_the_recorded_trace(capsys, workload):
    rc = harness.main(["--workload", workload, "--seed", "77", "--seconds",
                       "0.5", "--trace", "1", "--rehearsal"])
    assert rc == harness.REHEARSAL_EXIT
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert {"correct", "attempted", "failed", "metrics", "device",
            "breakdown", "compared"} <= set(line)
    per_layer = {m["name"] for m in BENCH["per_layer"]}
    assert set(line["metrics"]) <= per_layer and line["metrics"]
    assert "setup_s" not in line["metrics"]
    for v in line["metrics"].values():
        assert set(v) == {"value", "unit"}
    d = line["device"]
    assert d["platform"] == "cpu" and 0 < d["busy_s"] <= d["window_s"]
    assert len(line["breakdown"]["device_ops"]) <= 10


def test_every_cell_reports_setup_another_end_to_end_and_a_per_layer_metric():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    for w in BENCH["workloads"]:
        rate = _rate(w)
        assert w["name"] in e2e[rate].get("workloads", [w["name"]])
        assert any(harness._applies(m, w["name"], {"setup_s", rate})
                   and m["moves"] == rate for m in BENCH["per_layer"])
    # an end-to-end metric that exists only in some cells lists them
    assert {_rate(w) for w in BENCH["workloads"]} | {"setup_s"} == set(e2e)


def test_memory_peak_counts_arrays_and_the_programs_reservation():
    class Dev:
        def __init__(self, stats):
            self.stats = stats

        def memory_stats(self):
            return self.stats

    devs = [Dev({"peak_bytes_in_use": 10, "peak_bytes_reserved": 5}),
            Dev({"peak_bytes_in_use": 12}), Dev(None)]
    assert harness._memory_peak(devs) == 15
