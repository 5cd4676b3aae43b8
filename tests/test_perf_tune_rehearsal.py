"""End-to-end rehearsal of the perf_tune tune -> flip -> persist pipeline.

tools/perf_tune.py lands its measurements through an atexit handler; a bug
there was historically only discovered on the chip (a NameError at
interpreter shutdown lost a whole run's results, 2026-08-02). These
tests run the real script as a subprocess on CPU in rehearsal mode
(PERF_TUNE_REHEARSAL=1: tiny data, 1-rep timings, trimmed variants, flip
allowed off-chip) so the entire shutdown path — raw-results write, winner
selection, tuned-defaults flip — is exercised by CI instead.

Marked slow: excluded from tier-1 (-m 'not slow'); ci.sh runs it in a
dedicated step.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "tools", "perf_tune.py")


def _run(tmp_path, extra_env=None, timeout=420):
    tuned_path = os.path.join(str(tmp_path), "tuned.json")
    results_path = os.path.join(str(tmp_path), "results.json")
    env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "PERF_TUNE_REHEARSAL": "1",
        "SYNAPSEML_TPU_TUNED_DEFAULTS": tuned_path,
        "PERF_TUNE_RESULTS_PATH": results_path,
        "PERF_TUNE_BUDGET_S": "360",
        **(extra_env or {}),
    }
    proc = subprocess.run([sys.executable, SCRIPT], env=env, cwd=REPO,
                          capture_output=True, text=True, timeout=timeout)
    return proc, tuned_path, results_path


@pytest.mark.slow
def test_full_tune_flip_persist(tmp_path):
    rows_path = os.path.join(str(tmp_path), "rows.jsonl")
    proc, tuned_path, results_path = _run(
        tmp_path, extra_env={"SYNAPSEML_TPU_PERF_ROWS": rows_path})
    assert proc.returncode == 0, proc.stderr[-2000:]

    # phase B journaled its kernel-variant sweep as perf-model rows, in the
    # arm vocabulary suggest_kernel_variant consumes
    with open(rows_path) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    kernel_rows = [r for r in rows if r.get("kind") == "gbdt_kernel"]
    assert kernel_rows, "phase B journaled no gbdt_kernel rows"
    arms = {r["arm"] for r in kernel_rows}
    assert "partition_sort" in arms and "masked" in arms
    assert all(r["observed_s"] > 0 for r in kernel_rows)

    # raw results landed and cover the phases that can run on CPU
    with open(results_path) as f:
        results = json.load(f)
    assert results["phase_a_ms_per_tree"], "phase A measured nothing"
    assert results["phase_b_train25_row_iters"], "phase B measured nothing"
    assert results["platform"] == "cpu"
    assert results["captured_at"]

    # the flip landed at the operator-set path and the reader accepts it
    assert os.path.exists(tuned_path), proc.stdout[-2000:]
    from synapseml_tpu.core import tuned

    vals = tuned.current_file_values(path=tuned_path)
    assert vals, "tuned file present but no validated values survived"
    assert "row_layout" in vals or "partition_impl" in vals
    with open(tuned_path) as f:
        raw = json.load(f)
    prov = raw["provenance"]
    assert prov["source"] == "tools/perf_tune.py"
    assert prov["winner"] in results["phase_b_train25_row_iters"]
    assert "TUNED DEFAULTS FLIPPED" in proc.stdout


@pytest.mark.slow
def test_short_budget_falls_back_to_phase_a(tmp_path):
    # a budget that only admits phase A (guards skip below 90 s left): the
    # flip must still land, decided by the phase-A fallback scores
    proc, tuned_path, results_path = _run(
        tmp_path, extra_env={"PERF_TUNE_BUDGET_S": "100",
                             "PERF_TUNE_ROWS": "1024"})
    assert proc.returncode == 0, proc.stderr[-2000:]
    with open(results_path) as f:
        results = json.load(f)
    assert results["phase_a_ms_per_tree"]
    assert os.path.exists(tuned_path), proc.stdout[-2000:]
    with open(tuned_path) as f:
        prov = json.load(f)["provenance"]
    if not results["phase_b_train25_row_iters"]:
        assert prov["decided_by"] == "phase A ms/tree (B never ran)"


@pytest.mark.slow
def test_flip_failure_never_loses_raw_results(tmp_path):
    # point the tuned-defaults path INTO A DIRECTORY THAT CANNOT BE CREATED
    # (a path component is a regular file): the flip write fails, but the
    # raw-results write must already have landed and the exit stays clean —
    # the exact hazard the atexit hardening exists for
    blocker = os.path.join(str(tmp_path), "blocker")
    with open(blocker, "w") as f:
        f.write("not a directory\n")
    bad_tuned = os.path.join(blocker, "nested", "tuned.json")
    proc, _, results_path = _run(
        tmp_path, extra_env={"SYNAPSEML_TPU_TUNED_DEFAULTS": bad_tuned,
                             "PERF_TUNE_BUDGET_S": "100",
                             "PERF_TUNE_ROWS": "1024"})
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert os.path.exists(results_path)
    with open(results_path) as f:
        assert json.load(f)["phase_a_ms_per_tree"]
    assert not os.path.exists(bad_tuned)
    assert "flip failed" in proc.stderr or "flip\nfailed" in proc.stderr or \
        "tuned-defaults flip" in proc.stderr
