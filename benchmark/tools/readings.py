"""The readings the limits are set from: ``benchmark.run --readings FILE``
runs a cell as always (the timed path at the timed size) and then, on the
same answer, reads the control (the reference in the next lower precision
put in the program's place) and the planted faults, and writes them with the
run's own numbers (the lower reading) to FILE. No benchmark run does this.

Where set-up is most of a run, the lower readings of many seeds are read in
one process, without a window (a training cell's judged steps lie in its
set-up):

    python3 -m benchmark.tools.readings --workload bert_base_fit \
        --seeds 11,12,13,14 --stand-ins 2 --out chiprun_out/readings.jsonl

reads the program on every seed and the control and the faults on the first
``--stand-ins`` of them, one JSON line a seed.
"""

from __future__ import annotations

import argparse
import copy
import json
import os
import sys


def stand_ins(ref_mod, config: dict, traffic: dict, inputs: dict,
              only=None) -> dict:
    """{name: numbers} of the control and the faults (``only``: of those
    named, none for an empty tuple)."""
    if hasattr(ref_mod, "stand_ins"):
        # a reference that brings its own control and faults
        return ref_mod.stand_ins(config, traffic, inputs, only)
    if only is not None:
        raise ValueError("this reference's stand-ins are read all or not")
    params = config["params"]
    value_type = params["histogram_values"]
    ref = ref_mod.Reference(params, inputs["X"], inputs["y"])
    judged, follow = inputs["judged"], inputs["follow"]
    rows = len(inputs["y"])
    shards = int((traffic.get("mesh") or {}).get("data", 1))
    out = {"control": ref.compare(judged, follow, {
        "value_type": ref_mod.LOWER[value_type]})}
    same = copy.deepcopy(judged)
    same["trees"] = [same["trees"][0]] * len(same["trees"])
    out["state_unchanged"] = ref.compare(same, follow)
    out["half_rows"] = ref.compare(judged, follow, {
        "value_type": value_type, "rows": (0, rows // 2)})
    if shards > 1:
        out["one_shard"] = ref.compare(judged, follow, {
            "value_type": value_type, "rows": (0, rows // shards)})
    nudged = copy.deepcopy(judged)
    nudged["trees"][-1]["leaf_value"][3] *= 1.01
    out["leaf_value_altered"] = ref.compare(nudged, follow)
    moved = copy.deepcopy(judged)
    t = moved["trees"][-1]
    b = ref.bounds[int(t["feature"][2])]
    at = int((b == t["threshold"][2]).nonzero()[0][0])
    last = int((b < float("inf")).sum()) - 1
    t["threshold"][2] = b[at + 8 if at + 8 <= last else at - 8]
    out["threshold_altered"] = ref.compare(moved, follow)
    return out


def nearest(numbers: dict, limits: dict) -> tuple:
    """(name, value, limit, value over limit, leaf) of the compared number
    nearest its limit, or farthest past it; an exact number (limit 0) that
    reads more than 0 is past it by infinity. ``leaf`` is where the number
    was read (``numbers["where"]``, a reference's candidate), or None."""
    def ratio(k):
        if limits[k] > 0:
            return numbers[k] / limits[k]
        return float("inf") if numbers[k] > 0 else 0.0

    name = max((k for k in limits if k in numbers), key=ratio)
    return (name, numbers[name], limits[name], ratio(name),
            (numbers.get("where") or {}).get(name))


def summary(row: dict, limits: dict) -> str:
    """One line a seed: for the program and each stand-in, the compared
    number nearest its limit, its leaf and the ratio."""
    parts = []
    for who, numbers in row.items():
        if isinstance(numbers, dict):
            name, value, limit, ratio, leaf = nearest(numbers, limits)
            parts.append(f"{who} {name} {value:.4g} at {leaf or '-'} "
                         f"= {ratio:.3g} x {limit:g}")
    return f"seed {row['seed']}: " + "; ".join(parts)


def write(path, ref_mod, config, traffic, inputs, seed, program) -> None:
    row = {"seed": seed, "program": program,
           **stand_ins(ref_mod, config, traffic, inputs)}
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    with open(path, "w") as f:
        json.dump(row, f, indent=1)


def main(argv=None) -> int:
    from benchmark import run as harness

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--stand-ins", type=int, default=0)
    ap.add_argument("--rehearsal", action="store_true")
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    _, cell, config, traffic = harness.load_cell(args.workload,
                                                 args.rehearsal)
    harness.use_compile_cache()
    harness._device(int(cell["chips"]), args.rehearsal)
    ref_mod = harness._load_module("references", cell["config"])
    entry_mod = harness._load_module("entries", traffic["entry"])
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        entry = entry_mod.Entry(config, traffic, seed, int(cell["chips"]))
        entry.setup()
        inputs = entry.check_inputs()
        entry.release()
        del entry
        row = {"seed": seed}
        if i < args.stand_ins:
            row.update(stand_ins(ref_mod, config, traffic, inputs))
        elif hasattr(ref_mod, "stand_ins"):
            row.update(stand_ins(ref_mod, config, traffic, inputs, ()))
        # a reference's own stand-ins bring the program's numbers too
        row.setdefault("program", ref_mod.check(config, inputs))
        with open(args.out, "a") as f:
            f.write(json.dumps(row) + "\n")
        print(summary(row, config["limits"]), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
