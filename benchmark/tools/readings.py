"""The readings the limits are set from: ``benchmark.run --readings FILE``
runs a cell as always (the timed path at the timed size) and then, on the
same answer, reads the control (the reference in the next lower precision
put in the program's place) and the planted faults, and writes them with the
run's own numbers (the lower reading) to FILE. No benchmark run does this.
"""

from __future__ import annotations

import copy
import json
import os


def stand_ins(ref_mod, config: dict, traffic: dict, inputs: dict) -> dict:
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


def write(path, ref_mod, config, traffic, inputs, seed, program) -> None:
    row = {"seed": seed, "program": program,
           **stand_ins(ref_mod, config, traffic, inputs)}
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    with open(path, "w") as f:
        json.dump(row, f, indent=1)
