"""Which traced operations are which, and the least time a traced fit needs.

Names are matched on what the device trace shows for them; shapes and counts
come from the cell's files and the trees that were grown.
"""

from __future__ import annotations

from benchmark import counts
from benchmark.peaks import peaks_for

def is_hist_kernel(name: str) -> bool:
    return "hist_pallas" in name


def fit_least_seconds(ctx) -> float:
    """Least seconds one chip needs for its share of the histogram passes of
    every tree of the traced fit."""
    features = int(ctx["config"]["table"]["features"])
    rows = sum(counts.tree_hist_rows(ctx["entry"].rows, t["splits"])
               for t in ctx["trees"])
    rows = rows / ctx["chips"]
    seconds, _ = counts.least_seconds(
        counts.hist_bytes(rows, features),
        counts.hist_additions(rows, features), peaks_for(ctx["device_kind"]))
    return seconds
