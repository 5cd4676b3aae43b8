"""The share of the traced fit's wall time (the benchmark's clock around
the call) that none of the program's top-level spans covers. Child spans
(``parent/child``) and counters are left out of the sum; a record without
the spans this metric was added with (``objectiveSetup``, ``modelAssembly``)
is an older program's and reads nothing."""

NEEDS = ("referenceDataset", "dataPreparation", "objectiveSetup",
         "trainingIterations", "modelAssembly")


def read(ctx):
    entry = ctx["entry"]
    spans = entry.spans[0] if entry.spans else {}
    if any(k not in spans for k in NEEDS):
        return None
    wall = entry.fit_seconds[0]
    covered = sum(v for k, v in spans.items()
                  if "/" not in k and not k.startswith("count:"))
    return 100.0 * (wall - covered) / wall
