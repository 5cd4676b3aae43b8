"""The share of the traced fit's wall time that is not the boosting loop
(the program's ``trainingIterations`` span): label handling, bin boundaries,
the copy to the device, binning, and the model's assembly. The program's own
``dataPreparation`` span closes when the binning is dispatched, not when it
is done, so it cannot be used: the binning then shows inside the fit's wall
time and in the device trace only."""


def read(ctx):
    entry = ctx["entry"]
    if not entry.spans or "trainingIterations" not in entry.spans[0]:
        return None
    wall = entry.fit_seconds[0]
    return 100.0 * (wall - entry.spans[0]["trainingIterations"]) / wall
