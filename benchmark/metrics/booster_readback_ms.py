"""The program's ``trainingIterations/treesReadback`` span of the traced
fit: the stacked trees fetched to the host and unstacked into the model's
list, after the scan's outputs are ready."""


def read(ctx):
    spans = ctx["entry"].spans[0] if ctx["entry"].spans else {}
    if "trainingIterations/treesReadback" not in spans:
        return None
    return spans["trainingIterations/treesReadback"] * 1e3
