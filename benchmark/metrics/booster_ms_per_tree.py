"""The program's ``trainingIterations`` span (it ends in a device_get) of
the traced fit, over the trees grown."""


def read(ctx):
    spans = ctx["entry"].spans[0] if ctx["entry"].spans else {}
    trees = spans.get("count:iterations")
    if not trees or "trainingIterations" not in spans:
        return None
    return spans["trainingIterations"] * 1e3 / trees
