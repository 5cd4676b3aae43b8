"""(token, expert) pairs the held experts computed, a token and expert
layer, over the traced epoch (the program's ``routedPairs`` and ``tokens``
counters): held / published x experts a token when routing is even."""

from benchmark.trainer_record import traced_epoch


def read(ctx):
    counters = (traced_epoch(ctx) or {}).get("counters")
    if not counters or not counters.get("tokens"):
        return None
    layers = len(counters["expertTokens"])
    return counters["routedPairs"] / (counters["tokens"] * layers)
