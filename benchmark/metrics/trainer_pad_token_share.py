"""Positions of the traced epoch that held PAD, of all the positions its
steps computed (the program's ``padTokens`` and ``tokens`` counters)."""

from benchmark.trainer_record import traced_epoch


def read(ctx):
    counters = (traced_epoch(ctx) or {}).get("counters")
    if not counters or not counters.get("tokens"):
        return None
    return 100.0 * counters["padTokens"] / counters["tokens"]
