"""The busiest held expert's tokens over the mean held expert's, over the
traced epoch and every expert layer (the program's ``expertTokens``
counter)."""

from benchmark.trainer_record import traced_epoch


def read(ctx):
    counters = (traced_epoch(ctx) or {}).get("counters")
    loads = [n for layer in (counters or {}).get("expertTokens", [])
             for n in layer]
    if not loads or not sum(loads):
        return None
    return max(loads) * len(loads) / sum(loads)
