"""1 - busy time over the traced window, fullest device."""

from benchmark import trace as tr


def read(ctx):
    _, events = tr.fullest(ctx["trace"])
    if not events:
        return None
    return 100.0 * (1.0 - tr.busy_ns(events) / 1e9 / ctx["traced_s"])
