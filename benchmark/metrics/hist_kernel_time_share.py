"""Device time inside the histogram kernels over the device's busy time,
fullest device."""

from benchmark import trace as tr
from benchmark.kernels import is_hist_kernel


def read(ctx):
    _, events = tr.fullest(ctx["trace"])
    kernel = tr.matching_ns(events, is_hist_kernel)
    if kernel <= 0:
        return None
    return 100.0 * kernel / tr.busy_ns(events)
