"""Least time for the rows the histogram passes of the traced fit have to
cover (bytes over the memory bandwidth: they bind, see benchmark/counts.py)
over the traced time of the histogram kernels, fullest device."""

from benchmark import trace as tr
from benchmark.kernels import is_hist_kernel, fit_least_seconds


def read(ctx):
    _, events = tr.fullest(ctx["trace"])
    kernel = tr.matching_ns(events, is_hist_kernel) / 1e9
    if kernel <= 0 or not ctx["trees"]:
        return None
    return 100.0 * fit_least_seconds(ctx) / kernel
