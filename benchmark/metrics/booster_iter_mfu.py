"""The whole boosting iteration's share of the chip's peak: the algorithm's
least traffic for the trees the traced fit grew, over the memory bandwidth,
over the time of the program's ``trainingIterations`` span in the trace."""

from benchmark import trace as tr
from benchmark.kernels import fit_least_seconds


def read(ctx):
    spans = [e for e in tr.host_annotations(ctx["trace"])
             if e[0] == "trainingIterations"]
    if not spans or not ctx["trees"]:
        return None
    seconds = sum(e[2] for e in spans) / 1e9
    return 100.0 * fit_least_seconds(ctx) / seconds
