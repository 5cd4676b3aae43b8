"""``moe_pairs_per_token`` read in the short-conv / expert cell: (token,
expert) pairs the held experts computed, a token and expert layer, over the
traced epoch (the program's ``routedPairs`` and ``tokens`` counters)."""

from benchmark.metrics.moe_pairs_per_token import read  # noqa: F401
