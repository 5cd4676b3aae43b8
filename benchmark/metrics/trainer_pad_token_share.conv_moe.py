"""``trainer_pad_token_share`` read in the short-conv / expert cell: positions
of the traced epoch that held PAD, of all the positions its steps computed
(the program's ``padTokens`` and ``tokens`` counters)."""

from benchmark.metrics.trainer_pad_token_share import read  # noqa: F401
