"""``moe_expert_load_max_over_mean`` read in the short-conv / expert cell:
the busiest held expert's tokens over the mean held expert's, over the traced
epoch and every expert layer (the program's ``expertTokens`` counter)."""

from benchmark.metrics.moe_expert_load_max_over_mean import read  # noqa: F401
