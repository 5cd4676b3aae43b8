"""What a training step of the gated short-convolution / sparse-expert
backbone (``lfm2_moe``) needs, computed from the configuration's sizes and
from what the program counted (the pairs its held experts computed), never
from the program's code.

A training step is three times its forward pass. Forward, a token, each
matrix two operations a weight:

``conv``       ``in_proj`` (hidden -> 3 x hidden) and ``out_proj`` (hidden ->
               hidden); the gates and the convolution are elementwise and
               count nothing.
``attention``  the four projections, and the scores and weighted values
               against the ``seq / 2`` earlier keys a token meets on
               average: ``4 x (seq / 2) x heads x head_dim``; the norms and
               the rotary embedding are elementwise.
``dense``      the SwiGLU's three matrices at ``intermediate_size``.
``experts``    the router over every published expert, and the SwiGLU
               experts' three matrices at ``moe_intermediate_size`` for the
               pairs the held experts computed, ``pairs / tokens`` a token,
               from the program's own counter.

The head sees one pooled row a sample. The embedding is a gather and counts
nothing; padding positions count, since the step computes them;
recomputation counts nothing.
"""

from __future__ import annotations


def conv_forward_flops_per_token(hidden) -> float:
    return 2 * (hidden * 3 * hidden + hidden * hidden)


def attention_forward_flops_per_token(hidden, heads, kv_heads, seq) -> float:
    head_dim = hidden // heads
    weights = hidden * head_dim * (2 * heads + 2 * kv_heads)
    return 2 * weights + 4 * (seq / 2) * heads * head_dim


def dense_forward_flops_per_token(hidden, width) -> float:
    return 2 * 3 * hidden * width


def experts_forward_flops_per_token(hidden, router, width,
                                    pairs_per_token) -> float:
    return 2 * hidden * router + pairs_per_token * 2 * 3 * hidden * width


def sublayers(config: dict) -> list:
    """Each held layer's operator ('conv' or 'attention') and FFN ('dense'
    in the first ``num_dense_layers``, else 'experts')."""
    types = config["layer_types"][:int(config["num_hidden_layers"])]
    dense = int(config["num_dense_layers"])
    return [kind for i, t in enumerate(types)
            for kind in ("conv" if t == "conv" else "attention",
                         "dense" if i < dense else "experts")]


def conv_moe_forward_flops_per_token(config: dict, seq: int,
                                     pairs_per_token: float) -> float:
    c = config
    hidden = int(c["hidden_size"])
    per_kind = {
        "conv": conv_forward_flops_per_token(hidden),
        "attention": attention_forward_flops_per_token(
            hidden, c["num_attention_heads"], c["num_key_value_heads"], seq),
        "dense": dense_forward_flops_per_token(hidden, c["intermediate_size"]),
        "experts": experts_forward_flops_per_token(
            hidden, c["router_experts"], c["moe_intermediate_size"],
            pairs_per_token),
    }
    return sum(per_kind[kind] for kind in sublayers(c))


def conv_moe_train_flops(config: dict, samples: int, seq: int,
                         routed_pairs: int, classes: int) -> float:
    """Operations the forward and backward passes of ``samples`` rows of
    ``seq`` positions require, ``routed_pairs`` (token, expert) pairs having
    gone through held experts over all the expert layers together."""
    tokens = int(samples) * int(seq)
    layers = sublayers(config).count("experts")
    pairs = routed_pairs / (tokens * layers) if layers else 0.0
    forward = (tokens * conv_moe_forward_flops_per_token(config, int(seq),
                                                         pairs)
               + int(samples) * 2 * int(config["hidden_size"]) * int(classes))
    return 3 * forward
