"""What a training step of the hybrid state-space / sparse-expert backbone
needs, computed from the configuration's sizes and from what the program
counted (the pairs its held experts computed), never from the program's code.

A training step is three times its forward pass. Forward, a token:

``M``  the two projections, two operations a weight (``hidden x (z + xBC +
       dt)`` and ``d_inner x hidden``), and the state-space recurrence as the
       chunked form needs it: inside a chunk of ``chunk`` positions a token
       meets ``chunk / 2`` earlier ones on average (the causal half, as for
       attention), each costing one ``C . B`` product a group (``2 x
       state``) and one weighted sum a head (``2 x head_dim``); once a token,
       its share of the chunk's state and its read of the entering state,
       ``2 x heads x head_dim x state`` each. The convolution, the gate and
       the norms are elementwise and count nothing.
``*``  the four projections, and the scores and weighted values against the
       ``seq / 2`` earlier keys a token meets on average: ``4 x (seq / 2) x
       heads x head_dim``.
``E``  the router over every published expert, the shared expert's two
       matrices, and the routed experts' two matrices for the pairs the held
       experts computed, ``pairs / tokens`` a token, from the program's own
       counter, not from the expected share.

The head sees one position a sample. The embedding is a gather and counts
nothing; padding positions count, since the step computes them;
recomputation counts nothing.
"""

from __future__ import annotations


def mamba_forward_flops_per_token(hidden, heads, head_dim, groups, state,
                                  chunk) -> float:
    d_inner = heads * head_dim
    in_proj = hidden * (2 * d_inner + 2 * groups * state + heads)
    inside = (chunk / 2) * (groups * 2 * state + heads * 2 * head_dim)
    across = 2 * (2 * heads * head_dim * state)
    return 2 * (in_proj + d_inner * hidden) + inside + across


def attention_forward_flops_per_token(hidden, heads, kv_heads, head_dim,
                                      seq) -> float:
    weights = hidden * head_dim * (2 * heads + 2 * kv_heads)
    return 2 * weights + 4 * (seq / 2) * heads * head_dim


def experts_forward_flops_per_token(hidden, router, width, shared_width,
                                    pairs_per_token) -> float:
    return (2 * hidden * router + 2 * (2 * hidden * shared_width)
            + pairs_per_token * 2 * (2 * hidden * width))


def hybrid_forward_flops_per_token(config: dict, seq: int,
                                   pairs_per_token: float) -> float:
    c = config
    per_kind = {
        "M": mamba_forward_flops_per_token(
            c["hidden_size"], c["mamba_num_heads"], c["mamba_head_dim"],
            c["n_groups"], c["ssm_state_size"], c["chunk_size"]),
        "*": attention_forward_flops_per_token(
            c["hidden_size"], c["num_attention_heads"],
            c["num_key_value_heads"], c["head_dim"], seq),
        "E": experts_forward_flops_per_token(
            c["hidden_size"], c["router_experts"], c["moe_intermediate_size"],
            c["moe_shared_expert_intermediate_size"], pairs_per_token),
    }
    return sum(per_kind[kind] for kind in c["hybrid_override_pattern"])


def hybrid_train_flops(config: dict, samples: int, seq: int,
                       routed_pairs: int, classes: int) -> float:
    """Operations the forward and backward passes of ``samples`` rows of
    ``seq`` positions require, ``routed_pairs`` (token, expert) pairs having
    gone through held experts over all the expert layers together."""
    tokens = int(samples) * int(seq)
    layers = config["hybrid_override_pattern"].count("E")
    pairs = routed_pairs / (tokens * layers) if layers else 0.0
    forward = (tokens * hybrid_forward_flops_per_token(config, int(seq), pairs)
               + int(samples) * 2 * int(config["hidden_size"]) * int(classes))
    return 3 * forward
