"""What the algorithm needs, computed from shapes and from the trees that were
grown, never from the kernels' code.

A histogram pass over ``rows`` rows of ``features`` features reads one bin id
a feature (one byte at up to 256 bins) and one gradient and one hessian
(float32) a row: ``rows * (features + 8)`` bytes. Its arithmetic is three
additions a (row, feature) pair, which at the chip's peaks takes far less
time than reading the bytes, so the bytes bind: the least time is bytes over
the memory bandwidth.

Growing one tree leaf-wise needs one pass over all rows for the root and, for
every split, one pass over the smaller child (the larger child's histogram is
the parent's less the smaller's).

A training step of a dense text encoder needs three times its forward
pass's operations (the backward pass computes two products for each of the
forward's). Forward, a token and layer: four hidden x hidden projections
(query, key, value, output) and the two feed-forward matrices, two
operations a weight, plus the scores against ``seq`` keys and the weighted
sum of ``seq`` values, ``2 * seq * hidden`` each. The head sees one position
a sample. The embedding is a gather and counts nothing; padding positions
count, since a dense step computes them; recomputation counts nothing.
"""

from __future__ import annotations


def hist_bytes(rows: int, features: int) -> int:
    return int(rows) * (int(features) + 8)


def hist_additions(rows: int, features: int) -> int:
    return 3 * int(rows) * int(features)


def tree_hist_rows(n_rows: int, splits) -> int:
    """Rows the histogram passes of one tree have to cover. ``splits``:
    (rows in the left child, rows in the right child) for every split."""
    return int(n_rows) + sum(min(int(a), int(b)) for a, b in splits)


def tree_least_bytes(n_rows: int, splits, features: int) -> int:
    return hist_bytes(tree_hist_rows(n_rows, splits), features)


def least_seconds(n_bytes: int, additions: int, peaks: dict) -> tuple:
    """(seconds, which bound binds)."""
    by_bytes = n_bytes / peaks["hbm_bytes_per_s"]
    by_ops = additions / peaks["bf16_flops_per_s"]
    return (by_bytes, "bytes") if by_bytes >= by_ops else (by_ops, "operations")


def encoder_forward_flops_per_token_layer(seq: int, hidden: int,
                                          intermediate: int) -> int:
    matrices = 4 * hidden * hidden + 2 * hidden * intermediate
    return 2 * matrices + 4 * int(seq) * hidden


def encoder_train_flops(samples: int, seq: int, hidden: int, layers: int,
                        intermediate: int, classes: int) -> int:
    """Operations the forward and backward passes of ``samples`` rows of
    ``seq`` positions require."""
    forward = (int(samples) * int(seq) * int(layers)
               * encoder_forward_flops_per_token_layer(seq, int(hidden),
                                                       int(intermediate))
               + int(samples) * 2 * int(hidden) * int(classes))
    return 3 * forward
