"""Tables made from ``--seed``: the one generator every booster cell reads.

A configuration's ``table`` block gives ``generator``, ``rows_per_chip``,
``features``, ``base_seed`` and ``sample``; a cell's table has
``rows_per_chip`` rows for each of its chips. ``higgs_like`` is the rule of
``chip_smoke._higgs_like`` (copied, so that a later PR cannot change the
yardstick): standard-normal float32 features and a binary label from a margin
that multiplies, adds and folds the first four features under noise, so that
trees have interactions to find.

Every seed gives the same rows in another order, and the same columns in
another order: the values are drawn from ``base_seed``, and ``--seed`` draws
the two permutations. Freshly drawn values would change the work with the
seed: a child of 1.04 M rows and one of 1.06 M fall on either side of one of
the grower's power-of-two buckets, and six seeds' rates spread by 1.8 % while
two runs of one seed differ by 0.05 % (my chip runs, PR 24). The row
permutation keeps the rows that the estimator samples for its bin boundaries
(``sample``: the seed and the count of its sampler) among themselves, so the
boundaries, and with them the trees, are the same for every seed. The same
seed gives the same table.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np


_CHUNKS = 16      # fixed, so that the table does not depend on the host


def _higgs_like(rows: int, features: int, seed: int, spec: dict):
    X = np.empty((rows, features), np.float32)
    noise = np.empty(rows, np.float32)
    edges = np.linspace(0, rows, _CHUNKS + 1).astype(np.int64)
    streams = np.random.SeedSequence(int(spec["base_seed"])).spawn(_CHUNKS)

    def fill(i):
        rng = np.random.default_rng(streams[i])
        lo, hi = edges[i], edges[i + 1]
        rng.standard_normal(out=X[lo:hi], dtype=np.float32)
        rng.standard_normal(out=noise[lo:hi], dtype=np.float32)

    with ThreadPoolExecutor(max_workers=8) as pool:
        list(pool.map(fill, range(_CHUNKS)))
    margin = (X[:, 0] * X[:, 1] + 0.5 * X[:, 2] - 0.3 * np.abs(X[:, 3])
              + 0.5 * noise)
    y = (margin > 0).astype(np.float32)

    # --seed: the order of the rows (sampled rows among themselves, the
    # others among themselves) and the order of the columns
    rng = np.random.default_rng(seed)
    sample = spec["sample"]
    perm = np.arange(rows)
    if rows > int(sample["count"]):
        held = np.zeros(rows, bool)
        held[np.random.default_rng(int(sample["seed"])).choice(
            rows, size=int(sample["count"]), replace=False)] = True
        for part in (np.flatnonzero(held), np.flatnonzero(~held)):
            perm[part] = rng.permutation(part)
    else:
        perm = rng.permutation(rows)
    cols = rng.permutation(features)
    out = np.empty_like(X)

    def move(i):
        lo, hi = edges[i], edges[i + 1]
        np.take(X, perm[lo:hi], axis=0, out=out[lo:hi])
        out[lo:hi] = out[lo:hi][:, cols]

    with ThreadPoolExecutor(max_workers=8) as pool:
        list(pool.map(move, range(_CHUNKS)))
    return out, y[perm]


GENERATORS = {"higgs_like": _higgs_like}


def make(spec: dict, chips: int, seed: int):
    return GENERATORS[spec["generator"]](int(spec["rows_per_chip"]) * chips,
                                         int(spec["features"]), int(seed),
                                         spec)
