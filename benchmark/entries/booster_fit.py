"""Entry ``booster_fit``: whole gradient-boosting fits, back to back.

What a traffic file can set: ``via`` (``estimator``: ``LightGBMClassifier(
numIterations=T, **estimator).fit(Table)``; ``train_booster``: the engine's
own call, which is the only one that takes a mesh today), ``mesh`` (axis
sizes) and ``follow_trees``. The configuration gives ``numIterations`` (T;
the scan length is static in the compiled program, so warm-up runs the same
T) and the ``table`` block, which with the cell's chips and ``--seed`` makes
the table (``rows_per_chip`` rows for every chip of the cell).

One unit of work is one fit; it ends with the trees on the host. The work it
reports is rows x iterations.
"""

from __future__ import annotations

import json
import logging
import time

import numpy as np

from benchmark import tables


class _Measures(logging.Handler):
    """Catches the estimator's ``trainingMeasures`` record (its phase spans)."""

    def __init__(self):
        super().__init__(level=logging.DEBUG)
        self.last = None

    def emit(self, record):
        try:
            payload = json.loads(record.getMessage())
        except ValueError:
            return
        if payload.get("method") == "trainingMeasures":
            self.last = payload


def _steps_from_children(left, right, n_splits):
    """Leaf id that each growth step split. LightGBM numbering: the left
    child of a split keeps the parent leaf's id, the right child of split i
    becomes leaf i + 1; a child pointer >= 0 is an internal node, ~leaf
    otherwise."""
    leaf_of = {0: 0}
    for i in range(n_splits):
        for child, is_right in ((left[i], False), (right[i], True)):
            if child >= 0:
                leaf_of[int(child)] = i + 1 if is_right else leaf_of[i]
    return [leaf_of[i] for i in range(n_splits)]


def _tree_answer(booster, index):
    from synapseml_tpu.ops.quantize import bin_threshold_to_value

    t = booster.trees[index]
    s = int(np.asarray(t.num_splits))
    left = np.asarray(t.left_child)[:s]
    right = np.asarray(t.right_child)[:s]
    feat = np.asarray(t.split_feature)[:s]
    sbin = np.asarray(t.split_bin)[:s]
    leaf_count = np.asarray(t.leaf_count)
    node_count = np.asarray(t.internal_count)

    def rows(child):
        return int(node_count[child]) if child >= 0 else int(leaf_count[~child])

    return {
        "leaf": _steps_from_children(left, right, s),
        "feature": feat.tolist(),
        "threshold": [np.float32(bin_threshold_to_value(
            booster.mapper, int(f), int(b))) for f, b in zip(feat, sbin)],
        "leaf_value": np.asarray(t.leaf_value, np.float64),
        "leaf_count": leaf_count.astype(np.int64),
        "splits": [(rows(int(l)), rows(int(r))) for l, r in zip(left, right)],
    }


class Entry:
    def __init__(self, config: dict, traffic: dict, seed: int, chips: int):
        self.config, self.traffic = config, traffic
        self.seed, self.chips = seed, chips
        self.iterations = int(config["numIterations"])
        self.spans = []          # one dict of phase seconds per fit
        self.fit_seconds = []
        self.booster = None
        self.mesh = None

    # -- set-up -------------------------------------------------------------
    def setup_data(self):
        self.X, self.y = tables.make(self.config["table"], self.chips,
                                     self.seed)
        if self.traffic.get("mesh"):
            from synapseml_tpu.parallel.mesh import make_mesh

            self.mesh = make_mesh(dict(self.traffic["mesh"]))
        if self.traffic["via"] == "estimator":
            self._handler = _Measures()
            log = logging.getLogger("synapseml_tpu")
            log.addHandler(self._handler)
            log.setLevel(logging.DEBUG)
            log.propagate = False

    def setup(self):
        self.setup_data()
        self.unit()              # warm-up: same T, same shapes
        self.spans.clear()
        self.fit_seconds.clear()

    @property
    def rows(self):
        return int(self.X.shape[0])

    # -- one fit ------------------------------------------------------------
    def unit(self) -> int:
        t0 = time.perf_counter()
        if self.traffic["via"] == "estimator":
            from synapseml_tpu.core import Table
            from synapseml_tpu.models import LightGBMClassifier

            est = LightGBMClassifier(numIterations=self.iterations,
                                     **self.config.get("estimator", {}))
            self._handler.last = None
            model = est.fit(Table({"features": self.X, "label": self.y}))
            self.booster = model.booster
            spans = {k: v for k, v in (self._handler.last or {}).items()
                     if isinstance(v, (int, float))}
        else:
            from synapseml_tpu.core.logging import InstrumentationMeasures
            from synapseml_tpu.gbdt import BoosterConfig, train_booster

            m = InstrumentationMeasures()
            cfg = BoosterConfig(num_iterations=self.iterations,
                                **self.config.get("booster", {}))
            self.booster = train_booster(self.X, self.y, cfg, mesh=self.mesh,
                                         measures=m)
            spans = m.report()
        self.fit_seconds.append(time.perf_counter() - t0)
        self.spans.append(spans)
        return self.rows * self.iterations

    # -- what the reference judges, and what the count functions read --------
    def trees(self, upto=None):
        n = len(self.booster.trees) if upto is None else min(
            upto, len(self.booster.trees))
        return [_tree_answer(self.booster, i) for i in range(n)]

    def check_inputs(self) -> dict:
        follow = int(self.traffic.get("follow_trees", 3))
        return {"X": self.X, "y": self.y, "follow": follow,
                "judged": {"base_score": float(self.booster.base_score[0]),
                           "trees": self.trees(follow)}}

    def release(self):
        """Drop what the program left on the device before the reference
        runs."""
        import jax

        self.booster = None
        if getattr(self, "_handler", None) is not None:
            logging.getLogger("synapseml_tpu").removeHandler(self._handler)
        jax.clear_caches()
