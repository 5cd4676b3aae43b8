"""Plain reference for the ``lgbm_higgs`` configuration.

Gradient boosting with histogram splits as the configuration file states it,
in straightforward ``numpy`` / ``jax.numpy``: no kernels, no row partitioning,
no histogram subtraction, no scan over iterations. It imports nothing of the
program and is handed nothing the program made except the answer that is
being judged (the first trees of the timed fit).

The comparison is teacher-forced, as a served model's is on its served
tokens: the reference follows the structure of the trees under judgement and,
at every growth step and every leaf, computes from the raw table what a plain
computation gives there.

* ``gain_gap``  at each growth step, how far the gain of the split that was
  taken (leaf, feature, threshold; evaluated by the reference) lies below the
  best gain the reference finds over every leaf that could have been split,
  as a share of that best gain. Worst step of the trees followed.
* ``leaf_gap``  |leaf value judged - reference's| over max(|reference's|,
  median |reference's|), worst leaf.
* ``loss_gap``  |log-loss with the judged answer - with the reference's| over
  the reference's: at the base score, then after each tree followed (the
  trees before it as judged, this tree's leaf values the judged ones against
  the reference's), worst of them.
* ``count_gap`` largest difference of a leaf's row count (exact: limit 0).
* ``grid_gap``  thresholds of the judged trees that are not one of the
  reference's own bin boundaries (exact: limit 0). The boundaries are made
  here from the configuration's rule, not taken from the program.

``stand_in`` computes the same answers in another value type or from a
subset of the rows, under the same teacher forcing. That is the control (the
reference in the next lower precision put in the program's place) and the
planted faults (half of the rows left out, one shard's rows only).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

BIG = 1e30      # stands for "no such split" in a gap; JSON holds no infinity

# (exponent bits, mantissa bits) of each value type. Values are rounded with
# lax.reduce_precision: a pair of astype calls is dropped by the TPU compiler
# (excess precision is allowed there), which would leave them unrounded.
_VALUE_TYPES = {
    "float32": (8, 23),
    "bfloat16": (8, 7),
    "float8_e4m3fn": (4, 3),
}
# the step down that would tempt a later PR, per stated precision
LOWER = {"float32": "bfloat16", "bfloat16": "float8_e4m3fn"}


# ---------------------------------------------------------------------------
# the configuration's binning rule
# ---------------------------------------------------------------------------

def bin_boundaries(X: np.ndarray, p: dict) -> np.ndarray:
    """(F, max_bin - 1) upper boundaries, +inf padded. Rule of the config:
    a seeded sample of ``bin_sample_count`` rows, boundaries at the interior
    points of ``max_bin`` equally spaced quantiles (midpoints when a feature
    has fewer distinct values), neighbours merged until every bin holds
    ``min_data_in_bin`` sample rows. A value goes to the first bin whose
    boundary is not below it."""
    n, f = X.shape
    max_bin, count = int(p["max_bin"]), int(p["bin_sample_count"])
    sample = X
    if n > count:
        rows = np.random.default_rng(int(p["bin_seed"])).choice(
            n, size=count, replace=False)
        sample = X[rows]
    out = np.full((f, max_bin - 1), np.inf, np.float32)
    for j in range(f):
        col = sample[:, j]
        uniq = np.unique(col)
        if uniq.size <= 1:
            continue
        if uniq.size <= max_bin - 1:
            b = (uniq[:-1] + uniq[1:]) * 0.5
        else:
            qs = np.linspace(0.0, 1.0, max_bin)[1:-1]
            b = np.unique(np.quantile(col, qs).astype(np.float32))
        least = int(p["min_data_in_bin"])
        if least > 1 and b.size:
            counts = np.bincount(np.searchsorted(b, col, side="left"),
                                 minlength=b.size + 1)
            keep, acc = [], 0
            for i in range(b.size):
                acc += counts[i]
                if acc >= least:
                    keep.append(i)
                    acc = 0
            if keep and counts[b.size] + acc < least:
                keep.pop()
            b = b[keep]
        out[j, : b.size] = b
    return out


@jax.jit
def _bin_rows(xg, bounds):
    """(inner, R, F) floats -> (inner, F, R) uint8 bin ids."""
    def one(col, b):
        return jnp.searchsorted(b, col, side="left")
    flat = xg.reshape(-1, xg.shape[-1])
    ids = jax.vmap(one, in_axes=(1, 0), out_axes=0)(flat, bounds)   # (F, rows)
    ids = ids.astype(jnp.uint8).reshape(ids.shape[0], xg.shape[0], xg.shape[1])
    return jnp.transpose(ids, (1, 0, 2))


# ---------------------------------------------------------------------------
# device passes: all elementwise or one matrix product, in row blocks
# ---------------------------------------------------------------------------

@jax.jit
def _route(bins, step_leaf, step_feat, step_bin):
    """Leaf of every row under the judged tree. Step i sends the rows of leaf
    ``step_leaf[i]`` whose bin of ``step_feat[i]`` is above ``step_bin[i]``
    to the new leaf i + 1."""
    def body(i, leaf):
        col = lax.dynamic_index_in_dim(bins, step_feat[i], 2, keepdims=False)
        right = (leaf == step_leaf[i]) & (col.astype(jnp.int32) > step_bin[i])
        return jnp.where(right, i + 1, leaf)
    g, inner, _, r = bins.shape
    return lax.fori_loop(0, step_leaf.shape[0], body,
                         jnp.zeros((g, inner, r), jnp.int32))


@jax.jit
def _grad_hess(score, y):
    p = jax.nn.sigmoid(score)
    return p - y, jnp.maximum(p * (1.0 - p), 1e-16)


@functools.partial(jax.jit, static_argnames=("leaves", "value_type"))
def _leaf_hists(bins, leaf, g, h, use, leaves, value_type):
    """(G, F, 256, leaves, 3) sums of (grad, hess, 1) by group of row blocks.
    Values are rounded to ``value_type`` row by row and summed in float32
    inside a group; groups are added up in float64 on the host."""
    ebits, mbits = _VALUE_TYPES[value_type]
    exact_in_bf16 = value_type != "float32"
    op = jnp.bfloat16 if exact_in_bf16 else jnp.float32
    prec = None if exact_in_bf16 else lax.Precision.HIGHEST
    f = bins.shape[2]
    ids = jnp.arange(256, dtype=jnp.int32)

    def block(acc, x):
        b, lf, gb, hb, ub = x
        vals = jnp.stack([lax.reduce_precision(gb, ebits, mbits),
                          lax.reduce_precision(hb, ebits, mbits),
                          jnp.ones_like(gb)], -1) * ub[:, None]      # (R, 3)
        sel = (lf[:, None] == jnp.arange(leaves)[None, :])
        v = (sel[:, :, None] * vals[:, None, :]).reshape(lf.shape[0], -1)
        oh = (b.astype(jnp.int32)[:, :, None] == ids).astype(op)     # (F,R,256)
        part = jnp.einsum("frb,rv->fbv", oh, v.astype(op), precision=prec,
                          preferred_element_type=jnp.float32)
        return acc + part, None

    def group(x):
        acc, _ = lax.scan(block, jnp.zeros((f, 256, leaves * 3), jnp.float32),
                          x)
        return acc

    out = lax.map(group, (bins, leaf, g, h, use))
    return out.reshape(out.shape[0], f, 256, leaves, 3)


@jax.jit
def _loss_sums(score_j, score_ref, y, use):
    """Per-group sums of the reference's log-loss and of the judged one's
    difference from it, row by row (added up in float64 on the host): the
    difference of two float32 sums over millions of rows would carry the
    sums' own rounding, a few millionths."""
    ll_ref = jnp.logaddexp(0.0, score_ref) - y * score_ref
    ll_j = jnp.logaddexp(0.0, score_j) - y * score_j
    return (jnp.sum(ll_ref * use, axis=(1, 2)),
            jnp.sum((ll_j - ll_ref) * use, axis=(1, 2)))


@jax.jit
def _add_leaf_values(score, leaf, values):
    return score + values[leaf]


# ---------------------------------------------------------------------------
# host arithmetic, float64
# ---------------------------------------------------------------------------

def _gain_table(hist, p):
    """hist (F, 256, 3) -> gain of every (feature, threshold bin), -inf where
    the split would break min_data_in_leaf / min_sum_hessian_in_leaf."""
    l2 = float(p["lambda_l2"])
    tot = hist[0].sum(axis=0)
    G, H, C = tot
    cum = np.cumsum(hist, axis=1)
    GL, HL, CL = cum[..., 0], cum[..., 1], cum[..., 2]
    GR, HR, CR = G - GL, H - HL, C - CL
    with np.errstate(divide="ignore", invalid="ignore"):
        gain = GL * GL / (HL + l2) + GR * GR / (HR + l2) - G * G / (H + l2)
    ok = ((CL >= p["min_data_in_leaf"]) & (CR >= p["min_data_in_leaf"])
          & (HL >= p["min_sum_hessian_in_leaf"])
          & (HR >= p["min_sum_hessian_in_leaf"]))
    return np.where(ok, gain, -np.inf)


def _leaf_values(hist_leaf, p):
    tot = hist_leaf[:, 0].sum(axis=1)                     # (L, 3)
    val = -tot[:, 0] / (tot[:, 1] + float(p["lambda_l2"]))
    return val * float(p["learning_rate"]), np.rint(tot[:, 2]).astype(np.int64)


def _replay(hist_leaf, step_leaf):
    """Yield, for each growth step i from the last to the first, the list of
    histograms of the leaves that existed before step i (index = leaf id)."""
    cur = [hist_leaf[l] for l in range(hist_leaf.shape[0])]
    out = [None] * len(step_leaf)
    for i in range(len(step_leaf) - 1, -1, -1):
        l = int(step_leaf[i])
        cur = list(cur[: i + 2])
        cur[l] = cur[l] + cur[i + 1]
        cur = cur[: i + 1]
        out[i] = cur
    return out


class _Gains:
    """Gain tables, one per distinct histogram (a leaf keeps its histogram
    from step to step until it is split)."""

    def __init__(self, p):
        self.p, self.memo = p, {}

    def of(self, hist):
        key = id(hist)
        if key not in self.memo:
            self.memo[key] = (hist, _gain_table(hist, self.p))
        return self.memo[key][1]


# ---------------------------------------------------------------------------
# the reference
# ---------------------------------------------------------------------------

class Reference:
    def __init__(self, params: dict, X: np.ndarray, y: np.ndarray):
        self.p = dict(params)
        self.value_type = self.p["histogram_values"]
        self.leaves = int(self.p["num_leaves"])
        n, f = X.shape
        self.n = n
        r = 8192 if n >= (1 << 20) else 1024
        inner = 32 if n >= (1 << 20) else 4
        per = r * inner
        self.groups = -(-n // per)
        pad = self.groups * per - n
        self.bounds = bin_boundaries(X, self.p)
        bounds = jnp.asarray(self.bounds)
        parts = []
        for gi in range(self.groups):
            xg = X[gi * per: (gi + 1) * per]
            if xg.shape[0] < per:
                xg = np.concatenate(
                    [xg, np.zeros((per - xg.shape[0], f), np.float32)])
            parts.append(_bin_rows(jnp.asarray(xg.reshape(inner, r, f)),
                                   bounds))
        self.bins = jnp.stack(parts)                      # (G, inner, F, R)
        shape = (self.groups, inner, r)
        self.y = jnp.asarray(np.concatenate(
            [y.astype(np.float32), np.zeros(pad, np.float32)]).reshape(shape))
        use = np.concatenate([np.ones(n, np.float32),
                              np.zeros(pad, np.float32)])
        self.use = jnp.asarray(use.reshape(shape))
        pavg = float(np.clip(np.mean(y, dtype=np.float64), 1e-12, 1 - 1e-12))
        self.base = float(np.log(pavg / (1.0 - pavg)))

    # -- judged tree -> the reference's own grid ---------------------------
    def _on_grid(self, tree):
        feat = np.asarray(tree["feature"], np.int64)
        thr = np.asarray(tree["threshold"], np.float32)
        bins, off = [], 0
        for f, t in zip(feat, thr):
            b = self.bounds[f]
            hit = np.flatnonzero(b == t)
            if hit.size:
                bins.append(int(hit[0]))
            else:
                off += 1
                bins.append(int(np.searchsorted(b, t, side="right")) - 1)
        return np.asarray(bins, np.int64), off

    def _rows(self, rows):
        if rows is None:
            return self.use
        lo, hi = rows
        idx = np.arange(self.use.size).reshape(self.use.shape)
        return self.use * jnp.asarray(((idx >= lo) & (idx < hi))
                                      .astype(np.float32))

    def _hists(self, leaf, score, value_type, use):
        g, h = _grad_hess(score, self.y)
        parts = _leaf_hists(self.bins, leaf, g, h, use, self.leaves,
                            value_type)
        total = np.asarray(parts, np.float64).sum(axis=0)  # (F, 256, L, 3)
        return np.ascontiguousarray(np.transpose(total, (2, 0, 1, 3)))

    def _loss_gap(self, score_j, score_ref):
        ref, diff = _loss_sums(score_j, score_ref, self.y, self.use)
        gap = abs(np.asarray(diff, np.float64).sum()) / np.asarray(
            ref, np.float64).sum()
        return float(gap) if np.isfinite(gap) else BIG

    # -- the comparison -----------------------------------------------------
    def compare(self, judged: dict, follow: int = 3, stand_in: dict = None):
        """``judged``: {"base_score", "trees": [{"leaf", "feature",
        "threshold", "leaf_value", "leaf_count"}, ...]} as the timed fit
        returned them (growth steps in order; step i splits ``leaf[i]`` and
        makes leaf i + 1). With ``stand_in`` ({"value_type", "rows"}) the
        answers at every step are not the judged trees' own but those of a
        plain computation in that value type over those rows, under the same
        structure. Returns {name: number}."""
        p = self.p
        trees = judged["trees"][:follow]
        shape = self.y.shape
        base_j = self.base if stand_in else float(judged["base_score"])
        score_j = jnp.full(shape, base_j, jnp.float32)

        # before any tree: the judged base score against the reference's own
        out = {"gain_gap": 0.0, "leaf_gap": 0.0, "count_gap": 0.0,
               "grid_gap": 0.0, "loss_gap": self._loss_gap(
                   score_j, jnp.full(shape, self.base, jnp.float32))}
        for tree in trees:
            step_leaf = np.asarray(tree["leaf"], np.int64)
            step_feat = np.asarray(tree["feature"], np.int64)
            step_bin, off = self._on_grid(tree)
            out["grid_gap"] += float(off)
            leaf = _route(self.bins, jnp.asarray(step_leaf, jnp.int32),
                          jnp.asarray(step_feat, jnp.int32),
                          jnp.asarray(step_bin, jnp.int32))
            # gradients come from the judged answers so far (teacher forcing,
            # as a served token's reference is run over the served tokens):
            # after a tree only a few distinct gradients exist, each shared
            # by all rows of a leaf, and a last-digit difference of a score
            # would move a whole leaf's rounding
            hist = self._hists(leaf, score_j, self.value_type, self.use)
            v_ref, c_ref = _leaf_values(hist, p)
            before = _replay(hist, step_leaf)
            gains = _Gains(p)
            if stand_in:
                hist_s = self._hists(leaf, score_j, stand_in["value_type"],
                                     self._rows(stand_in.get("rows")))
                v_j, c_j = _leaf_values(hist_s, p)
                before_s = _replay(hist_s, step_leaf)
                gains_s = _Gains(p)
            else:
                v_j = np.asarray(tree["leaf_value"], np.float64)
                c_j = np.asarray(tree["leaf_count"], np.int64)
            live = len(step_leaf) + 1
            for i in range(len(step_leaf)):
                tables = [gains.of(hh) for hh in before[i]]
                best = max(float(t.max()) for t in tables)
                if stand_in:
                    ts = [gains_s.of(hh) for hh in before_s[i]]
                    l = int(np.argmax([float(t.max()) for t in ts]))
                    fsel, bsel = np.unravel_index(int(np.argmax(ts[l])),
                                                  ts[l].shape)
                else:
                    l, fsel, bsel = (int(step_leaf[i]), int(step_feat[i]),
                                     int(step_bin[i]))
                took = float(tables[l][fsel, bsel])
                gap = BIG if not np.isfinite(took) or best <= 0 \
                    else (best - took) / best
                out["gain_gap"] = max(out["gain_gap"], gap)
            scale = np.maximum(np.abs(v_ref[:live]),
                               np.median(np.abs(v_ref[:live])))
            lg = np.abs(v_j[:live] - v_ref[:live]) / scale
            out["leaf_gap"] = max(out["leaf_gap"], float(np.nan_to_num(
                lg, nan=BIG, posinf=BIG).max()))
            out["count_gap"] = max(out["count_gap"], float(
                np.abs(c_j[:live] - c_ref[:live]).max()))
            after_ref = _add_leaf_values(score_j, leaf,
                                         jnp.asarray(v_ref, jnp.float32))
            score_j = _add_leaf_values(score_j, leaf,
                                       jnp.asarray(v_j, jnp.float32))
            out["loss_gap"] = max(out["loss_gap"],
                                  self._loss_gap(score_j, after_ref))
        return out


def check(config: dict, inputs: dict, stand_in: dict = None) -> dict:
    """What ``benchmark.run`` calls once the window has closed."""
    ref = Reference(config["params"], inputs["X"], inputs["y"])
    return ref.compare(inputs["judged"], inputs["follow"], stand_in)
