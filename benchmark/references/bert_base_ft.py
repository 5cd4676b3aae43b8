"""Plain reference for configuration ``bert_base_ft``: the first training steps
of the text encoder, in float32 at ``highest`` matrix precision.

It imports nothing of the program and takes nothing the program has made. From
the configuration's file and the run's seed it makes the token ids, the order
of the rows, the initial parameters and the dropout masks itself, by the
rules the configuration states, follows the first ``judged_steps`` optimizer
steps from there, and compares what the timed fit's hook kept of the same
steps:

``loss_gap``     worst of the judged steps: \\|program's loss - reference's\\| /
                 reference's. The estimator's defaults (no warm-up) take the
                 loss from 0.8 to 8 and back to 3 in these steps, and that
                 swing multiplies the rounding of the first two updates: it
                 reads up to a hundred times wider on one seed than on the
                 next, and its limit is wide; it is there for a step that
                 goes wrong after the first. The first step's loss alone
                 (``first_loss_gap``, among the candidates) reads 0.0002 to
                 0.0044 and neither the control nor a fault reads ten times
                 that on every seed, so it is not compared.
``grad_gap``     the first gradient as the optimizer got it, worked out from
                 the first moment after step 0 (``mu / (1 - b1)``): worst leaf
                 of \\| ||program's|| - ||reference's|| \\| over the larger of
                 the reference's norm of that leaf and of the median leaf.
``grad_difference`` the same gradient, all leaves together: ||program's -
                 reference's|| / ||reference's||. A gap of norms grows with
                 the square of random rounding and its worst leaf is a small
                 one whose sums cancel (the head's bias), so it swings fifty
                 times from seed to seed and float8 hides under it; the
                 difference grows in proportion, reads alike on every seed,
                 and is the number that the control has to fail.
``change_gap``   the parameters' change over the judged steps (the program's
                 parameters after the last of them less the initial ones),
                 by the same measure. Leaves whose gradient is nought to
                 rounding in the reference (under a thousandth of the median
                 leaf's, in every judged step: the keys' biases, which a
                 softmax cannot see) move under Adam by round-off alone and
                 are left out of this number, by that rule and not by name.
``step_count_gap`` the optimizer's own count at the window's end, and the
                 steps of the trainer's epoch records, against the steps the
                 hook saw: exact.

The model (``synapseml_tpu/dl/text.py:TransformerEncoder`` as the
configuration describes it): token embedding + learned positions; per layer
LayerNorm -> self-attention (PAD keys and queries masked, softmax, dropout
on the attention weights by one keep-mask a layer that is broadcast over
batch and heads) -> residual -> LayerNorm -> Dense, GELU (tanh form), Dense
-> residual; a last LayerNorm, the [CLS] position, a linear head; mean
softmax cross-entropy; AdamW as ``optax.adamw`` defines it.

flax is asked for two things, as a library and by its public rules, because
the configuration states them in flax's terms: the initial parameters (a
module tree of flax's own layers with the encoder's names, initialised under
``PRNGKey(seed)``) and each layer's dropout key (a child named as the
encoder's attention layer that returns ``make_rng("dropout")`` under the
step's key ``fold_in(PRNGKey(seed), step)``). The masks are drawn here
(``bernoulli(key, 1 - rate, (1, 1, S, S))``) and the forward pass, the loss,
the gradient (``jax.grad`` of the plain function) and the optimizer are
written out below.

The control is this reference with the operands of every matrix product
rounded to float8 (e4m3, scaled per tensor), the step below the stated
bfloat16, put in the program's place; the planted faults likewise
(``benchmark/tools/readings.py``).
"""

from __future__ import annotations

import math
import re
import sys
import zlib

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

PAD_ID, CLS_ID, RESERVED = 0, 1, 2
_TOKEN = re.compile(r"[a-z0-9']+")

# (exponent bits, mantissa bits); rounded with lax.reduce_precision, since
# the TPU compiler drops a pair of astype calls
_VALUE_TYPES = {"float32": None, "bfloat16": (8, 7), "float8_e4m3fn": (4, 3)}
_FLOAT8_TOP = 224.0      # a tensor's largest magnitude is scaled to this
# the step down that would tempt a later PR, per stated precision
LOWER = {"float32": "bfloat16", "bfloat16": "float8_e4m3fn"}

# optax.adamw's defaults, which the trainer leaves alone; TrainConfig's
# weight decay is 0.0
ADAM = {"b1": 0.9, "b2": 0.999, "eps": 1e-8, "weight_decay": 0.0}
DROPOUT = 0.1            # on the attention weights only
ZERO_GRADIENT = 1e-3     # of the median leaf's gradient norm


# ---------------------------------------------------------------------------
# the configuration's rules for the data
# ---------------------------------------------------------------------------

def tokenize(texts, vocab: int, max_len: int) -> np.ndarray:
    """Lower-case words ``[a-z0-9']+``, each to bucket
    ``2 + crc32(word) % (vocab - 2)``; [CLS] (1) first; PAD (0) after."""
    out = np.zeros((len(texts), max_len), np.int32)
    out[:, 0] = CLS_ID
    for i, t in enumerate(texts):
        words = _TOKEN.findall(str(t).lower())[: max_len - 1]
        out[i, 1:1 + len(words)] = [
            RESERVED + zlib.crc32(w.encode()) % (vocab - RESERVED)
            for w in words]
    return out


def epoch_order(seed: int, epoch: int, n: int) -> np.ndarray:
    return np.random.default_rng([int(seed), int(epoch)]).permutation(n)


# ---------------------------------------------------------------------------
# what flax is asked for
# ---------------------------------------------------------------------------

def initial_parameters(cfg: dict, seed: int, classes: int) -> dict:
    import flax.linen as nn

    hidden, heads = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])

    class Tree(nn.Module):
        @nn.compact
        def __call__(self, ids):
            x = nn.Embed(int(cfg["vocab_size"]), hidden, name="tok_embed")(ids)
            self.param("pos_embed", nn.initializers.normal(0.02),
                       (int(cfg["max_position_embeddings"]), hidden))
            for i in range(int(cfg["num_hidden_layers"])):
                y = nn.LayerNorm()(x)
                nn.MultiHeadDotProductAttention(
                    num_heads=heads, name=f"attn_{i}")(y, y)
                y = nn.Dense(int(cfg["intermediate_size"]))(nn.LayerNorm()(x))
                nn.Dense(hidden)(y)
            return nn.Dense(classes, name="head")(nn.LayerNorm()(x)[:, 0])

    variables = Tree().init(jax.random.PRNGKey(int(seed)),
                            jnp.zeros((1, 2), jnp.int32))
    return jax.tree.map(jnp.asarray, dict(variables["params"]))


def dropout_keys(seed: int, step: int, layers: int) -> list:
    import flax.linen as nn

    class Key(nn.Module):
        @nn.compact
        def __call__(self):
            return self.make_rng("dropout")

    class Keys(nn.Module):
        @nn.compact
        def __call__(self):
            return [Key(name=f"attn_{i}")() for i in range(layers)]

    step_key = jax.random.fold_in(jax.random.PRNGKey(int(seed)), int(step))
    return Keys().apply({}, rngs={"dropout": step_key})


def keep_masks(seed: int, step: int, layers: int, length: int) -> jnp.ndarray:
    """(layers, 1, 1, S, S) booleans: one mask a layer, the same for every
    row and head."""
    return jnp.stack([jax.random.bernoulli(k, 1.0 - DROPOUT,
                                           (1, 1, length, length))
                      for k in dropout_keys(seed, step, layers)])


# ---------------------------------------------------------------------------
# the model and its loss, written out
# ---------------------------------------------------------------------------

def _rounder(value_type: str):
    """Rounds an operand of a matrix product to ``value_type``. The rounding
    is seen by the forward pass and, through the operands it saves, by the
    backward pass's products; the cotangents themselves pass unrounded
    (``reduce_precision``'s own derivative would round them unscaled, and
    flush most of them to zero)."""
    bits = _VALUE_TYPES[value_type]
    if bits is None:
        return lambda x: x

    def rounded(x):
        if value_type == "float8_e4m3fn":
            scale = _FLOAT8_TOP / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
            return lax.reduce_precision(x * scale, *bits) / scale
        return lax.reduce_precision(x, *bits)

    return lambda x: x + lax.stop_gradient(rounded(x) - x)


def _layer_norm(x, p, eps):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) * lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def forward(params, ids, keep, cfg: dict, value_type="float32",
            use_mask=True):
    """Logits (rows, classes). ``keep``: (layers, 1, 1, S, S) booleans."""
    r = _rounder(value_type)
    eps = float(cfg["layer_norm_eps"])
    heads = int(cfg["num_attention_heads"])
    depth = int(cfg["hidden_size"]) // heads
    length = ids.shape[1]
    real = ids != PAD_ID
    pair = real[:, None, None, :] & real[:, None, :, None]
    x = params["tok_embed"]["embedding"][ids] + params["pos_embed"][None,
                                                                    :length]
    for i in range(int(cfg["num_hidden_layers"])):
        a = params[f"attn_{i}"]
        y = r(_layer_norm(x, params[f"LayerNorm_{2 * i}"], eps))
        q, k, v = (jnp.einsum("bsh,hnd->bsnd", y, r(a[n]["kernel"]))
                   + a[n]["bias"] for n in ("query", "key", "value"))
        scores = jnp.einsum("bqnd,bknd->bnqk", r(q / math.sqrt(depth)), r(k))
        if use_mask:
            scores = jnp.where(pair, scores, jnp.finfo(scores.dtype).min)
        weights = jax.nn.softmax(scores, axis=-1)
        weights = weights * (keep[i] / (1.0 - DROPOUT))
        out = jnp.einsum("bnqk,bknd->bqnd", r(weights), r(v))
        x = x + jnp.einsum("bqnd,ndh->bqh", r(out), r(a["out"]["kernel"])) \
            + a["out"]["bias"]
        d1, d2 = params[f"Dense_{2 * i}"], params[f"Dense_{2 * i + 1}"]
        y = r(_layer_norm(x, params[f"LayerNorm_{2 * i + 1}"], eps))
        y = _gelu(y @ r(d1["kernel"]) + d1["bias"])
        x = x + r(y) @ r(d2["kernel"]) + d2["bias"]
    last = 2 * int(cfg["num_hidden_layers"])
    cls = _layer_norm(x, params[f"LayerNorm_{last}"], eps)[:, 0]
    return cls @ params["head"]["kernel"] + params["head"]["bias"]


def loss_sum(params, ids, y, keep, cfg, value_type, use_mask):
    """Sum over the rows of the softmax cross-entropy."""
    logits = forward(params, ids, keep, cfg, value_type, use_mask)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, y[:, None], axis=-1).sum()


def flatten(tree) -> dict:
    return {"/".join(str(getattr(k, "key", k)) for k in path): leaf
            for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]}


# ---------------------------------------------------------------------------
# the judged steps
# ---------------------------------------------------------------------------

class Reference:
    def __init__(self, config: dict, texts, labels, seed: int, batch: int,
                 steps: int):
        self.cfg, self.seed, self.batch, self.steps = (config, int(seed),
                                                       int(batch), int(steps))
        self.lr = float(config["estimator"]["learningRate"])
        self.stated = config["estimator"]["precision"]
        self.block = int(config.get("reference_rows_per_block", 8))
        classes, y = np.unique(np.asarray(labels), return_inverse=True)
        self.classes = len(classes)
        ids = tokenize(texts, int(config["vocab_size"]),
                       int(config["max_position_embeddings"]))
        order = epoch_order(self.seed, 0, len(ids))
        self.ids = [ids[order[k * batch:(k + 1) * batch]]
                    for k in range(self.steps)]
        self.y = [y[order[k * batch:(k + 1) * batch]].astype(np.int32)
                  for k in range(self.steps)]
        self._grad = {}
        self._sound = None

    def _block_grad(self, value_type, use_mask):
        key = (value_type, use_mask)
        if key not in self._grad:
            cfg = self.cfg
            self._grad[key] = jax.jit(jax.value_and_grad(
                lambda p, ids, y, keep: loss_sum(p, ids, y, keep, cfg,
                                                 value_type, use_mask)))
        return self._grad[key]

    def loss_and_grad(self, params, step, value_type, use_mask, rows):
        """Mean loss over ``rows`` rows of the step's batch and its gradient,
        in blocks of rows."""
        fn = self._block_grad(value_type, use_mask)
        keep = keep_masks(self.seed, step, int(self.cfg["num_hidden_layers"]),
                          self.ids[step].shape[1])
        total, grads = 0.0, None
        for lo in range(0, rows, self.block):
            hi = min(lo + self.block, rows)
            part, g = fn(params, jnp.asarray(self.ids[step][lo:hi]),
                         jnp.asarray(self.y[step][lo:hi]), keep)
            total = total + part
            grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
        return total / rows, jax.tree.map(lambda g: g / rows, grads)

    def follow(self, how: dict = None) -> dict:
        """The judged steps from the seed: {"losses", "mu" (first moment
        after step 0), "params" (after the last step), "grad_norms" (of every
        step, by leaf)}; ``how`` plants the control or a fault."""
        how = how or {}
        value_type = how.get("value_type", "float32")
        use_mask = how.get("mask", True)
        rows = self.batch // 2 if how.get("rows") == "half" else self.batch
        b1, b2, eps = ADAM["b1"], ADAM["b2"], ADAM["eps"]
        with jax.default_matmul_precision("highest"):
            params = initial_parameters(self.cfg, self.seed, self.classes)
            mu = jax.tree.map(jnp.zeros_like, params)
            nu = jax.tree.map(jnp.zeros_like, params)
            out = {"losses": [], "grad_norms": [], "initial": params}
            for step in range(self.steps):
                loss, g = self.loss_and_grad(params, step, value_type,
                                             use_mask, rows)
                out["losses"].append(float(loss))
                out["grad_norms"].append(
                    {k: float(jnp.linalg.norm(v.ravel()))
                     for k, v in flatten(g).items()})
                if step == 0:
                    out["first_gradient"] = g
                if how.get("state") == "unchanged":
                    continue         # the step hands its state on as it was
                if not (how.get("moment") == "stale" and step > 0):
                    mu = jax.tree.map(lambda m, x: b1 * m + (1 - b1) * x,
                                      mu, g)
                nu = jax.tree.map(lambda n, x: b2 * n + (1 - b2) * x * x,
                                  nu, g)
                c1, c2 = 1 - b1 ** (step + 1), 1 - b2 ** (step + 1)
                params = jax.tree.map(
                    lambda p, m, n: p - self.lr * (
                        (m / c1) / (jnp.sqrt(n / c2) + eps)
                        + ADAM["weight_decay"] * p), params, mu, nu)
                if step == 0:
                    out["mu"] = mu
            out.setdefault("mu", mu)
            out["params"] = params
        return out

    def sound(self) -> dict:
        if self._sound is None:
            self._sound = self.follow()
        return self._sound

    def compare(self, judged: dict, candidates: bool = False) -> dict:
        """The numbers of ``judged`` ({"losses", "mu", "params"}: flat dicts
        of arrays or trees) against the reference's own steps. With
        ``candidates`` also the numbers that were read beside them when the
        limits were set (PERF.md section 4) and are not compared."""
        ref = self.sound()
        b1 = ADAM["b1"]
        losses = [abs(a - b) / abs(b)
                  for a, b in zip(judged["losses"], ref["losses"])]
        if len(judged["losses"]) != self.steps:
            losses.append(1.0)

        def flat_of(tree, scale=1.0, less=None):
            flat = tree if _is_flat(tree) else flatten(tree)
            return {k: (jnp.asarray(v) - (0.0 if less is None else less[k]))
                    * scale for k, v in flat.items()}

        initial = flatten(ref["initial"])
        g_ref = flatten(ref["first_gradient"])
        g_prog = flat_of(judged["mu"], 1.0 / (1.0 - b1))
        c_ref = flat_of(ref["params"], less=initial)
        c_prog = flat_of(judged["params"], less=initial)
        median_g = [float(np.median(list(n.values())))
                    for n in ref["grad_norms"]]
        moved = [k for k in g_ref if any(
            n[k] >= ZERO_GRADIENT * m
            for n, m in zip(ref["grad_norms"], median_g))]
        grad = _gaps(g_prog, g_ref, list(g_ref))
        change = _gaps(c_prog, c_ref, moved)
        grad_at, change_at = (max(d, key=d.get) for d in (grad, change))
        print(f"reference: losses {ref['losses']}; judged {judged['losses']}; "
              f"grad_gap at {grad_at}, change_gap at {change_at}; "
              f"{len(g_ref) - len(moved)} leaf(s) of {len(g_ref)} left out of "
              f"the change: {sorted(set(g_ref) - set(moved))[:4]}",
              file=sys.stderr)
        out = {"loss_gap": float(max(losses)),
               "grad_gap": grad[grad_at],
               "grad_difference": _difference(g_prog, g_ref, list(g_ref)),
               "change_gap": change[change_at]}
        if candidates:
            out.update(
                first_loss_gap=float(losses[0]),
                grad_gap_median_leaf=float(np.median(list(grad.values()))),
                change_gap_median_leaf=float(np.median(list(change.values()))),
                change_difference=_difference(c_prog, c_ref, moved),
                where={"loss_gap": f"step {int(np.argmax(losses))}",
                       "grad_gap": grad_at, "change_gap": change_at})
        return out


def _norm(x) -> float:
    return float(jnp.linalg.norm(jnp.ravel(x)))


def _gaps(got: dict, want: dict, leaves) -> dict:
    """{leaf: | ||got|| - ||want|| | / max(||want||, the median leaf's)}."""
    norms = {k: _norm(want[k]) for k in leaves}
    median = float(np.median(list(norms.values())))
    return {k: abs((_norm(got[k]) if k in got else 0.0) - norms[k])
            / max(norms[k], median) for k in leaves}


def _difference(got: dict, want: dict, leaves) -> float:
    """||got - want|| / ||want|| over all the leaves together."""
    num = sum(_norm(got[k] - want[k]) ** 2 for k in leaves)
    return math.sqrt(num / sum(_norm(want[k]) ** 2 for k in leaves))


def _is_flat(tree) -> bool:
    return all(not isinstance(v, dict) for v in tree.values())


def stand_in_plans(config: dict) -> dict:
    """What is put in the program's place to show that ``correct`` fails: the
    control (the stated precision's next step down) and the planted
    faults, each as ``Reference.follow``'s ``how``."""
    return {
        "control": {"value_type": LOWER[config["estimator"]["precision"]]},
        "state_unchanged": {"state": "unchanged"},
        "half_batch": {"rows": "half"},
        "mask_dropped": {"mask": False},
        "moment_stale": {"moment": "stale"},
    }


def stand_ins(config: dict, traffic: dict, inputs: dict,
              only=None) -> dict:
    """{name: numbers} of the program and of every stand-in (or of those in
    ``only``), the candidates with them: ``benchmark/tools/readings``."""
    ref = Reference(config, inputs["texts"], inputs["labels"], inputs["seed"],
                    inputs["batch"], inputs["steps"])
    out = {"program": dict(ref.compare(inputs["judged"], True),
                           step_count_gap=_count_gap(inputs["judged"]))}
    for name, how in stand_in_plans(config).items():
        if only is None or name in only:
            out[name] = dict(ref.compare(ref.follow(how), True),
                             step_count_gap=0.0)
    return out


def check(config: dict, inputs: dict, how: dict = None,
          reference: Reference = None) -> dict:
    """The numbers ``run.py`` holds against the configuration's limits. With
    ``how`` the reference's own steps under the control or a fault stand in
    the program's place."""
    judged = inputs["judged"]
    ref = reference or Reference(config, inputs["texts"], inputs["labels"],
                                 inputs["seed"], inputs["batch"],
                                 inputs["steps"])
    if how is not None:
        numbers = ref.compare(ref.follow(how))
        numbers["step_count_gap"] = 0.0
        return numbers
    return dict(ref.compare(judged), step_count_gap=_count_gap(judged))


def _count_gap(judged: dict) -> float:
    seen = int(judged["hook_steps"])
    return float(abs(int(judged["opt_count"]) - seen)
                 + abs(int(judged["program_steps"]) - seen))
