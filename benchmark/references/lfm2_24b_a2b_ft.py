"""Plain reference for configuration ``lfm2_24b_a2b_ft``: the first training
steps of the gated short-convolution / sparse-expert backbone, in float32 at
``highest`` matrix precision.

It imports nothing of the program and takes nothing the program has made. From
the configuration's file and the run's seed it makes the token ids, the order
of the rows and the initial parameters itself, by the rules the configuration
states, follows the first ``judged_steps`` optimizer steps from there, and
compares what the timed fit's hook kept of the same steps. The steps, the
optimizer and the comparison's helpers are ``nemotron3_nano_ft``'s (the same
estimator, trainer and hook); the model and the compared numbers are this
file's:

``first_loss_gap``  the first judged step's loss: \\|program's - reference's\\|
                    / reference's.
``grad_gap``        the first gradient as the optimizer got it (the first
                    moment after step 0 over ``1 - b1``): the median leaf's
                    gap of norms against the larger of the reference's norm
                    of that leaf and of the median leaf's.
``routed_gap``      the same gradient's routed experts (``experts_up``,
                    ``experts_down`` of every expert layer) as one vector: the
                    gap of their norms over the reference's.
``routing_difference``  the same gradient's routed experts and routers:
                    ||program's - reference's|| over the root of their rows'
                    squared gradient norms. What the choice of experts
                    decides: a selection that drops the bias moves a quarter
                    of the choices, where bfloat16 rounding upstream of the
                    router moves a few in a hundred.
``grad_difference`` the same gradient, all leaves together: ||program's -
                    reference's|| over the root of the rows' squared gradient
                    norms.
``leaf_difference`` the same gradient, the worst leaf outside the expert
                    sublayers: ||program's - reference's|| over the root of
                    that leaf's own rows' squared norms. Every leaf of an
                    expert sublayer (its norm too: with no shared expert the
                    sublayer's whole output hangs on the choice) moves with a
                    flip of the chosen, which bfloat16 rounding upstream of
                    the router makes in a sound run; ``routed_gap`` and
                    ``routing_difference`` judge those.
``change_gap``      the parameters' change over the judged steps: the median
                    leaf's gap of norms, over the leaves whose gradient is not
                    nought.
``step_count_gap``  the optimizer's own count at the window's end, and the
                    steps of the trainer's epoch records, against the steps
                    the hook saw: exact.

The ``expert_bias`` buffers take no gradient and do not move: they are left
out of every number (PERF.md section 4 gives the readings and limits).

The model (the ``lfm2_moe`` family as the configuration's file describes it):
``h = Embed(ids)``; for each held layer ``h = h + Op(RMSNorm(h))``, then ``h =
h + FFN(RMSNorm(h))``; a last RMSNorm; the mean over each row's non-PAD
positions; a linear head without bias; mean softmax cross-entropy; AdamW as
``optax.adamw`` defines it. The parameters of layer ``i``'s operator are
``layer_{2i}``, of its FFN ``layer_{2i+1}``.

* ``conv``: ``[B; C; x] = h W_in``; ``v_t = sum_{j<K} w_j (B x)_{t-K+1+j}``
  written out as K shifted products; ``(C v) W_out``.
* ``full_attention``: q, k and v; an RMSNorm over each head's ``head_dim``
  for q and k; the rotary embedding, the pairs ``(i, i + D/2)`` turned by
  ``t / theta^(2i / D)``; softmax over all earlier keys, head by head (eight
  in a pass).
* dense FFN: ``(silu(h W_1) * h W_3) W_2``, ``[W_1 | W_3]`` one matrix.
* experts: sigmoid scores ``s`` over all the published experts; the
  ``top_k`` largest of ``s + expert_bias`` chosen; weights ``s`` of the
  chosen over their sum (+1e-6), scaled; the held experts as a plain loop,
  each over every token and weighted by the token's weight for it (nought
  where it was not chosen); what absent experts would add is left out. A PAD
  position is not routed.

Every sublayer and every group of heads is recomputed in the backward pass
(``jax.checkpoint``); the rows of a step go through in blocks.

flax is asked for the initial parameters, as a library and by its public
rules (a module tree with the backbone's names and the stated initializers
under ``PRNGKey(seed)``).

The control is this reference with the operands of every matrix product that
the configuration states in bfloat16 rounded to float8 (e4m3, scaled per
tensor); the planted faults are switches of one compiled program, one for
each mechanism this configuration brings, plus the optimizer's
(``benchmark/tools/readings.py``). A stand-in plays the program, so its
products run at the default matrix precision, the router's excepted.
"""

from __future__ import annotations

import math
import sys

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.references import nemotron3_nano_ft as steps
from benchmark.references.nemotron3_nano_ft import (
    ADAM, LOWER, PAD_ID, ZERO_GRADIENT, _adam_leaf, _gap_of_sums, _gaps,
    _leaf_norms, _rms_norm, _root_sum_square, _rounder, _silu, _unflatten,
    flatten)

ROUTE_EPS = 1e-6


class Sizes:
    def __init__(self, cfg: dict):
        g = lambda k: int(cfg[k])
        self.hidden, self.vocab = g("hidden_size"), g("vocab_size")
        self.layers = [str(t) for t in cfg["layer_types"][:g(
            "num_hidden_layers")]]
        self.dense = g("num_dense_layers")
        self.heads, self.kv = g("num_attention_heads"), g("num_key_value_heads")
        self.head_dim = self.hidden // self.heads
        self.conv = g("conv_L_cache")
        self.width = g("intermediate_size")
        self.router = g("router_experts")
        self.held = [int(e) for e in cfg["held_experts"]]
        assert len(self.held) == g("num_experts")
        self.top_k = g("num_experts_per_tok")
        self.expert_width = g("moe_intermediate_size")
        self.scaling = float(cfg["routed_scaling_factor"])
        self.eps = float(cfg["norm_eps"])
        self.theta = float(cfg["rope_parameters"]["rope_theta"])
        self.bias_std = float(
            cfg["estimator"]["architecture"]["expert_bias_std"])

    def sublayers(self) -> list:
        """(kind, name) of every sublayer, in order: each layer's operator
        ('conv' or 'attention'), then its FFN ('dense' or 'experts')."""
        out = []
        for i, t in enumerate(self.layers):
            out.append(("conv" if t == "conv" else "attention",
                        f"layer_{2 * i}"))
            out.append(("dense" if i < self.dense else "experts",
                        f"layer_{2 * i + 1}"))
        return out


def initial_parameters(cfg: dict, seed: int, classes: int) -> dict:
    """The stated initializers: matrices, router and head normal(0.02); norm
    weights one; the convolution uniform(+-conv_L_cache^-1/2);
    ``expert_bias`` normal(``expert_bias_std``); the embedding flax's
    ``Embed`` default."""
    import flax.linen as nn

    z = Sizes(cfg)
    dense, ones = nn.initializers.normal(0.02), nn.initializers.ones
    d, hd = z.hidden, z.head_dim

    def uniform(bound):
        return lambda key, shape: jax.random.uniform(
            key, shape, jnp.float32, -bound, bound)

    shapes = {
        "conv": {"in_proj": (dense, (d, 3 * d)),
                 "conv_kernel": (uniform(z.conv ** -0.5), (z.conv, d)),
                 "out_proj": (dense, (d, d))},
        "attention": {"q": (dense, (d, z.heads * hd)),
                      "k": (dense, (d, z.kv * hd)),
                      "v": (dense, (d, z.kv * hd)),
                      "o": (dense, (z.heads * hd, d)),
                      "q_norm": (ones, (hd,)), "k_norm": (ones, (hd,))},
        "dense": {"up": (dense, (d, 2 * z.width)),
                  "down": (dense, (z.width, d))},
        "experts": {"router": (dense, (d, z.router)),
                    "expert_bias": (nn.initializers.normal(z.bias_std),
                                    (z.router,)),
                    "experts_up": (dense, (len(z.held), d,
                                           2 * z.expert_width)),
                    "experts_down": (dense, (len(z.held), z.expert_width, d))},
    }

    def layer_of(kind):
        class Layer(nn.Module):
            @nn.compact
            def __call__(self):
                self.param("norm", ones, (d,))
                for name, (init, shape) in shapes[kind].items():
                    self.param(name, init, shape)

        return Layer

    class Tree(nn.Module):
        @nn.compact
        def __call__(self, ids):
            x = nn.Embed(z.vocab, d, name="tok_embed")(ids)
            for kind, name in z.sublayers():
                layer_of(kind)(name=name)()
            self.param("final_norm", ones, (d,))
            return nn.Dense(classes, use_bias=False, kernel_init=dense,
                            name="head")(x[:, 0])

    variables = Tree().init(jax.random.PRNGKey(int(seed)),
                            jnp.zeros((1, 2), jnp.int32))
    return jax.tree.map(jnp.asarray, dict(variables["params"]))


# ---------------------------------------------------------------------------
# the model and its loss, written out
# ---------------------------------------------------------------------------

def _short_conv(p, x, z: Sizes, r, how):
    length = x.shape[1]
    b, c, xs = jnp.split(r(x) @ r(p["in_proj"]), 3, -1)
    u = b * xs
    # one more position at the end: the fault "conv_acausal" reads it
    padded = jnp.pad(u, ((0, 0), (z.conv - 1, 1), (0, 0)))
    ahead = jnp.where(how["conv_acausal"], 1, 0)
    v = sum(lax.dynamic_slice_in_dim(padded, j + ahead, length, 1)
            * p["conv_kernel"][j] for j in range(z.conv))
    # the fault "conv_ungated": the output gate C left out
    y = jnp.where(how["conv_ungated"], v, c * v)
    return r(y) @ r(p["out_proj"])


def _rope(x, theta):
    """x (rows, S, heads, D): the pairs (i, i + D/2) turned by t /
    theta^(2i / D) at position t."""
    length, d = x.shape[1], x.shape[-1]
    half = d // 2
    freq = theta ** (-(2.0 * jnp.arange(half, dtype=jnp.float32)) / d)
    angle = jnp.arange(length, dtype=jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(angle)[None, :, None], jnp.sin(angle)[None, :, None]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _attention(p, x, z: Sizes, r, how):
    rows, length, _ = x.shape
    per = z.heads // z.kv
    q = (r(x) @ r(p["q"])).reshape(rows, length, z.heads, z.head_dim)
    k = (r(x) @ r(p["k"])).reshape(rows, length, z.kv, z.head_dim)
    v = (r(x) @ r(p["v"])).reshape(rows, length, z.kv, z.head_dim)
    q = jnp.where(how["qk_norm_dropped"], q, _rms_norm(q, p["q_norm"], z.eps))
    k = jnp.where(how["qk_norm_dropped"], k, _rms_norm(k, p["k_norm"], z.eps))
    q = jnp.where(how["rope_dropped"], q, _rope(q, z.theta))
    k = jnp.where(how["rope_dropped"], k, _rope(k, z.theta))
    t = jnp.arange(length)
    seen = t[:, None] >= t[None, :]

    def head(h):
        g = h // per
        s = jnp.einsum("rtd,rsd->rts", r(jnp.take(q, h, axis=2)),
                       r(jnp.take(k, g, axis=2))) / math.sqrt(z.head_dim)
        w = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.einsum("rts,rsd->rtd", r(w), r(jnp.take(v, g, axis=2)))

    together = math.gcd(z.heads, 8)
    out = lax.map(jax.checkpoint(jax.vmap(head)),
                  jnp.arange(z.heads).reshape(-1, together))
    out = out.reshape((z.heads,) + out.shape[2:])          # (heads, rows, S, D)
    out = out.transpose(1, 2, 0, 3).reshape(rows, length, z.heads * z.head_dim)
    return r(out) @ r(p["o"])


def _swiglu(x, up, down, r):
    w1, w3 = jnp.split(up, 2, -1)
    return r(_silu(r(x) @ r(w1)) * (r(x) @ r(w3))) @ r(down)


def _experts(p, x, z: Sizes, r, how, real=None):
    """``real`` (x's leading shape): the positions that hold a token."""
    # float32 at ``highest`` whatever the other products are, never rounded
    scores = jax.nn.sigmoid(jnp.matmul(x, p["router"],
                                       precision=lax.Precision.HIGHEST))
    bias = lax.stop_gradient(p["expert_bias"])       # a buffer: never trained
    # the fault "bias_dropped": selection from s alone
    _, ids = lax.top_k(scores + jnp.where(how["bias_dropped"], 0.0, bias),
                       z.top_k)
    # the fault "bias_in_weights": the weights read s + b too
    read = jnp.where(how["bias_in_weights"], scores + bias, scores)
    chosen = jnp.take_along_axis(read, ids, -1)
    weights = chosen / (chosen.sum(-1, keepdims=True) + ROUTE_EPS) * z.scaling
    if real is not None:
        weights = jnp.where(real[..., None], weights, 0.0)
    routed = 0.0
    for j, e in enumerate(z.held):
        mine = jnp.where(ids == e, weights, 0.0).sum(-1, keepdims=True)
        routed = routed + mine * _swiglu(x, p["experts_up"][j],
                                         p["experts_down"][j], r)
    return jnp.where(how["experts_unrouted"], 0.0, routed)


# the planted faults that are switches of the one compiled program (all
# False: the sound model, to the last bit)
FAULTS = ("bias_in_weights", "bias_dropped", "conv_ungated", "conv_acausal",
          "rope_dropped", "qk_norm_dropped", "experts_unrouted")


def switches(*on) -> dict:
    return {name: jnp.asarray(name in on) for name in FAULTS}


def forward(params, ids, cfg: dict, value_type="float32", how=None):
    """Logits (rows, classes). ``how``: ``switches(...)``."""
    how = how if how is not None else switches()
    z = Sizes(cfg)
    r = _rounder(value_type)
    h = params["tok_embed"]["embedding"][ids]
    real = ids != PAD_ID
    for kind, name in z.sublayers():
        def layer(p, h, kind=kind):
            y = _rms_norm(h, p["norm"], z.eps)
            if kind == "conv":
                return h + _short_conv(p, y, z, r, how)
            if kind == "attention":
                return h + _attention(p, y, z, r, how)
            if kind == "dense":
                return h + _swiglu(y, p["up"], p["down"], r)
            rows, length, d = y.shape
            return h + _experts(p, y.reshape(-1, d), z, r, how,
                                real.reshape(-1)).reshape(rows, length, d)

        h = jax.checkpoint(layer)(params[name], h)
    h = _rms_norm(h, params["final_norm"], z.eps)
    pooled = (jnp.where(real[..., None], h, 0.0).sum(1)
              / jnp.maximum(real.sum(-1, keepdims=True), 1))
    return pooled @ params["head"]["kernel"]


def loss_sum(params, ids, y, cfg, value_type, how):
    """Sum over the rows of the softmax cross-entropy."""
    logp = jax.nn.log_softmax(forward(params, ids, cfg, value_type, how), -1)
    return -jnp.take_along_axis(logp, y[:, None], axis=-1).sum()


# ---------------------------------------------------------------------------
# the judged steps
# ---------------------------------------------------------------------------

class Reference(steps.Reference):
    """``nemotron3_nano_ft``'s steps (tokens, order, blocks of rows, AdamW by
    hand, what the hook would keep) over this file's model."""

    def _block_grad(self, value_type):
        if value_type not in self._grad:
            cfg = self.cfg
            self._grad[value_type] = jax.jit(jax.value_and_grad(
                lambda p, ids, y, how: loss_sum(p, ids, y, cfg, value_type,
                                                how)))
        return self._grad[value_type]

    def follow(self, how: dict = None) -> dict:
        """The judged steps from the seed: {"losses", "first_gradient",
        "change", "grad_norms", "rows_norms"}, the trees flat and on the
        host; ``how`` plants the control or a fault."""
        matmul = "highest" if how is None else "default"
        how = dict(how or {})
        value_type = how.get("value_type", "float32")
        rows = self.batch // 2 if how.get("rows") == "half" else self.batch
        state, moment = how.get("state_kept"), how.get("moment")
        how = switches(*how.get("switch", ()))
        with jax.default_matmul_precision(matmul):
            params = initial_parameters(self.cfg, self.seed, self.classes)
            if self._initial is None:
                self._initial = {k: np.asarray(v)
                                 for k, v in flatten(params).items()}
            out = {"losses": [], "grad_norms": []}
            mu = {k: np.zeros_like(v) for k, v in self._initial.items()}
            nu = {k: np.zeros_like(v) for k, v in self._initial.items()}
            for step in range(self.steps):
                loss, g, rows_norms = self.loss_and_grad(
                    params, step, value_type, how, rows)
                out["losses"].append(float(loss))
                g = flatten(g)
                out["grad_norms"].append(
                    {k: float(jnp.linalg.norm(v.ravel())) for k, v in g.items()})
                if step == 0:
                    out["rows_norms"] = rows_norms
                    out["first_gradient"] = {k: np.asarray(v)
                                             for k, v in g.items()}
                if state == "unchanged":
                    continue
                c1 = 1 - ADAM["b1"] ** (step + 1)
                c2 = 1 - ADAM["b2"] ** (step + 1)
                flat, new = flatten(params), {}
                for k in flat:
                    new[k], m, n = _adam_leaf(
                        flat[k], mu[k], nu[k], g[k], self.lr, c1, c2,
                        moment == "stale" and step > 0)
                    mu[k], nu[k] = np.asarray(m), np.asarray(n)
                params = _unflatten(new)
                del g, flat, new
            del mu, nu
            out["change"] = {k: np.asarray(v) - self._initial[k]
                             for k, v in flatten(params).items()}
        return out

    def compare(self, judged: dict, candidates: bool = False) -> dict:
        ref = self.sound()
        losses = [abs(a - b) / abs(b)
                  for a, b in zip(judged["losses"], ref["losses"])]
        if len(judged["losses"]) != self.steps:
            losses.append(1.0)
        g_ref, c_ref = ref["first_gradient"], ref["change"]
        scale = 1.0 / (1.0 - ADAM["b1"])
        trained = [k for k in g_ref if not is_buffer(k)]
        median_g = [float(np.median([n[k] for k in trained]))
                    for n in ref["grad_norms"]]
        moved = [k for k in trained if any(
            n[k] >= ZERO_GRADIENT * m
            for n, m in zip(ref["grad_norms"], median_g))]

        def change_of(k):
            if "change" in judged:
                return judged["change"][k]
            return judged["params"][k] - self._initial[k]

        grad = _leaf_norms(lambda k: judged["mu"][k] * scale, g_ref, trained)
        change = _leaf_norms(change_of, c_ref, moved)
        print(f"reference: losses {ref['losses']}; judged {judged['losses']}; "
              f"{len(trained) - len(moved)} leaf(s) of {len(trained)} left "
              f"out of the change: {sorted(set(trained) - set(moved))[:4]}",
              file=sys.stderr)
        return judge(self.cfg, losses, grad, change, ref["rows_norms"],
                     candidates)


def is_buffer(leaf: str) -> bool:
    return leaf.endswith("/expert_bias")


def routed_leaves(cfg: dict) -> list:
    """The routed experts' leaves of every expert layer."""
    z = Sizes(cfg)
    return [f"{name}/{leaf}" for kind, name in z.sublayers()
            if kind == "experts" for leaf in ("experts_up", "experts_down")]


def judge(cfg: dict, loss_gaps, grad: dict, change: dict, rows_norms: dict,
          candidates: bool = False) -> dict:
    """The compared numbers from what was read: the judged steps' loss gaps,
    {leaf: [program's norm, reference's norm, norm of the difference]} of
    the first gradient (``grad``) and of the change (``change``, the leaves
    that move), and {leaf: the root of the rows' squared norms of its first
    gradient} (``rows_norms``, the reference's). Data in, numbers out: a
    recorded reading is judged again by the tests as a run judges it."""
    grad = {k: v for k, v in grad.items() if not is_buffer(k)}
    routed = routed_leaves(cfg)
    routers = sorted(k for k in grad if k.endswith("/router"))
    grad_gaps, change_gaps = _gaps(grad), _gaps(change)
    rows_norm = _root_sum_square(rows_norms[k] for k in grad)
    experts = {name for kind, name in Sizes(cfg).sublayers()
               if kind == "experts"}
    own = {k: d / rows_norms[k] for k, (_, _, d) in grad.items()
           if k.split("/")[0] not in experts}
    own_at = max(own, key=own.get)
    out = {"first_loss_gap": float(loss_gaps[0]),
           "grad_gap": float(np.median(list(grad_gaps.values()))),
           "routed_gap": _gap_of_sums(grad, routed),
           "routing_difference": _root_sum_square(
               grad[k][2] for k in routed + routers) / _root_sum_square(
               rows_norms[k] for k in routed + routers),
           "grad_difference": _root_sum_square(
               d for _, _, d in grad.values()) / rows_norm,
           "leaf_difference": own[own_at],
           "change_gap": float(np.median(list(change_gaps.values())))}
    worst = max(grad_gaps, key=grad_gaps.get)
    print(f"judged: worst gradient leaf {worst} {grad_gaps[worst]:.4g}; "
          f"worst own leaf {own_at} {own[own_at]:.4g}", file=sys.stderr)
    if candidates:
        change_at = max(change_gaps, key=change_gaps.get)
        out.update(
            loss_gaps=[float(g) for g in loss_gaps],
            grad_gap_worst_leaf=grad_gaps[worst],
            gradient_norm=_root_sum_square(w for _, w, _ in grad.values()),
            rows_norm=rows_norm,
            change_gap_worst_leaf=change_gaps[change_at],
            leaf_norms={"grad": grad, "change": change},
            rows_norms=rows_norms,
            where={"grad_gap": f"median of {len(grad)} leaves",
                   "routed_gap": f"{len(routed)} routed leaves",
                   "routing_difference": f"{len(routed + routers)} leaves",
                   "change_gap": f"median of {len(change)} leaves",
                   "leaf_difference": own_at,
                   "grad_gap_worst_leaf": worst,
                   "change_gap_worst_leaf": change_at})
    return {k: v for k, v in out.items()
            if candidates or k in cfg["limits"]}


def stand_in_plans(config: dict) -> dict:
    """The control (the stated precision's next step down) and the planted
    faults, each as ``Reference.follow``'s ``how``."""
    return {
        "control": {"value_type": LOWER[config["estimator"]["precision"]]},
        **{name: {"switch": (name,)} for name in FAULTS},
        "half_batch": {"rows": "half"},
        "moment_stale": {"moment": "stale"},
        "state_unchanged": {"state_kept": "unchanged"},
    }


_KEPT = []     # [inputs, Reference]: the last run's, so that its own steps
               # are followed once for ``check`` and for ``stand_ins``


def _reference_of(config: dict, inputs: dict) -> Reference:
    if not (_KEPT and _KEPT[0] is inputs):
        _KEPT[:] = [inputs, Reference(
            config, inputs["texts"], inputs["labels"], inputs["seed"],
            inputs["batch"], inputs["steps"])]
    return _KEPT[1]


def stand_ins(config: dict, traffic: dict, inputs: dict,
              only=None) -> dict:
    """{name: numbers} of the program and of every stand-in (or of those in
    ``only``), the candidates with them: ``benchmark/tools/readings``."""
    ref = _reference_of(config, inputs)
    out = {"program": dict(ref.compare(inputs["judged"], True),
                           step_count_gap=steps._count_gap(inputs["judged"]))}
    for name, how in stand_in_plans(config).items():
        if only is None or name in only:
            out[name] = dict(ref.compare(ref.judged_of(ref.follow(how)),
                                         True), step_count_gap=0.0)
            print(f"stand-in {name}: {out[name]}", file=sys.stderr, flush=True)
    return out


def check(config: dict, inputs: dict, how: dict = None,
          reference: Reference = None) -> dict:
    """The numbers ``run.py`` holds against the configuration's limits. With
    ``how`` the reference's own steps under the control or a fault stand in
    the program's place."""
    judged = inputs["judged"]
    ref = reference or _reference_of(config, inputs)
    if how is not None:
        numbers = ref.compare(ref.judged_of(ref.follow(how)))
        numbers["step_count_gap"] = 0.0
        return numbers
    return dict(ref.compare(judged), step_count_gap=steps._count_gap(judged))
