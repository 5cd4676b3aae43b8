"""Plain reference for configuration ``nemotron3_nano_ft``: the first training
steps of the hybrid state-space / sparse-expert backbone, in float32 at
``highest`` matrix precision.

It imports nothing of the program and takes nothing the program has made. From
the configuration's file and the run's seed it makes the token ids, the order
of the rows and the initial parameters itself, by the rules the configuration
states, follows the first ``judged_steps`` optimizer steps from there, and
compares what the timed fit's hook kept of the same steps:

``first_loss_gap``  the first judged step's loss: \\|program's - reference's\\|
                    / reference's. Not the worst step's: the first update
                    moves the loss of four rows anywhere from 0.04 to 6, and
                    a gap relative to a loss near nought is large with
                    nothing wrong (0.14 on seed 134403365 at a loss of
                    0.037, 0.26 on another seed), while the first step reads
                    at most 0.0015 in a sound run and a batch half left out
                    or PAD taken for words 0.04 or more. A fault of the
                    update shows in ``change_gap`` and ``step_count_gap``
                    (PERF.md section 4).
``grad_gap``        the first gradient as the optimizer got it (the first
                    moment after step 0 over ``1 - b1``): the **median
                    leaf's** \\| ||program's|| - ||reference's|| \\| over the
                    larger of the reference's norm of that leaf and of the
                    median leaf's. Not the worst leaf's: the step's gradient
                    is the mean of four rows of two classes, which cancel,
                    and most in the leaves next to the head, while a row's
                    rounding does not cancel. On seed 134403365 the mean's
                    norm is 0.16 of the rows' and the last expert layer's
                    ``shared_down`` read 0.045, the head 0.035, with nothing
                    wrong; ``state_reset`` read 0.045 at its worst leaf there
                    too. The median leaf reads
                    at most 0.0043 in a sound run, the control 0.035 and
                    0.065 (PERF.md section 4).
``routed_gap``      the same gradient's routed experts (``experts_up``,
                    ``experts_down`` of every expert layer), the eight leaves
                    as one: the gap of their norms over the reference's.
                    Their leaves hang on choices that the program may rightly
                    make otherwise: the router's scores are float32 on both
                    sides, but bfloat16 rounding upstream of it moves the
                    six chosen at 4 % of positions in the first expert layer
                    and 10 % in the fourth, so a leaf alone reads up to 0.04
                    in a sound run; a flip mostly moves a token between
                    experts, which the sum hardly sees (at most 0.013), while
                    five chosen for six (``topk_altered``) reads 0.047 and
                    0.091 and a dropped scaling 0.59.
``grad_difference`` the same gradient, all leaves together: ||program's -
                    reference's|| over the root of the rows' squared gradient
                    norms over the rows, not over the norm of their mean. A
                    row's rounding does not know of the other rows: the
                    program's gradient lies 0.08 to 0.12 from the
                    reference's on every seed read, while the mean's norm
                    runs from 2.0 to 17.8 with the labels of the four rows
                    (two of each class cancel; four of one do not) and a
                    sound run read 0.005 to 0.037 over it. The rows' own
                    norms cancel nothing (PERF.md section 4).
``leaf_difference`` the same gradient, a leaf at a time: the worst leaf's
                    ||program's - reference's|| over the root of that leaf's
                    own rows' squared gradient norms. The leaves whose
                    gradient hangs on which experts were chosen are left out:
                    the routed experts' (``routed_gap`` takes them) and the
                    routers', whose gradient a flip of the chosen moves by
                    0.15 to 0.25 of its rows' in a sound run. It sees a fault
                    confined to a few leaves, which the median leaf and the
                    sum over all leaves do not: ``state_reset`` reads 0.35
                    and 0.42 where sound runs read at most 0.051, most often
                    at a Mamba-2 layer's ``dt_bias`` (PERF.md section 4).
``change_gap``      the parameters' change over the judged steps: the **median
                    leaf's** gap by the same measure; leaves whose gradient is
                    nought to rounding in the reference (under a thousandth of
                    the median leaf's, in every judged step) are left out, by
                    that rule. Not the worst leaf's: AdamW divides every
                    element's moment by the root of its second moment, so the
                    rows of an expert that few tokens chose move as far as
                    those of one that thousands chose; bfloat16 rounding
                    upstream sends about one position in a hundred to another
                    held expert, which is little to the gradient's norm and
                    much to such an expert's rows. A sound run whose last
                    expert layer gave two of its experts 20 and 45 of a
                    step's 11,000 words read 0.027 by the worst leaf, where
                    ``topk_altered`` read 0.052 and ``state_reset`` 0.029
                    (PERF.md section 4); the median leaf is moved by none of
                    that and by every fault of the optimizer, which is what
                    the number is for.
``step_count_gap``  the optimizer's own count at the window's end, and the
                    steps of the trainer's epoch records, against the steps
                    the hook saw: exact.

The model (the ``nemotron_h`` family as the configuration's file describes
it): ``h = Embed(ids)``; for each character of the pattern ``h = h +
Mixer(RMSNorm(h))``; a last RMSNorm; the mean over each row's non-PAD
positions; a linear head without bias; mean softmax cross-entropy; AdamW as ``optax.adamw``
defines it.

* ``M``: the state-space layer is written here **as the quadratic form, head
  by head** (the heads of a group, which share B and C, in one pass): ``y_t = sum_{s<=t} exp(cs_t - cs_s) (C_t . B_s) dt_s x_s +
  D x_t`` with ``cs`` the cumulative sum of ``dt * A`` over the whole row. No
  chunks, no carried state: not the program's algorithm.
* ``*``: softmax over all earlier keys, head by head (eight in a pass).
* ``E``: sigmoid scores over all the published experts, the ``top_k``
  largest, normalised and scaled; the experts as a plain loop over the held
  ids, every one over every token and weighted by the token's weight for it
  (nought where it was not chosen); what absent experts would add is left
  out, as the configuration's share says; the shared expert on every token.
  A PAD position is no token: it is not routed.

Every layer and every head is recomputed in the backward pass
(``jax.checkpoint``), which changes no value and lets a row of 4,096
positions fit; the rows of a step go through in blocks. The optimizer's
moments and the initial parameters live on the host.

flax is asked for one thing, as a library and by its public rules, because
the configuration states it in flax's terms: the initial parameters (a module
tree with the backbone's names and the stated initializers under
``PRNGKey(seed)``, the run's seed).

The control is this reference with the operands of every matrix product that
the configuration states in bfloat16 rounded to float8 (e4m3, scaled per
tensor), the step below; the planted faults likewise
(``benchmark/tools/readings.py``). A stand-in plays the program, so its
products run as the program's do on the device, at the default matrix
precision (on the chip one bfloat16 pass with a float32 sum; on the CPU
float32), the router's excepted: it then carries the program's rounding
beside its fault.
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

_VALUE_TYPES = {"float32": None, "bfloat16": (8, 7), "float8_e4m3fn": (4, 3)}
_FLOAT8_TOP = 224.0
LOWER = {"float32": "bfloat16", "bfloat16": "float8_e4m3fn"}
ADAM = {"b1": 0.9, "b2": 0.999, "eps": 1e-8, "weight_decay": 0.0}
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
# sizes, and what flax is asked for
# ---------------------------------------------------------------------------

class Sizes:
    def __init__(self, cfg: dict):
        g = lambda k: int(cfg[k])
        self.hidden, self.vocab = g("hidden_size"), g("vocab_size")
        self.pattern = str(cfg["hybrid_override_pattern"])
        assert len(self.pattern) == g("num_hidden_layers")
        self.heads, self.kv = g("num_attention_heads"), g("num_key_value_heads")
        self.head_dim = g("head_dim")
        self.m_heads, self.m_dim = g("mamba_num_heads"), g("mamba_head_dim")
        self.groups, self.state = g("n_groups"), g("ssm_state_size")
        self.conv, self.chunk = g("conv_kernel"), g("chunk_size")
        self.d_inner = self.m_heads * self.m_dim
        self.conv_dim = self.d_inner + 2 * self.groups * self.state
        self.router = g("router_experts")
        self.held = [int(e) for e in cfg["held_experts"]]
        assert len(self.held) == g("n_routed_experts")
        self.top_k = g("num_experts_per_tok")
        self.width = g("moe_intermediate_size")
        self.shared = g("moe_shared_expert_intermediate_size")
        self.scaling = float(cfg["routed_scaling_factor"])
        self.eps = float(cfg["norm_eps"])
        self.dt = (float(cfg["time_step_min"]), float(cfg["time_step_max"]),
                   float(cfg["time_step_floor"]))


def initial_parameters(cfg: dict, seed: int, classes: int) -> dict:
    """The stated initializers: matrices, router and head normal(0.02); norm
    weights and D one; the convolution uniform(+-conv_kernel^-1/2);
    ``A_log`` the logarithm of uniform(1, 16); ``dt_bias`` the inverse
    softplus of a step log-uniform between ``time_step_min`` and
    ``time_step_max``, at least ``time_step_floor``; the embedding flax's
    ``Embed`` default."""
    import flax.linen as nn

    z = Sizes(cfg)
    dense, ones = nn.initializers.normal(0.02), nn.initializers.ones
    lo, hi, floor = z.dt

    def uniform(bound):
        return lambda key, shape: jax.random.uniform(
            key, shape, jnp.float32, -bound, bound)

    def dt_bias(key, shape):
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32)
                     * (math.log(hi) - math.log(lo)) + math.log(lo))
        dt = jnp.maximum(dt, floor)
        return dt + jnp.log(-jnp.expm1(-dt))

    def a_log(key, shape):
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))

    shapes = {
        "M": {"in_proj": (dense, (z.hidden, z.d_inner + z.conv_dim + z.m_heads)),
              "conv_kernel": (uniform(z.conv ** -0.5), (z.conv, z.conv_dim)),
              "conv_bias": (uniform(z.conv ** -0.5), (z.conv_dim,)),
              "dt_bias": (dt_bias, (z.m_heads,)),
              "A_log": (a_log, (z.m_heads,)),
              "D": (ones, (z.m_heads,)),
              "gate_norm": (ones, (z.d_inner,)),
              "out_proj": (dense, (z.d_inner, z.hidden))},
        "*": {"q": (dense, (z.hidden, z.heads * z.head_dim)),
              "k": (dense, (z.hidden, z.kv * z.head_dim)),
              "v": (dense, (z.hidden, z.kv * z.head_dim)),
              "o": (dense, (z.heads * z.head_dim, z.hidden))},
        "E": {"router": (dense, (z.hidden, z.router)),
              "experts_up": (dense, (len(z.held), z.hidden, z.width)),
              "experts_down": (dense, (len(z.held), z.width, z.hidden)),
              "shared_up": (dense, (z.hidden, z.shared)),
              "shared_down": (dense, (z.shared, z.hidden))},
    }

    def layer_of(kind):
        # the kind by closure: a module with a field would have to be found
        # in sys.modules, where the harness does not put this file
        class Layer(nn.Module):
            @nn.compact
            def __call__(self):
                self.param("norm", ones, (z.hidden,))
                for name, (init, shape) in shapes[kind].items():
                    self.param(name, init, shape)

        return Layer

    class Tree(nn.Module):
        @nn.compact
        def __call__(self, ids):
            x = nn.Embed(z.vocab, z.hidden, name="tok_embed")(ids)
            for i, kind in enumerate(z.pattern):
                layer_of(kind)(name=f"layer_{i}")()
            self.param("final_norm", ones, (z.hidden,))
            return nn.Dense(classes, use_bias=False, kernel_init=dense,
                            name="head")(x[:, 0])

    variables = Tree().init(jax.random.PRNGKey(int(seed)),
                            jnp.zeros((1, 2), jnp.int32))
    return jax.tree.map(jnp.asarray, dict(variables["params"]))


# ---------------------------------------------------------------------------
# the model and its loss, written out
# ---------------------------------------------------------------------------

def _rounder(value_type: str):
    """Rounds an operand of a matrix product to ``value_type``; seen by the
    forward pass and, through the operands it saves, by the backward pass's
    products; the cotangents pass unrounded."""
    bits = _VALUE_TYPES[value_type]
    if bits is None:
        return lambda x: x

    def rounded(x):
        if value_type == "float8_e4m3fn":
            scale = _FLOAT8_TOP / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
            return lax.reduce_precision(x * scale, *bits) / scale
        return lax.reduce_precision(x, *bits)

    return lambda x: x + lax.stop_gradient(rounded(x) - x)


def _rms_norm(x, weight, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * weight


def _silu(x):
    return x * jax.nn.sigmoid(x)


def _state_space(p, x, z: Sizes, r, how):
    """x (rows, S, hidden) -> (rows, S, hidden): the quadratic form, head
    by head."""
    rows, length, _ = x.shape
    per = z.m_heads // z.groups
    zxd = r(x) @ r(p["in_proj"])
    gate, xbc, dt = jnp.split(zxd, [z.d_inner, z.d_inner + z.conv_dim], -1)
    padded = jnp.pad(xbc, ((0, 0), (z.conv - 1, 0), (0, 0)))
    xbc = _silu(sum(padded[:, j:j + length] * p["conv_kernel"][j]
                    for j in range(z.conv)) + p["conv_bias"])
    xs, b, c = jnp.split(xbc, [z.d_inner, z.d_inner + z.groups * z.state], -1)
    xs = xs.reshape(rows, length, z.m_heads, z.m_dim)
    b = b.reshape(rows, length, z.groups, z.state)
    c = c.reshape(rows, length, z.groups, z.state)
    dt = jax.nn.softplus(dt + p["dt_bias"])               # (rows, S, H)
    cs = jnp.cumsum(dt * -jnp.exp(p["A_log"]), axis=1)
    t = jnp.arange(length)
    # the fault "state_reset": what a chunk hands to the next is dropped
    seen = (t[:, None] >= t[None, :]) & (
        (t[:, None] // z.chunk == t[None, :] // z.chunk)
        | ~how["state_reset"])

    @jax.checkpoint
    def group(g):
        """The heads that share group ``g``'s B and C, one after another's
        formula, together: (per, rows, S, P)."""
        cb = jnp.einsum("rtn,rsn->rts", r(jnp.take(c, g, axis=2)),
                        r(jnp.take(b, g, axis=2)))

        def head(h):
            x_h = jnp.take(xs, h, axis=2)                  # (rows, S, P)
            cs_h, dt_h = jnp.take(cs, h, axis=2), jnp.take(dt, h, axis=2)
            decay = jnp.exp(jnp.where(
                seen, cs_h[:, :, None] - cs_h[:, None, :], -jnp.inf))
            m = cb * decay * dt_h[:, None, :]
            return (jnp.einsum("rts,rsp->rtp", r(m), r(x_h))
                    + jnp.take(p["D"], h) * x_h)

        return jax.vmap(head)(g * per + jnp.arange(per))

    y = lax.map(group, jnp.arange(z.groups))           # (G, per, rows, S, P)
    y = y.reshape((z.m_heads,) + y.shape[2:])
    y = y.transpose(1, 2, 0, 3).reshape(rows, length, z.d_inner) * _silu(gate)
    y = y.reshape(rows, length, z.groups, z.d_inner // z.groups)
    y = y * lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + z.eps)
    y = y.reshape(rows, length, z.d_inner) * p["gate_norm"]
    return r(y) @ r(p["out_proj"])


def _attention(p, x, z: Sizes, r):
    rows, length, _ = x.shape
    per = z.heads // z.kv
    q = (r(x) @ r(p["q"])).reshape(rows, length, z.heads, z.head_dim)
    k = (r(x) @ r(p["k"])).reshape(rows, length, z.kv, z.head_dim)
    v = (r(x) @ r(p["v"])).reshape(rows, length, z.kv, z.head_dim)
    t = jnp.arange(length)
    seen = t[:, None] >= t[None, :]

    def head(h):
        g = h // per
        s = jnp.einsum("rtd,rsd->rts", r(jnp.take(q, h, axis=2)),
                       r(jnp.take(k, g, axis=2))) / math.sqrt(z.head_dim)
        w = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.einsum("rts,rsd->rtd", r(w), r(jnp.take(v, g, axis=2)))

    together = math.gcd(z.heads, 8)        # heads a pass, so that it fits
    out = lax.map(jax.checkpoint(jax.vmap(head)),
                  jnp.arange(z.heads).reshape(-1, together))
    out = out.reshape((z.heads,) + out.shape[2:])          # (heads, rows, S, D)
    out = out.transpose(1, 2, 0, 3).reshape(rows, length, z.heads * z.head_dim)
    return r(out) @ r(p["o"])


def _experts(p, x, z: Sizes, r, how, real=None):
    """``real`` (x's leading shape): the positions that hold a token."""
    def expert(up, down):
        return r(jnp.square(jax.nn.relu(r(x) @ r(up)))) @ r(down)

    # float32 at ``highest`` whatever the other products are, never rounded
    scores = jax.nn.sigmoid(jnp.matmul(x, p["router"],
                                       precision=lax.Precision.HIGHEST))
    top, ids = lax.top_k(scores, z.top_k)
    # the fault "topk_altered": the last of the chosen is let go
    top = jnp.where(how["topk_altered"] & (jnp.arange(z.top_k) == z.top_k - 1),
                    0.0, top)
    weights = top / (top.sum(-1, keepdims=True) + 1e-20)
    weights = weights * jnp.where(how["scaling_dropped"], 1.0, z.scaling)
    if real is not None:
        weights = jnp.where(real[..., None], weights, 0.0)
    routed = 0.0
    for j, e in enumerate(z.held):
        mine = jnp.where(ids == e, weights, 0.0).sum(-1, keepdims=True)
        routed = routed + mine * expert(p["experts_up"][j],
                                        p["experts_down"][j])
    return (expert(p["shared_up"], p["shared_down"])
            + jnp.where(how["experts_unrouted"], 0.0, routed))


# the planted faults that are switches of the one compiled program (all
# False: the sound model, to the last bit)
FAULTS = ("topk_altered", "scaling_dropped", "state_reset",
          "experts_unrouted", "mask_dropped")


def switches(*on) -> dict:
    return {name: jnp.asarray(name in on) for name in FAULTS}


def forward(params, ids, cfg: dict, value_type="float32", how=None):
    """Logits (rows, classes). ``how``: ``switches(...)``."""
    how = how if how is not None else switches()
    z = Sizes(cfg)
    r = _rounder(value_type)
    h = params["tok_embed"]["embedding"][ids]
    # the fault "mask_dropped": PAD is taken for a word
    real = (ids != PAD_ID) | how["mask_dropped"]
    for i, kind in enumerate(z.pattern):
        def layer(p, h, kind=kind):
            y = _rms_norm(h, p["norm"], z.eps)
            if kind == "M":
                return h + _state_space(p, y, z, r, how)
            if kind == "*":
                return h + _attention(p, y, z, r)
            return h + _experts(p, y, z, r, how, real)

        h = jax.checkpoint(layer)(params[f"layer_{i}"], h)
    h = _rms_norm(h, params["final_norm"], z.eps)
    pooled = (jnp.where(real[..., None], h, 0.0).sum(1)
              / jnp.maximum(real.sum(-1, keepdims=True), 1))
    return pooled @ params["head"]["kernel"]


def loss_sum(params, ids, y, cfg, value_type, how):
    """Sum over the rows of the softmax cross-entropy."""
    logp = jax.nn.log_softmax(forward(params, ids, cfg, value_type, how), -1)
    return -jnp.take_along_axis(logp, y[:, None], axis=-1).sum()


def flatten(tree) -> dict:
    return {"/".join(str(getattr(k, "key", k)) for k in path): leaf
            for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]}


# ---------------------------------------------------------------------------
# the judged steps
# ---------------------------------------------------------------------------

@jax.jit
def _adam_leaf(p, m, n, g, lr, c1, c2, keep_m):
    b1, b2, eps = ADAM["b1"], ADAM["b2"], ADAM["eps"]
    m = jnp.where(keep_m, m, b1 * m + (1 - b1) * g)
    n = b2 * n + (1 - b2) * g * g
    p = p - lr * ((m / c1) / (jnp.sqrt(n / c2) + eps)
                  + ADAM["weight_decay"] * p)
    return p, m, n


class Reference:
    def __init__(self, config: dict, texts, labels, seed: int, batch: int,
                 steps: int):
        self.cfg, self.seed, self.batch, self.steps = (config, int(seed),
                                                       int(batch), int(steps))
        self.lr = float(config["estimator"]["learningRate"])
        self.stated = config["estimator"]["precision"]
        self.block = int(config.get("reference_rows_per_block", 1))
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
        self._initial = None     # flat, on the host

    def _block_grad(self, value_type):
        """One compiled program a precision; the faults are its switches."""
        if value_type not in self._grad:
            cfg = self.cfg
            self._grad[value_type] = jax.jit(jax.value_and_grad(
                lambda p, ids, y, how: loss_sum(p, ids, y, cfg, value_type,
                                                how)))
        return self._grad[value_type]

    def loss_and_grad(self, params, step, value_type, how, rows):
        """The step's loss and gradient, and, leaf by leaf, the root of the
        blocks' squared gradient norms over the rows: what of the rows'
        gradients does not cancel between them."""
        fn = self._block_grad(value_type)
        total, grads, apart = 0.0, None, None
        for lo in range(0, rows, self.block):
            hi = min(lo + self.block, rows)
            part, g = fn(params, jnp.asarray(self.ids[step][lo:hi]),
                         jnp.asarray(self.y[step][lo:hi]), how)
            total = total + part
            sq = {k: jnp.sum(jnp.square(v)) for k, v in flatten(g).items()}
            apart = sq if apart is None else {k: apart[k] + sq[k] for k in sq}
            grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
        return (total / rows, jax.tree.map(lambda g: g / rows, grads),
                {k: math.sqrt(float(v)) / rows for k, v in apart.items()})

    def follow(self, how: dict = None) -> dict:
        """The judged steps from the seed: {"losses", "first_gradient",
        "change" (the parameters after the last step less the initial ones),
        "grad_norms" (of every step, by leaf), "rows_norms" (of the first
        step, by leaf: ``loss_and_grad``'s third)}, the trees flat and on the
        host;
        ``how`` plants the control or a fault."""
        # the reference itself at ``highest``; a stand-in as the program
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
                    continue         # the step hands its state on as it was
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

    def sound(self) -> dict:
        if self._sound is None:
            self._sound = self.follow()
        return self._sound

    def judged_of(self, followed: dict) -> dict:
        """What the hook would have kept of ``followed`` steps."""
        return {"losses": followed["losses"],
                "mu": {k: v * (1.0 - ADAM["b1"])
                       for k, v in followed["first_gradient"].items()},
                "change": followed["change"]}

    def compare(self, judged: dict, candidates: bool = False) -> dict:
        """The numbers of ``judged`` ({"losses", "mu", and "params" or
        "change"}, the trees flat dicts of host arrays) against the
        reference's own steps."""
        ref = self.sound()
        losses = [abs(a - b) / abs(b)
                  for a, b in zip(judged["losses"], ref["losses"])]
        if len(judged["losses"]) != self.steps:
            losses.append(1.0)
        g_ref, c_ref = ref["first_gradient"], ref["change"]
        scale = 1.0 / (1.0 - ADAM["b1"])
        median_g = [float(np.median(list(n.values())))
                    for n in ref["grad_norms"]]
        moved = [k for k in g_ref if any(
            n[k] >= ZERO_GRADIENT * m
            for n, m in zip(ref["grad_norms"], median_g))]

        def change_of(k):
            if "change" in judged:
                return judged["change"][k]
            return judged["params"][k] - self._initial[k]

        grad = _leaf_norms(lambda k: judged["mu"][k] * scale, g_ref,
                           list(g_ref))
        change = _leaf_norms(change_of, c_ref, moved)
        print(f"reference: losses {ref['losses']}; judged {judged['losses']}; "
              f"{len(g_ref) - len(moved)} leaf(s) of {len(g_ref)} left out of "
              f"the change: {sorted(set(g_ref) - set(moved))[:4]}",
              file=sys.stderr)
        return judge(self.cfg, losses, grad, change, ref["rows_norms"],
                     candidates)


def judge(cfg: dict, loss_gaps, grad: dict, change: dict, rows_norms: dict,
          candidates: bool = False) -> dict:
    """The compared numbers from what was read: the judged steps' loss gaps,
    {leaf: [program's norm, reference's norm, norm of the difference]} of
    the first gradient (``grad``) and of the change (``change``, the leaves
    that move), and {leaf: the root of the rows' squared norms of its first
    gradient} (``rows_norms``, the reference's). Data in, numbers out: a
    recorded reading is judged again by the tests as a run judges it."""
    routed = routed_leaves(cfg)
    grad_gaps, change_gaps = _gaps(grad), _gaps(change)
    rows_norm = _root_sum_square(rows_norms.values())
    chosen = set(routed) | {k for k in grad if k.endswith("/router")}
    own = {k: d / rows_norms[k] for k, (_, _, d) in grad.items()
           if k not in chosen}
    own_at = max(own, key=own.get)
    out = {"first_loss_gap": float(loss_gaps[0]),
           "grad_gap": float(np.median(list(grad_gaps.values()))),
           "routed_gap": _gap_of_sums(grad, routed),
           "grad_difference": _root_sum_square(
               d for _, _, d in grad.values()) / rows_norm,
           "leaf_difference": own[own_at],
           "change_gap": float(np.median(list(change_gaps.values())))}
    worst = max(grad_gaps, key=grad_gaps.get)
    print(f"judged: worst gradient leaf {worst} {grad_gaps[worst]:.4g}; "
          f"routed leaves { {k: round(grad_gaps[k], 4) for k in routed} }",
          file=sys.stderr)
    if candidates:
        change_at = max(change_gaps, key=change_gaps.get)
        out.update(
            loss_gaps=[float(g) for g in loss_gaps],
            loss_gap_worst_step=float(max(loss_gaps)),
            grad_gap_worst_leaf=grad_gaps[worst],
            gradient_norm=_root_sum_square(w for _, w, _ in grad.values()),
            rows_norm=rows_norm,
            change_gap_worst_leaf=change_gaps[change_at],
            leaf_norms={"grad": grad, "change": change},
            rows_norms=rows_norms,
            where={"loss_gap_worst_step": f"step {int(np.argmax(loss_gaps))}",
                   "grad_gap": f"median of {len(grad)} leaves",
                   "routed_gap": f"{len(routed)} routed leaves",
                   "change_gap": f"median of {len(change)} leaves",
                   "leaf_difference": own_at,
                   "grad_gap_worst_leaf": worst,
                   "change_gap_worst_leaf": change_at})
    return out


def routed_leaves(cfg: dict) -> list:
    """The routed experts' leaves of every expert layer: what ``routed_gap``
    takes together."""
    layers = [i for i, kind in enumerate(cfg["hybrid_override_pattern"])
              if kind == "E"]
    return [f"layer_{i}/{name}" for i in layers
            for name in ("experts_up", "experts_down")]


def _unflatten(flat: dict) -> dict:
    out = {}
    for k, v in flat.items():
        node = out
        *parents, leaf = k.split("/")
        for part in parents:
            node = node.setdefault(part, {})
        node[leaf] = v
    return out


def _norm(x) -> float:
    return math.sqrt(float(np.sum(np.square(np.ravel(x), dtype=np.float64))))


def _root_sum_square(values) -> float:
    return math.sqrt(sum(v * v for v in values))


def _leaf_norms(got_of, want: dict, leaves) -> dict:
    """A leaf at a time (a tree is 2.5 GB): {leaf: [||got||, ||want||,
    ||got - want||]}."""
    out = {}
    for k in leaves:
        got = got_of(k)
        out[k] = [_norm(got), _norm(want[k]), _norm(got - want[k])]
    return out


def _gaps(norms: dict) -> dict:
    """{leaf: | ||got|| - ||want|| | / max(||want||, the median leaf's)}."""
    median = float(np.median([w for _, w, _ in norms.values()]))
    return {k: abs(g - w) / max(w, median) for k, (g, w, _) in norms.items()}


def _gap_of_sums(norms: dict, leaves) -> float:
    """The gap of the norms of ``leaves`` taken together: | ||got|| -
    ||want|| | / ||want|| over the leaves as one vector."""
    got = _root_sum_square(norms[k][0] for k in leaves)
    want = _root_sum_square(norms[k][1] for k in leaves)
    return abs(got - want) / want


def stand_in_plans(config: dict) -> dict:
    """What is put in the program's place to show that ``correct`` fails: the
    control (the stated precision's next step down) and the planted faults,
    each as ``Reference.follow``'s ``how``."""
    return {
        "control": {"value_type": LOWER[config["estimator"]["precision"]]},
        # the nearest to the limits first: ``stand_ins`` prints as it goes
        **{name: {"switch": (name,)} for name in FAULTS},
        "half_batch": {"rows": "half"},
        "moment_stale": {"moment": "stale"},
        "state_unchanged": {"state_kept": "unchanged"},
    }


_KEPT = []     # [inputs, Reference]: the last run's, so that its own steps
               # (two minutes on the chip) are followed once for ``check``
               # and for ``stand_ins`` after it


def _reference_of(config: dict, inputs: dict) -> "Reference":
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
                           step_count_gap=_count_gap(inputs["judged"]))}
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
    return dict(ref.compare(judged), step_count_gap=_count_gap(judged))


def _count_gap(judged: dict) -> float:
    seen = int(judged["hook_steps"])
    return float(abs(int(judged["opt_count"]) - seen)
                 + abs(int(judged["program_steps"]) - seen))
