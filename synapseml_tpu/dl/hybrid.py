"""Hybrid text backbone: one pre-norm residual stack whose mixers come from a
layer-type list.

``h = Embed(ids)``; for every character of ``HybridArch.pattern``:
``h = h + Mixer(RMSNorm(h))``; a last RMSNorm, the mean over each row's
non-PAD positions and a linear head. Three mixers (the ``nemotron_h`` family's):

``M``  Mamba-2: ``in_proj`` to gate ``z``, ``xBC`` and ``dt``; a causal
       depth-wise convolution and SiLU over ``xBC``; per head the recurrence
       ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t``, ``y_t = S_t C_t +
       D x_t`` computed by chunks (``ssd_chunked``); a gated group RMSNorm;
       ``out_proj``.
``*``  causal grouped-query attention, no positional embedding, by blocks of
       queries so that no S x S tensor is kept (``causal_attention``).
``E``  sparse experts: a sigmoid router over all the published experts picks
       ``top_k`` a token; the layer is told which experts it holds
       (``HybridArch.held``), computes the pairs whose expert it holds by
       grouped products (``held_experts_part``) and adds the shared expert.
       What absent experts would have added is left out: on one chip there is
       no exchange. No pair is dropped at any load. A PAD position is no
       token: it is not routed (its routed part is nought; nothing reads it
       after the last word of a right-padded row).

Every block is recomputed in the backward pass (``nn.remat``). Parameters are
float32; products run in ``dtype``; the router, the decay's cumulative sums,
the scan's state, the softmax and the norms' statistics are float32.

The stack counts what it computes into the ``counters`` collection (tokens,
PAD tokens, pairs the held experts computed, tokens of every held expert);
``FlaxTrainer`` carries the collection through the steps and reads it once an
epoch.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from . import text as _text    # PAD_ID: the tokenizer's, read when traced

ATTENTION_BLOCK = 512


@dataclasses.dataclass(frozen=True)
class HybridArch:
    pattern: str
    hidden: int
    vocab: int
    heads: int
    kv_heads: int
    head_dim: int
    mamba_heads: int
    mamba_head_dim: int
    groups: int
    state: int
    conv: int
    chunk: int
    experts: int            # the router's width: every published expert
    top_k: int
    expert_width: int
    shared_width: int
    scaling: float
    held: Tuple[int, ...]   # ids of the experts this chip holds
    eps: float
    dt_min: float = 0.001
    dt_max: float = 0.1
    dt_floor: float = 1e-4

    @classmethod
    def from_source(cls, src: dict, *, hidden: int, layers: int, heads: int,
                    vocab: int) -> "HybridArch":
        """From the source's own ``config.json`` keys; ``held_experts`` (ids)
        is this framework's, default every expert."""
        pattern = str(src["hybrid_override_pattern"])
        if len(pattern) != layers or set(pattern) - set("ME*"):
            raise ValueError(
                f"hybrid_override_pattern {pattern!r} must have numLayers="
                f"{layers} characters of 'M', 'E', '*'")
        experts = int(src.get("n_routed_experts", 0))
        held = tuple(int(e) for e in src.get("held_experts", range(experts)))
        if any(not 0 <= e < experts for e in held) or len(set(held)) != len(held):
            raise ValueError(f"held_experts {held} are not distinct ids "
                             f"below n_routed_experts={experts}")
        if int(src.get("n_group", 1)) != 1 or int(src.get("topk_group", 1)) != 1:
            raise NotImplementedError("grouped routing (n_group > 1)")
        return cls(
            pattern=pattern, hidden=hidden, vocab=vocab, heads=heads,
            kv_heads=int(src.get("num_key_value_heads", heads)),
            head_dim=int(src.get("head_dim", hidden // heads)),
            mamba_heads=int(src.get("mamba_num_heads", 0)),
            mamba_head_dim=int(src.get("mamba_head_dim", 0)),
            groups=int(src.get("n_groups", 1)),
            state=int(src.get("ssm_state_size", 0)),
            conv=int(src.get("conv_kernel", 4)),
            chunk=int(src.get("chunk_size", 128)),
            experts=experts, top_k=int(src.get("num_experts_per_tok", 0)),
            expert_width=int(src.get("moe_intermediate_size", 0)),
            shared_width=int(src.get("moe_shared_expert_intermediate_size", 0)),
            scaling=float(src.get("routed_scaling_factor", 1.0)),
            held=held, eps=float(src.get("norm_eps", 1e-5)),
            dt_min=float(src.get("time_step_min", 0.001)),
            dt_max=float(src.get("time_step_max", 0.1)),
            dt_floor=float(src.get("time_step_floor", 1e-4)))

    @property
    def d_inner(self) -> int:
        return self.mamba_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.groups * self.state


def rms_norm(x, weight, eps: float):
    x32 = x.astype(jnp.float32)
    y = x32 * lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (y * weight).astype(x.dtype)


# ---------------------------------------------------------------------------
# M: the state-space mixer
# ---------------------------------------------------------------------------

def ssd_chunked(x, dt, a, b, c, chunk: int):
    """``y_t = C_t . S_t`` with ``S_t = exp(dt_t a) S_{t-1} + dt_t x_t (x)
    B_t``, in the chunked matrix form: products inside chunks of ``chunk``
    positions, a short scan over the chunks' states.

    x (B, S, H, P); dt (B, S, H) float32, positive; a (H,) float32, negative;
    b, c (B, S, G, N), every group serving H / G heads. Returns (B, S, H, P)
    float32. Any S: the tail is padded with dt = 0, which leaves the state
    as it is."""
    bsz, length, heads, p = x.shape
    groups, n = b.shape[2], b.shape[3]
    per = heads // groups
    pad = (-length) % chunk
    if pad:
        x, dt, b, c = (jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
                       for t in (x, dt, b, c))
    nc = (length + pad) // chunk
    f32 = jnp.float32
    x = x.reshape(bsz, nc, chunk, groups, per, p)
    b = b.reshape(bsz, nc, chunk, groups, n)
    c = c.reshape(bsz, nc, chunk, groups, n)
    # positions last: the (chunk, chunk) tensors below then tile as they lie
    dt = dt.reshape(bsz, nc, chunk, groups, per).transpose(0, 1, 3, 4, 2)
    cs = jnp.cumsum(dt * a.reshape(groups, per, 1), axis=-1)  # (B,C,G,R,L)
    # inside a chunk: y_l += sum_{s<=l} exp(cs_l - cs_s) (C_l . B_s) dt_s x_s
    cb = jnp.einsum("bclgn,bcsgn->bcgls", c, b, preferred_element_type=f32)
    gap = cs[..., :, None] - cs[..., None, :]                 # (B,C,G,R,L,S)
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))
    decay = jnp.exp(jnp.where(causal, gap, -jnp.inf)) * dt[..., None, :]
    scores = (cb[:, :, :, None] * decay).astype(x.dtype)
    y = jnp.einsum("bcgrls,bcsgrp->bclgrp", scores, x,
                   preferred_element_type=f32)
    # every chunk's own contribution to the state at its end
    by_position = lambda t: t.transpose(0, 1, 4, 2, 3)[..., None]
    to_end = (jnp.exp(cs[..., -1:] - cs) * dt).astype(x.dtype)
    states = jnp.einsum("bclgn,bclgrp->bcgrpn", b, x * by_position(to_end),
                        preferred_element_type=f32)
    # across chunks: the state that enters every chunk, float32
    through = jnp.exp(cs[..., -1])                            # (B,C,G,R)

    def step(s, inp):
        st, th = inp
        return th[..., None, None] * s + st, s

    _, entering = lax.scan(
        step, jnp.zeros(states.shape[:1] + states.shape[2:], f32),
        (states.swapaxes(0, 1), through.swapaxes(0, 1)))
    entering = entering.swapaxes(0, 1).astype(x.dtype)        # (B,C,G,R,P,N)
    y_in = jnp.einsum("bclgn,bcgrpn->bclgrp", c, entering,
                      preferred_element_type=f32)
    y = y + y_in * by_position(jnp.exp(cs))
    return y.reshape(bsz, nc * chunk, heads, p)[:, :length]


def _causal_depthwise_conv(x, kernel, bias):
    """x (B, S, C); kernel (K, C): y_t = sum_j kernel[j] x_{t-K+1+j} + bias."""
    k = kernel.shape[0]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    length = x.shape[1]
    return sum(xp[:, j:j + length] * kernel[j] for j in range(k)) + bias


def mamba2_mixer(p: dict, x, arch: HybridArch):
    dtype = x.dtype
    f32 = jnp.float32
    bsz, length, _ = x.shape
    heads, hd, g, n = arch.mamba_heads, arch.mamba_head_dim, arch.groups, arch.state
    zxd = x @ p["in_proj"].astype(dtype)
    z, xbc, dt = jnp.split(zxd, [arch.d_inner, arch.d_inner + arch.conv_dim], -1)
    xbc = jax.nn.silu(_causal_depthwise_conv(
        xbc, p["conv_kernel"].astype(dtype), p["conv_bias"].astype(dtype)))
    xs, b, c = jnp.split(xbc, [arch.d_inner, arch.d_inner + g * n], -1)
    dt = jax.nn.softplus(dt.astype(f32) + p["dt_bias"])
    xs = xs.reshape(bsz, length, heads, hd)
    y = ssd_chunked(xs, dt, -jnp.exp(p["A_log"]),
                    b.reshape(bsz, length, g, n), c.reshape(bsz, length, g, n),
                    arch.chunk)
    y = y + p["D"][:, None] * xs.astype(f32)
    y = y.reshape(bsz, length, arch.d_inner) * jax.nn.silu(z.astype(f32))
    # gated RMSNorm over groups of d_inner / n_groups channels
    yg = y.reshape(bsz, length, g, arch.d_inner // g)
    yg = yg * lax.rsqrt(jnp.mean(yg * yg, -1, keepdims=True) + arch.eps)
    y = (yg.reshape(bsz, length, arch.d_inner) * p["gate_norm"]).astype(dtype)
    return y @ p["out_proj"].astype(dtype)


# ---------------------------------------------------------------------------
# *: causal grouped-query attention
# ---------------------------------------------------------------------------

def _attention_block(q, k, v, first: int):
    """Queries ``first ..`` against the keys up to their own positions."""
    f32 = jnp.float32
    s = jnp.einsum("bqgrd,bkgd->bgrqk", q, k, preferred_element_type=f32)
    s = s * (q.shape[-1] ** -0.5)
    seen = (jnp.arange(k.shape[1])[None, :]
            <= first + jnp.arange(q.shape[1])[:, None])
    w = jax.nn.softmax(jnp.where(seen, s, jnp.finfo(f32).min), axis=-1)
    return jnp.einsum("bgrqk,bkgd->bqgrd", w.astype(q.dtype), v,
                      preferred_element_type=f32).astype(q.dtype)


def causal_attention(q, k, v, block: int = ATTENTION_BLOCK):
    """q (B, S, KV, R, D): R query heads on each of KV key-value heads;
    k, v (B, S, KV, D). A block of queries sees the keys up to its last
    position, and its scores are recomputed in the backward pass, so the
    largest tensor alive is one block's (B, KV, R, block, S)."""
    length = q.shape[1]
    blk = jax.checkpoint(_attention_block, static_argnums=(3,))
    return jnp.concatenate(
        [blk(q[:, lo:lo + block], k[:, :lo + block], v[:, :lo + block], lo)
         for lo in range(0, length, block)], axis=1)


def attention_mixer(p: dict, x, arch: HybridArch):
    dtype = x.dtype
    bsz, length, _ = x.shape
    kv, hd = arch.kv_heads, arch.head_dim
    q = (x @ p["q"].astype(dtype)).reshape(bsz, length, kv, arch.heads // kv, hd)
    k = (x @ p["k"].astype(dtype)).reshape(bsz, length, kv, hd)
    v = (x @ p["v"].astype(dtype)).reshape(bsz, length, kv, hd)
    out = causal_attention(q, k, v, min(ATTENTION_BLOCK, length))
    return out.reshape(bsz, length, arch.heads * hd) @ p["o"].astype(dtype)


# ---------------------------------------------------------------------------
# E: experts
# ---------------------------------------------------------------------------

def route(x, router, arch: HybridArch):
    """(ids (T, top_k), weights (T, top_k) float32): sigmoid scores over every
    published expert in float32, the ``top_k`` largest, their scores divided
    by their sum and scaled. The family's correction bias is a buffer that a
    fine-tune leaves at zero; it is left out."""
    with jax.named_scope("router"):
        s = jax.nn.sigmoid(jnp.dot(x.astype(jnp.float32), router,
                                   precision=lax.Precision.HIGHEST))
        top, ids = lax.top_k(s, arch.top_k)
        return ids, top / (top.sum(-1, keepdims=True) + 1e-20) * arch.scaling


def _expert(x, up, down):
    h = jnp.square(jax.nn.relu(x @ up))
    return h.astype(x.dtype) @ down


def _segment(xs, up, down, wt, sizes, valid):
    """One segment of sorted pairs: rows of one expert lie together, ``sizes``
    of them for each held expert; ``valid`` marks the rows that are pairs."""
    f32 = jnp.float32
    xs = jnp.where(valid[:, None], xs, 0)
    h = lax.ragged_dot(xs, up, sizes, preferred_element_type=f32)
    h = jnp.square(jax.nn.relu(h)).astype(xs.dtype)
    ys = lax.ragged_dot(h, down, sizes, preferred_element_type=f32)
    return jnp.where(valid[:, None], ys * wt[:, None], 0.0)


def segment_rows(pairs: int, held: int, experts: int) -> int:
    """Rows of sorted pairs that one grouped product takes: the held experts'
    even load, ``pairs * held / experts`` rounded up to eight rows. A segment
    costs the same however full it is, so a step costs the same at every load
    up to the even one; the load beyond it runs further segments."""
    even = -(-pairs * held // experts)
    return min(pairs, -(-even // 8) * 8)


def _segments(starts, counts, total, rows: int, index):
    """What segment ``index`` of ``rows`` sorted rows holds of every group."""
    lo = index * rows
    sizes = (jnp.clip(starts + counts - lo, 0, rows)
             - jnp.clip(starts - lo, 0, rows)).astype(jnp.int32)
    valid = lo + jnp.arange(rows) < total
    # the rows past the load go with the last group: zeros in, zeros out
    sizes = sizes.at[-1].add(rows - sizes.sum())
    return lo, sizes, valid, lo < total


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _grouped(rows, x, up, down, wt, tok, starts, counts):
    return _grouped_fwd(rows, x, up, down, wt, tok, starts, counts)[0]


def _grouped_fwd(rows, x, up, down, wt, tok, starts, counts):
    total = counts.sum()

    def body(acc, i):
        lo, sizes, valid, runs = _segments(starts, counts, total, rows, i)

        def run(acc):
            t = lax.dynamic_slice(tok, (lo,), (rows,))
            w = lax.dynamic_slice(wt, (lo,), (rows,))
            return acc.at[t].add(_segment(x[t], up, down, w, sizes, valid))

        return lax.cond(runs, run, lambda a: a, acc), None

    acc, _ = lax.scan(body, jnp.zeros(x.shape, jnp.float32),
                      jnp.arange(tok.shape[0] // rows))
    return acc, (x, up, down, wt, tok, starts, counts)


def _grouped_bwd(rows, res, g):
    x, up, down, wt, tok, starts, counts = res
    total = counts.sum()
    f32 = jnp.float32

    def body(carry, i):
        lo, sizes, valid, runs = _segments(starts, counts, total, rows, i)

        def run(carry):
            dx, dup, ddown, dwt = carry
            t = lax.dynamic_slice(tok, (lo,), (rows,))
            w = lax.dynamic_slice(wt, (lo,), (rows,))
            _, vjp = jax.vjp(
                lambda xs, up, down, w: _segment(xs, up, down, w, sizes, valid),
                x[t], up, down, w)
            dxs, du, dd, dw = vjp(g[t])
            return (dx.at[t].add(dxs.astype(f32)), dup + du.astype(f32),
                    ddown + dd.astype(f32),
                    lax.dynamic_update_slice(dwt, dw, (lo,)))

        return lax.cond(runs, run, lambda c: c, carry), None

    zeros = (jnp.zeros(x.shape, f32), jnp.zeros(up.shape, f32),
             jnp.zeros(down.shape, f32), jnp.zeros(wt.shape, f32))
    (dx, dup, ddown, dwt), _ = lax.scan(body, zeros,
                                        jnp.arange(tok.shape[0] // rows))
    none = lambda a: np.zeros(a.shape, jax.dtypes.float0)
    return (dx.astype(x.dtype), dup.astype(up.dtype), ddown.astype(down.dtype),
            dwt, none(tok), none(starts), none(counts))


_grouped.defvjp(_grouped_fwd, _grouped_bwd)


def held_experts_part(x, ids, weights, up, down, held, experts: int):
    """sum over the chosen pairs whose expert is held of ``weight *
    expert(x)``: (T, hidden) float32, and the tokens each held expert saw
    (len(held),) int32.

    The pairs are sorted by expert, so that every held expert's rows lie
    together, and taken a segment (``segment_rows``: the even load of
    ``len(held)`` of ``experts`` experts) at a time through grouped products
    (``lax.ragged_dot``). Every segment that holds a pair runs, so no pair
    is ever dropped; the segments past the last pair are skipped. x (T,
    hidden); ids, weights (T, top_k), an id of ``experts`` or more is no
    pair; up (len(held), hidden, width); down (len(held), width, hidden)."""
    tokens, top_k = ids.shape
    n_held = len(held)
    slot = np.full(int(max(held)) + 2, n_held, np.int32)
    slot[list(held)] = np.arange(n_held)
    local = jnp.asarray(slot)[jnp.minimum(ids, len(slot) - 1)].reshape(-1)
    order = jnp.argsort(local, stable=True)            # absent experts last
    counts = jnp.zeros(n_held + 1, jnp.int32).at[local].add(1)[:n_held]
    starts = jnp.cumsum(counts) - counts
    rows = segment_rows(tokens * top_k, n_held, experts)
    pad = (-tokens * top_k) % rows
    tok = jnp.pad((order // top_k).astype(jnp.int32), (0, pad))
    wt = jnp.pad(weights.reshape(-1)[order], (0, pad))
    return _grouped(rows, x, up, down, wt, tok, starts, counts), counts


def experts_mixer(p: dict, x, arch: HybridArch, real=None):
    """``real`` (B, S) bool: the positions that hold a token; the others are
    not routed."""
    dtype = x.dtype
    bsz, length, hidden = x.shape
    flat = x.reshape(bsz * length, hidden)
    ids, weights = route(flat, p["router"], arch)
    if real is not None:
        ids = jnp.where(real.reshape(-1, 1), ids, arch.experts)
    routed, counts = held_experts_part(
        flat, ids, weights, p["experts_up"].astype(dtype),
        p["experts_down"].astype(dtype), arch.held, arch.experts)
    shared = _expert(flat, p["shared_up"].astype(dtype),
                     p["shared_down"].astype(dtype))
    out = routed.astype(dtype) + shared
    return out.reshape(bsz, length, hidden), counts


# ---------------------------------------------------------------------------
# the stack
# ---------------------------------------------------------------------------

def _dt_bias_init(arch: HybridArch):
    def init(key, shape, dtype=jnp.float32):
        dt = jnp.exp(jax.random.uniform(key, shape, dtype)
                     * (math.log(arch.dt_max) - math.log(arch.dt_min))
                     + math.log(arch.dt_min))
        dt = jnp.maximum(dt, arch.dt_floor)
        # softplus's inverse; dt >= dt_floor > 0
        return dt + jnp.log(-jnp.expm1(-dt))  # lint-ok: nonfinite-escape dt is floored above 0
    return init


def _a_log_init(key, shape, dtype=jnp.float32):
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))  # lint-ok: nonfinite-escape uniform in [1, 16)


def _uniform(bound: float):
    def init(key, shape, dtype=jnp.float32):
        return jax.random.uniform(key, shape, dtype, -bound, bound)
    return init


class HybridLayer(nn.Module):
    """``h + Mixer(RMSNorm(h))`` for one character of the pattern; also the
    tokens each held expert saw (zeros where the mixer has no experts).
    ``real`` (B, S) marks the positions that hold a token."""

    kind: str
    arch: HybridArch

    @nn.compact
    def __call__(self, h, real):
        a = self.arch
        dense = nn.initializers.normal(0.02)
        ones = nn.initializers.ones

        def params(shapes: dict) -> dict:
            return {n: self.param(n, init, shape)
                    for n, (init, shape) in shapes.items()}

        y = rms_norm(h, self.param("norm", ones, (a.hidden,)), a.eps)
        counts = jnp.zeros(max(len(a.held), 1), jnp.int32)
        if self.kind == "M":
            p = params({
                "in_proj": (dense, (a.hidden, a.d_inner + a.conv_dim
                                    + a.mamba_heads)),
                "conv_kernel": (_uniform(a.conv ** -0.5), (a.conv, a.conv_dim)),
                "conv_bias": (_uniform(a.conv ** -0.5), (a.conv_dim,)),
                "dt_bias": (_dt_bias_init(a), (a.mamba_heads,)),
                "A_log": (_a_log_init, (a.mamba_heads,)),
                "D": (ones, (a.mamba_heads,)),
                "gate_norm": (ones, (a.d_inner,)),
                "out_proj": (dense, (a.d_inner, a.hidden))})
            with jax.named_scope("mamba2"):
                out = mamba2_mixer(p, y, a)
        elif self.kind == "*":
            p = params({
                "q": (dense, (a.hidden, a.heads * a.head_dim)),
                "k": (dense, (a.hidden, a.kv_heads * a.head_dim)),
                "v": (dense, (a.hidden, a.kv_heads * a.head_dim)),
                "o": (dense, (a.heads * a.head_dim, a.hidden))})
            with jax.named_scope("attention"):
                out = attention_mixer(p, y, a)
        else:
            p = params({
                "router": (dense, (a.hidden, a.experts)),
                "experts_up": (dense, (len(a.held), a.hidden, a.expert_width)),
                "experts_down": (dense, (len(a.held), a.expert_width, a.hidden)),
                "shared_up": (dense, (a.hidden, a.shared_width)),
                "shared_down": (dense, (a.shared_width, a.hidden))})
            with jax.named_scope("experts"):
                out, counts = experts_mixer(p, y, a, real)
        return h + out, counts


class HybridBackbone(nn.Module):
    arch: HybridArch
    num_classes: int = 2
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, ids, train: bool = True):
        a = self.arch
        h = nn.Embed(a.vocab, a.hidden, dtype=self.dtype, name="tok_embed")(ids)
        layer = nn.remat(HybridLayer)
        real = ids != _text.PAD_ID
        loads = []
        for i, kind in enumerate(a.pattern):
            h, counts = layer(kind, a, name=f"layer_{i}")(h, real)
            if kind == "E":
                loads.append(counts)
        h = rms_norm(h, self.param("final_norm", nn.initializers.ones,
                                   (a.hidden,)), a.eps)
        # the mean over the words: a row read at one position would hang on
        # that position's choice of experts in every expert layer
        pooled = (jnp.where(real[..., None], h.astype(jnp.float32), 0.0).sum(1)
                  / jnp.maximum(real.sum(-1, keepdims=True), 1))
        self._count("tokens", jnp.asarray(ids.size, jnp.int32))
        self._count("padTokens", (~real).sum().astype(jnp.int32))
        if loads:
            loads = jnp.stack(loads)                   # (E layers, held)
            self._count("routedPairs", loads.sum())
            self._count("expertTokens", loads)
        return nn.Dense(self.num_classes, use_bias=False, dtype=jnp.float32,
                        kernel_init=nn.initializers.normal(0.02),
                        name="head")(pooled)

    def _count(self, name: str, value):
        """Adds to the running sum ``counters/<name>`` (the trainer hands the
        sums in, takes them back with the step's output and zeroes them every
        epoch)."""
        self.sow("counters", name, value, reduce_fn=jnp.add,
                 init_fn=lambda: jnp.zeros_like(value))
