"""Pallas TPU flash-attention forward — the fused hot-op for long context.

The attention stack (parallel/ring_attention.py) already computes blockwise
online softmax, but as XLA ops: every (block_q, block_k) score tile round-
trips through HBM-visible intermediates. This kernel fuses scores, masking,
the online-softmax rescale, and the PV matmul into ONE Pallas program —
Q/K/V stream through VMEM once and the S² score matrix never exists
anywhere (the public FlashAttention / blockwise-parallel formulation; the
reference's DL stack has no long-context path at all — SURVEY §5.7 lists
this repo's long-context support as its bonus surface).

Differentiation: ``flash_attention`` carries a custom VJP whose backward
RECOMPUTES through the existing XLA blockwise path — the forward stays a
pure fused kernel, memory stays O(S·block), and gradients are exactly the
blockwise path's (itself equality-tested against attention_reference).

On the TPU backend each kernel is checked once per process against the XLA
blockwise path and a failure raises ``KernelError`` (ops/hist_kernel.py);
nothing falls back. Non-TPU backends always take the XLA path —
``interpret=True`` exists for CPU correctness tests of the kernel itself.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from .hist_kernel import _eager_selftest, check_kernel

_NEG_INF = -1e30          # finite -inf stand-in: keeps exp() NaN-free
# on-chip check tolerance: the kernels' f32 matmuls run at the TPU's default
# (bf16-pass) precision — measured 2.6e-3 against the full-precision
# reference on a v5e — while a masking or rescale bug is O(0.1)
_CHECK_TOL = 2e-2


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  scale: float, causal: bool, block_q: int, block_k: int,
                  s_q: int, s_k: int):
    """One (bh, q-block) × sequential-k-block step of the online softmax.

    Scratch (acc, m, l) persists across the sequential last grid dimension
    (TPU grids execute in order); m/l are stored lane-replicated at width
    128 so every store stays tile-aligned."""
    from jax.experimental import pallas as pl

    ki = pl.program_id(2)
    qi = pl.program_id(1)

    @pl.when(ki == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # causal: a k-block entirely above the diagonal contributes nothing —
    # skip its matmuls outright (~2x on the causal hot path)
    live = (ki * block_k <= qi * block_q + block_q - 1 if causal
            else ki >= 0)

    @pl.when(live)
    def _():
        q = q_ref[0]                                 # (block_q, D)
        k = k_ref[0]                                 # (block_k, D)
        v = v_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (bq, bk)
        rows = qi * block_q + lax.broadcasted_iota(jnp.int32,
                                                   (block_q, block_k), 0)
        cols = ki * block_k + lax.broadcasted_iota(jnp.int32,
                                                   (block_q, block_k), 1)
        valid = cols < s_k                           # kv padding mask
        if causal:
            valid &= rows >= cols
        s = jnp.where(valid, s, _NEG_INF)

        m_old = m_ref[...][:, :1]                    # (block_q, 1)
        l_old = l_ref[...][:, :1]
        m_new = jnp.maximum(m_old, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_old - m_new)               # finite: m monotone
        p = jnp.exp(s - m_new)                       # masked entries -> ~0
        p = jnp.where(valid, p, 0.0)                 # exact zero for padding
        l_new = l_old * alpha + p.sum(axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ki == pl.num_programs(2) - 1)
    def _():
        denom = jnp.where(l_ref[...][:, :1] > 0, l_ref[...][:, :1], 1.0)
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def _blocks_and_pad(q, k, v, block_q: int, block_k: int):
    """Shared layout preamble for both kernels: 8-row-aligned block clamp
    (f32 sublane tile — a raw-seq-length clip would hand Mosaic shapes the
    one-shot selftest never exercised), (B, S, H, D) → (B·H, S, D), and
    zero-padding to block multiples (padded kv columns are masked inside
    the kernels; padded q rows are dropped by the callers)."""
    B, s_q, H, D = q.shape
    s_k = k.shape[1]
    bq = min(block_q, -(-max(s_q, 8) // 8) * 8)
    bk = min(block_k, -(-max(s_k, 8) // 8) * 8)
    pad_q = (-s_q) % bq
    pad_k = (-s_k) % bk
    qT = jnp.moveaxis(q, 2, 1).reshape(B * H, s_q, D)
    kT = jnp.moveaxis(k, 2, 1).reshape(B * H, s_k, D)
    vT = jnp.moveaxis(v, 2, 1).reshape(B * H, s_k, D)
    if pad_q:
        qT = jnp.pad(qT, ((0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        kT = jnp.pad(kT, ((0, 0), (0, pad_k), (0, 0)))
        vT = jnp.pad(vT, ((0, 0), (0, pad_k), (0, 0)))
    return B, H, D, s_q, s_k, bq, bk, pad_q, qT, kT, vT


def _vmem_state_scratch(bq: int, D: int):
    from jax.experimental.pallas import tpu as pltpu

    return [pltpu.VMEM((bq, D), jnp.float32),        # acc
            pltpu.VMEM((bq, 128), jnp.float32),      # running max m
            pltpu.VMEM((bq, 128), jnp.float32)]      # normalizer l


@functools.partial(jax.jit, static_argnames=("causal", "scale", "block_q",
                                             "block_k", "interpret"))
def _flash_forward(q, k, v, causal: bool, scale: float, block_q: int,
                   block_k: int, interpret: bool):
    """(B, S, H, D) → (B, S, H, D): pad to block multiples, run the kernel
    over a (B·H, q-blocks, k-blocks) grid, slice the padding back off."""
    from jax.experimental import pallas as pl

    (B, H, D, s_q, s_k, bq, bk, _,
     qT, kT, vT) = _blocks_and_pad(q, k, v, block_q, block_k)
    nq, nk = qT.shape[1] // bq, kT.shape[1] // bk

    out = pl.pallas_call(
        functools.partial(_flash_kernel, scale=scale, causal=causal,
                          block_q=bq, block_k=bk, s_q=s_q, s_k=s_k),
        grid=(B * H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct(qT.shape, q.dtype),
        scratch_shapes=_vmem_state_scratch(bq, D),
        interpret=interpret,
    )(qT, kT, vT)
    out = out[:, :s_q].reshape(B, H, s_q, D)
    return jnp.moveaxis(out, 1, 2)                   # (B, S, H, D)


def divisor_block(s: int, want: int, floor: int = 8) -> int:
    """Largest divisor of ``s`` that is <= ``want`` and >= ``floor`` (0 when
    none exists) — keeps the blockwise path available for non-divisible
    sequence lengths instead of degrading to the O(S^2) reference."""
    for b in range(min(want, s), floor - 1, -1):
        if s % b == 0:
            return b
    return 0


def _xla_fallback(q, k, v, causal: bool, scale: float, block_k: int):
    """The existing blockwise path at the largest workable block divisor,
    or the reference einsum only when no divisor >= 8 exists (near-prime
    lengths) — one semantic, chosen by shape. This is also the backward
    recompute path: memory stays O(S·block) whenever a divisor exists."""
    from ..parallel.ring_attention import (attention_reference,
                                           blockwise_attention)

    bs = divisor_block(k.shape[1], block_k)
    if bs:
        return blockwise_attention(q, k, v, block_size=bs,
                                   causal=causal, scale=scale)
    return attention_reference(q, k, v, causal=causal, scale=scale)


@functools.cache
@_eager_selftest
def _check_flash_kernel() -> None:
    """One small on-device compile+run of the fused forward at the
    PRODUCTION block size (128) on a padded non-divisible length, so the
    lowering-relevant shapes — full 128-row tiles plus the padded edge
    block — are the ones checked; raises KernelError."""
    import numpy as np

    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(rng.normal(size=(2, 300, 2, 64)), jnp.float32)
               for _ in range(3))
    for causal in (False, True):
        check_kernel(
            "_flash_forward",
            dict(q=q.shape, dtype="float32", causal=causal, block_q=128,
                 block_k=128),
            lambda: _flash_forward(q, k, v, causal, 0.125, 128, 128,
                                   False),
            lambda: _xla_fallback(q, k, v, causal, 0.125, 128),
            rtol=_CHECK_TOL, atol=_CHECK_TOL)


def flash_attention(q, k, v, causal: bool = False,
                    scale: Optional[float] = None, block_q: int = 128,
                    block_k: int = 128, interpret: bool = False):
    """Fused flash attention, differentiable. Layout (B, S, H, D) — the same
    convention as attention_reference / blockwise_attention, and the same
    outputs to kernel tolerance. Backward recomputes through the XLA
    blockwise path (O(S·block) memory both directions). ``scale`` must be
    a static scalar (it folds into the compiled kernel); concrete jax/numpy
    scalars are accepted and converted."""
    scale = float(scale) if scale is not None else q.shape[-1] ** -0.5
    use_kernel = interpret or jax.default_backend() == "tpu"
    if use_kernel and not interpret:
        _check_flash_kernel()

    @jax.custom_vjp
    def f(q, k, v):
        if use_kernel:
            return _flash_forward(q, k, v, causal, scale, block_q, block_k,
                                  interpret)
        return _xla_fallback(q, k, v, causal, scale, block_k)

    def fwd(q, k, v):
        return f(q, k, v), (q, k, v)

    def bwd(res, g):
        q, k, v = res
        _, vjp = jax.vjp(
            lambda a, b, c: _xla_fallback(a, b, c, causal, scale, block_k),
            q, k, v)
        return vjp(g)

    f.defvjp(fwd, bwd)
    return f(q, k, v)


# ---------------------------------------------------------------------------
# State-carrying variant: the ring's inner step (parallel/ring_attention.py
# rotates K/V blocks around the mesh and folds each into carried online-
# softmax state). Same fused math as _flash_kernel, but (m, l, acc) enter
# and leave as tensors instead of living only in scratch — so the ring can
# run its per-step block attention as ONE kernel on TPU.
# ---------------------------------------------------------------------------

def _flash_block_kernel(off_ref, q_ref, k_ref, v_ref, m_in_ref, l_in_ref,
                        o_in_ref, m_out_ref, l_out_ref, o_out_ref,
                        acc_ref, m_ref, l_ref, *, scale, causal,
                        block_q, block_k, s_k):
    """off_ref (SMEM, scalar-prefetched): [q_offset, k_offset] — the blocks'
    GLOBAL sequence starts, traced values inside the ring's shard_map (the
    rank index decides them, so they cannot be compile-time constants)."""
    from jax.experimental import pallas as pl

    ki = pl.program_id(2)
    qi = pl.program_id(1)

    @pl.when(ki == 0)
    def _():
        acc_ref[...] = o_in_ref[0].astype(jnp.float32)
        m_ref[...] = jnp.broadcast_to(
            jnp.maximum(m_in_ref[0], _NEG_INF), m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_in_ref[0], l_ref.shape)

    # causal dead-block skip with RUNTIME offsets (same ~2x win as the
    # plain kernel's static guard): the whole tile is in the causal future
    # when its first global column exceeds the last global row
    live = (off_ref[1] + ki * block_k
            <= off_ref[0] + qi * block_q + block_q - 1
            if causal else ki >= 0)

    @pl.when(live)
    def _():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        rows = (off_ref[0] + qi * block_q
                + lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0))
        cols_local = ki * block_k + lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        cols = off_ref[1] + cols_local
        valid = cols_local < s_k
        if causal:
            valid &= rows >= cols
        s = jnp.where(valid, s, _NEG_INF)

        m_old = m_ref[...][:, :1]
        l_old = l_ref[...][:, :1]
        m_new = jnp.maximum(m_old, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_old - m_new)
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        l_new = l_old * alpha + p.sum(axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ki == pl.num_programs(2) - 1)
    def _():
        m_out_ref[0] = m_ref[...][:, :1]
        l_out_ref[0] = l_ref[...][:, :1]
        o_out_ref[0] = acc_ref[...].astype(o_out_ref.dtype)


def comparable_state(m, l, o):
    """Carried online-softmax state as (log-sum-exp, normalized output) —
    the two things it determines, and the form in which a kernel's state is
    compared with the XLA step's. m and l alone are only defined up to a
    shift (an error d in m rescales l by exp(-d)), and the unnormalized
    accumulator cancels to near zero where its absolute error is still
    ~l x eps. Fully-masked rows (l = 0) compare as the finite sentinel."""
    from ..parallel.ring_attention import _finalize

    lse = jnp.where(l > 0, m + jnp.log(jnp.where(l > 0, l, 1.0)), _NEG_INF)
    return lse, _finalize(m, l, o)


@functools.cache
@_eager_selftest
def _check_flash_block_kernel() -> None:
    """On-device check of the STATE-CARRYING lowering specifically (scalar
    prefetch, multi-output, (1, bq, 1) state blocks) — a distinct Mosaic
    compile path from _flash_forward's; raises KernelError."""
    import numpy as np

    from ..parallel.ring_attention import _block_attention

    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(2, 140, 2, 64)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(2, 128, 2, 64)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(2, 128, 2, 64)), jnp.float32)
    m0 = jnp.full((2, 2, 140), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((2, 2, 140), jnp.float32)
    o0 = jnp.zeros((2, 140, 2, 64), jnp.float32)

    for causal in (False, True):
        check_kernel(
            "flash_attention_block",
            dict(q=q.shape, k=k.shape, dtype="float32", causal=causal,
                 block_q=128, block_k=128),
            lambda: comparable_state(*flash_attention_block(
                q, k, v, m0, l0, o0, q_offset=64, k_offset=0, causal=causal,
                scale=0.125)),
            lambda: comparable_state(*_block_attention(
                q, k, v, m0, l0, o0, 64, 0, causal, 0.125)),
            rtol=_CHECK_TOL, atol=_CHECK_TOL)


def flash_attention_block(q, k, v, m, l, o, q_offset, k_offset,
                          causal: bool = False, scale: float = None,
                          block_q: int = 128, block_k: int = 128,
                          interpret: bool = False):
    """One fused online-softmax update of carried state — the drop-in
    kernel form of ring_attention._block_attention. Layouts match the
    ring: q (B, Sq, H, D), k/v (B, Sk, H, D), m/l (B, H, Sq) running
    max/normalizer, o (B, Sq, H, D) UNNORMALIZED accumulator; offsets are
    the blocks' global sequence starts (traced values are fine — they ride
    scalar prefetch). -inf entries in ``m`` are mapped to the kernel's
    finite sentinel; finalize with ring_attention._finalize as usual."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    scale = float(scale) if scale is not None else q.shape[-1] ** -0.5
    (B, H, D, s_q, s_k, bq, bk, pad_q,
     qT, kT, vT) = _blocks_and_pad(q, k, v, block_q, block_k)
    # m/l ride as (B·H, Sq, 1) columns: a (1, bq, 1) block keeps the last
    # two dimensions (8-divisible, whole) legal for the TPU lowering, and
    # the kernel reads the (bq, 1) column it needs with no relayout
    mT = m.reshape(B * H, s_q, 1)
    lT = l.reshape(B * H, s_q, 1)
    oT = jnp.moveaxis(o, 2, 1).reshape(B * H, s_q, D)
    if pad_q:
        pad = ((0, 0), (0, pad_q), (0, 0))
        oT = jnp.pad(oT, pad)
        mT = jnp.pad(mT, pad, constant_values=_NEG_INF)
        lT = jnp.pad(lT, pad)
    nq, nk = qT.shape[1] // bq, kT.shape[1] // bk
    offs = jnp.asarray(
        jnp.stack([jnp.asarray(q_offset, jnp.int32).reshape(()),
                   jnp.asarray(k_offset, jnp.int32).reshape(())]))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B * H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j, off: (b, i, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j, off: (b, j, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j, off: (b, j, 0)),
            pl.BlockSpec((1, bq, 1), lambda b, i, j, off: (b, i, 0)),
            pl.BlockSpec((1, bq, 1), lambda b, i, j, off: (b, i, 0)),
            pl.BlockSpec((1, bq, D), lambda b, i, j, off: (b, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, 1), lambda b, i, j, off: (b, i, 0)),
            pl.BlockSpec((1, bq, 1), lambda b, i, j, off: (b, i, 0)),
            pl.BlockSpec((1, bq, D), lambda b, i, j, off: (b, i, 0)),
        ],
        scratch_shapes=_vmem_state_scratch(bq, D),
    )
    m2, l2, o2 = pl.pallas_call(
        functools.partial(_flash_block_kernel, scale=scale, causal=causal,
                          block_q=bq, block_k=bk, s_k=s_k),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(mT.shape, jnp.float32),
            jax.ShapeDtypeStruct(lT.shape, jnp.float32),
            jax.ShapeDtypeStruct(oT.shape, jnp.float32),
        ],
        interpret=interpret,
    )(offs, qT, kT, vT, mT, lT, oT)
    m2 = m2[:, :s_q, 0].reshape(B, H, s_q)
    l2 = l2[:, :s_q, 0].reshape(B, H, s_q)
    o2 = jnp.moveaxis(o2[:, :s_q].reshape(B, H, s_q, D), 1, 2)
    return m2, l2, o2
