"""Pallas TPU histogram kernel — the GBDT hot loop on the MXU.

The reference's hot loop is LightGBM C++ ``ConstructHistograms`` driven through
``LGBM_BoosterUpdateOneIter`` (booster/LightGBMBooster.scala:355-392): for every
row and feature, add (grad, hess, 1) into the (feature, bin) histogram slot.
TPUs have no fast scatter, so this kernel reformulates histogramming as a
**two-level one-hot matmul on the MXU**:

    bin = hi * 8 + lo                     (hi in [0, B/8), lo in [0, 8))
    LHS[hi, row]        = 1{bin_hi(row) == hi}          (B/8, C)  bf16
    RHS[row, ch*8 + lo] = 1{bin_lo(row) == lo} * val_ch (C, 24)   bf16
    out[hi, ch*8+lo]   += LHS @ RHS                     (B/8, 24) f32 accum

Each (row, feature) costs one 128x128 MXU output tile per C-row chunk — the
cheapest possible one-hot-matmul decomposition (a single-level one-hot needs
two tiles: M = B = 256). The one-hot factors are generated in VMEM registers
and never touch HBM; gradients are rounded to bf16 (exact 0/1 LHS, f32
accumulation), which matches the precision story of LightGBM's GPU float
histograms.

Numerically the result equals a scatter-add with bf16-rounded grad/hess. The
XLA reference (`_hist_xla`) — used on CPU (tests' virtual mesh) and any
non-TPU backend — applies the same bf16 rounding so both paths agree bit-wise
in the accumulated sums up to f32 reduction order. On the TPU backend every
kernel is checked against it once per process and a failure raises
:class:`KernelError`; nothing falls back.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax import lax

FEATURE_BLOCK = 8     # features per kernel step (i32 sublane tile)
LANE = 128


class KernelError(RuntimeError):
    """A Pallas kernel failed to compile or run, or disagreed with its XLA
    reference, on the TPU backend. There is no fallback: a broken kernel
    must stop the job, not show up as a slow one."""


def check_kernel(kernel: str, shapes: dict, run, reference,
                 rtol: float, atol: float) -> None:
    """Run ``run()`` (the kernel) and ``reference()`` once and compare their
    array leaves. Any failure raises :class:`KernelError` naming the kernel,
    its static shapes and the compiler's own message."""
    import numpy as _np

    what = (f"Pallas kernel {kernel} ("
            + ", ".join(f"{k}={v}" for k, v in shapes.items()) + ")")
    try:
        got = [_np.asarray(x) for x in jax.tree.leaves(run())]
    except Exception as e:   # re-raised with the kernel named
        raise KernelError(
            f"{what} failed to compile or run on backend "
            f"{jax.default_backend()!r}: {type(e).__name__}: {e}") from e
    # the TPU's default f32 matmul is a bf16 pass: the reference is the
    # full-precision answer, and the tolerance is the kernel's alone
    with jax.default_matmul_precision("highest"):
        want = [_np.asarray(x) for x in jax.tree.leaves(reference())]
    for i, (g, w) in enumerate(zip(got, want)):
        if g.shape != w.shape or not _np.allclose(g, w, rtol=rtol,
                                                  atol=atol):
            err = (float(_np.max(_np.abs(g - w))) if g.shape == w.shape
                   else f"shape {g.shape} vs {w.shape}")
            raise KernelError(
                f"{what} disagrees with its XLA reference on backend "
                f"{jax.default_backend()!r}: output {i} max |diff| = {err} "
                f"(rtol={rtol}, atol={atol})")


def _eager_selftest(fn):
    """Run a kernel check outside any ambient trace: in a thread of its own.

    The checks compile+run small on-device programs and compare results as
    numpy — but their FIRST call can happen during an outer jit trace
    (``child_histogram`` is reached while the grower's ``lax.switch``
    branches trace), where every jnp op produces tracers and ``np.asarray``
    raises TracerArrayConversionError (observed on-chip 2026-08-02). jax's
    trace stack and config contexts are thread-local, so a fresh thread is
    exactly a top-level call. ``ensure_compile_time_eval`` is NOT enough: it
    keeps the ambient trace and folds constants eagerly, and a Pallas index
    map traced under it captures those constants, which the TPU lowering
    refuses ("Index map function ... must not capture constants", v5e,
    PR 21). ``functools.cache`` stays
    outermost so a kernel is checked once per process (a raised KernelError
    is not cached)."""
    @functools.wraps(fn)
    def wrapper(*a, **k):
        from concurrent.futures import ThreadPoolExecutor

        with ThreadPoolExecutor(max_workers=1) as pool:
            return pool.submit(fn, *a, **k).result()
    return wrapper


def default_chunk() -> int:
    """Rows per kernel step: SYNAPSEML_TPU_HIST_CHUNK, else 2048. A
    malformed value fails HERE with the variable named, not as a
    ZeroDivisionError mid-trace."""
    v = os.environ.get("SYNAPSEML_TPU_HIST_CHUNK") or 2048
    try:
        c = int(v)
        if c <= 0:
            raise ValueError
    except (TypeError, ValueError):
        raise ValueError(
            f"SYNAPSEML_TPU_HIST_CHUNK={v!r}: want a positive integer "
            "(kernel rows per grid step)") from None
    return c


def pad_bins(max_bin: int) -> int:
    """Kernel bin-space size: power of two >= max_bin, at least 256 (so hi fits
    the MXU sublane dim and lo is exactly 3 bits)."""
    b = 256
    while b < max_bin:
        b *= 2
    return b


def features_padded(f: int) -> int:
    return -(-f // FEATURE_BLOCK) * FEATURE_BLOCK


def _kernel(bin_ref, g_ref, h_ref, m_ref, out_ref, *, C: int, K1: int,
            FB: int, PACK: int):
    """Grid (feature_blocks, row_chunks). bin_ref (FB, C) i32,
    g/h/m (C,) f32, out (FB, K1, 24) f32 accumulated over chunks.

    PACK features share ONE dot: LHS (PACK*K1, C) stacks each feature's
    hi-one-hot along M and RHS (C, PACK*24) stacks each feature's
    lo-masked values along N, so one K-step streams PACK row-features
    through the MXU instead of one. The dot computes all PACK^2 cross
    blocks; only the diagonal blocks are histograms and the rest is
    discarded — the off-diagonal MACs ride the same cycles for free
    (the MXU is K-serialized: cost is C cycles per tile-pass regardless
    of how much of the 128x128 tile is useful). With K1=32, PACK=4 fills
    M=128, N=96 — one full tile-pass per K-step, ~4x the row-feature
    throughput of the per-feature formulation."""
    from jax.experimental import pallas as pl  # deferred: CPU never imports

    @pl.when(pl.program_id(1) == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    _packed_accumulate(bin_ref, out_ref, g_ref[:], h_ref[:], m_ref[:],
                       C=C, K1=K1, FB=FB, PACK=PACK)


def _packed_accumulate(bin_ref, out_ref, g1, h1, m1, *, C: int, K1: int,
                       FB: int, PACK: int):
    """Shared MXU pack body for both kernels: g1/h1/m1 are (C,) f32 value
    channels (already edge-masked by the segmented caller). All construction
    stays 2D (Mosaic-friendly: no cross-tile reshapes or gathers):
    per-position feature/hi/lo/channel ids come from iota math, and the
    per-feature bin rows are selected with PACK static where-terms."""
    from jax.experimental import pallas as pl

    M, N = PACK * K1, PACK * 24
    mf = lax.broadcasted_iota(jnp.int32, (M, C), 0) // K1        # row feature
    hi_pat = lax.broadcasted_iota(jnp.int32, (M, C), 0) % K1
    col = lax.broadcasted_iota(jnp.int32, (C, N), 1)
    nf = col // 24                                               # col feature
    rem = col - nf * 24
    ch_pat = rem >> 3
    lo_pat = rem & 7
    g2, h2, m2 = g1[:, None], h1[:, None], m1[:, None]
    val = jnp.where(ch_pat == 0, g2, jnp.where(ch_pat == 1, h2, m2))

    def pbody(p, _):
        bins_rows = jnp.zeros((M, C), jnp.int32)
        bins_cols = jnp.zeros((C, N), jnp.int32)
        for f in range(PACK):
            bf = bin_ref[pl.ds(p * PACK + f, 1), :]              # (1, C)
            bins_rows = jnp.where(mf == f, bf, bins_rows)
            bins_cols = jnp.where(nf == f, bf.T, bins_cols)
        lhs = (hi_pat == (bins_rows >> 3)).astype(jnp.bfloat16)
        rhs = jnp.where(lo_pat == (bins_cols & 7), val, 0.0
                        ).astype(jnp.bfloat16)
        acc = jnp.dot(lhs, rhs, preferred_element_type=jnp.float32)
        for f in range(PACK):                                    # diagonal
            blk = acc[f * K1:(f + 1) * K1, f * 24:(f + 1) * 24]
            out_ref[pl.ds(p * PACK + f, 1)] += blk[None]
        return 0

    lax.fori_loop(0, FB // PACK, pbody, 0)


def _pack_for(K1: int, FB: int, pack) -> int:
    """Features per dot: fill the 128-row MXU tile (M = PACK*K1) while
    keeping N = PACK*24 within one 128-lane tile; PACK must divide FB.
    ``pack`` (the argument, else SYNAPSEML_TPU_HIST_PACK) forces — clamped
    to the same tile constraints (128 // K1, 5, FB) so a forced value can
    never lose the one-tile-pass property the kernel docstring promises."""
    force = pack or os.environ.get("SYNAPSEML_TPU_HIST_PACK")
    PACK = max(1, min(int(force) if force else 128, 128 // K1, 5, FB))
    while FB % PACK:
        PACK -= 1
    return PACK


def _epilogue(out, FP: int, K1: int, num_bins_padded: int):
    # columns are (ch, lo): (FP, K1, 3, 8) -> (FP, K1, 8, 3) -> (FP, B, 3)
    return out.reshape(FP, K1, 3, 8).transpose(0, 1, 3, 2).reshape(
        FP, num_bins_padded, 3)


@functools.partial(jax.jit,
                   static_argnames=("num_bins_padded", "chunk", "interpret",
                                    "feature_block", "pack"))
def _hist_pallas(bT, g, h, m, num_bins_padded: int, chunk: int = None,
                 interpret: bool = False, feature_block: int = None,
                 pack: int = None):
    from jax.experimental import pallas as pl

    FP, n = bT.shape
    C = min(chunk or default_chunk(), n)
    FB = feature_block or FEATURE_BLOCK
    assert n % C == 0 and FP % FB == 0
    K1 = num_bins_padded // 8
    PACK = _pack_for(K1, FB, pack)
    out = pl.pallas_call(
        functools.partial(_kernel, C=C, K1=K1, FB=FB, PACK=PACK),
        grid=(FP // FB, n // C),
        in_specs=[
            pl.BlockSpec((FB, C), lambda f, c: (f, c)),
            pl.BlockSpec((C,), lambda f, c: (c,)),
            pl.BlockSpec((C,), lambda f, c: (c,)),
            pl.BlockSpec((C,), lambda f, c: (c,)),
        ],
        out_specs=pl.BlockSpec((FB, K1, 24), lambda f, c: (f, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((FP, K1, 24), jnp.float32),
        interpret=interpret,
    )(bT, g, h, m)
    # columns are (ch, lo): (FP, K1, 3, 8) -> (FP, K1, 8, 3) -> (FP, B, 3)
    return _epilogue(out, FP, K1, num_bins_padded)


def _range_kernel(info_ref, bin_ref, g_ref, h_ref, m_ref, out_ref, *,
                  C: int, K1: int, FB: int, PACK: int):
    """Segmented variant of :func:`_kernel`: the grid's row-chunk dimension
    starts at the block index derived from the scalar-prefetched
    ``info = [start, length]`` (see the index_maps in _hist_pallas_range),
    and edge rows outside [start, start+length) are masked HERE — so the
    caller passes the FULL row arrays and no dynamic_slice copy or
    pre-kernel mask multiply exists at all."""
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(1) == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    start, length = info_ref[0], info_ref[1]
    n_chunks = pl.num_programs(1)
    total = jnp.int32(C) * n_chunks
    first_chunk = jnp.minimum(start // C,
                              (info_ref[2] - total) // C)   # info[2] = Np
    row0 = (first_chunk + pl.program_id(1)) * C
    rows = row0 + lax.broadcasted_iota(jnp.int32, (C,), 0)
    inr = ((rows >= start) & (rows < start + length)).astype(jnp.float32)

    _packed_accumulate(bin_ref, out_ref, g_ref[:] * inr, h_ref[:] * inr,
                       m_ref[:] * inr, C=C, K1=K1, FB=FB, PACK=PACK)


@functools.partial(jax.jit,
                   static_argnames=("num_bins_padded", "size", "chunk",
                                    "interpret", "feature_block", "pack"))
def _hist_pallas_range(bT, g, h, m, start, length, num_bins_padded: int,
                       size: int, chunk: int = None, interpret: bool = False,
                       feature_block: int = None, pack: int = None):
    """Histogram of rows [start, start+length) of the FULL (FP, Np) arrays.
    ``size`` (static) is the covered extent: a multiple of the chunk with
    size >= length + chunk, so the chunk-aligned window starting at or
    before ``start`` always covers the range (edge rows masked in-kernel).
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    FP, n = bT.shape
    C = min(chunk or default_chunk(), n)
    FB = feature_block or FEATURE_BLOCK
    assert n % C == 0 and FP % FB == 0 and size % C == 0 and size <= n
    K1 = num_bins_padded // 8
    PACK = _pack_for(K1, FB, pack)
    info = jnp.stack([jnp.asarray(start, jnp.int32),
                      jnp.asarray(length, jnp.int32),
                      jnp.asarray(n, jnp.int32)])

    def row_block(f, c, info_ref):
        first = jnp.minimum(info_ref[0] // C, jnp.int32((n - size) // C))
        return first + c

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(FP // FB, size // C),
        in_specs=[
            pl.BlockSpec((FB, C), lambda f, c, i: (f, row_block(f, c, i))),
            pl.BlockSpec((C,), lambda f, c, i: (row_block(f, c, i),)),
            pl.BlockSpec((C,), lambda f, c, i: (row_block(f, c, i),)),
            pl.BlockSpec((C,), lambda f, c, i: (row_block(f, c, i),)),
        ],
        out_specs=pl.BlockSpec((FB, K1, 24), lambda f, c, i: (f, 0, 0)),
    )
    out = pl.pallas_call(
        functools.partial(_range_kernel, C=C, K1=K1, FB=FB, PACK=PACK),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((FP, K1, 24), jnp.float32),
        interpret=interpret,
    )(info, bT, g, h, m)
    return _epilogue(out, FP, K1, num_bins_padded)


def _level_kernel(starts_ref, bin_ref, g_ref, h_ref, m_ref, out_ref, *,
                  C: int, K1: int, FB: int, PACK: int, SLOTS: int):
    """Multi-leaf kernel: ONE pass over chunk-aligned slot-partitioned rows
    histograms EVERY slot (leaf) of a level. ``starts_ref`` (SLOTS+1,) i32
    holds each slot's first chunk index (ascending; starts[SLOTS] = total
    chunks). The output block for grid step (f, c) is the slot owning chunk
    c — computed by the same compare-sum in the index_map and here; the
    block is zero-initialized on the slot's first chunk. Slot-tail padding
    rows carry g=h=m=0, so no edge masking is needed."""
    from jax.experimental import pallas as pl

    c = pl.program_id(1)
    # first chunk of the owning slot ⇔ c equals ANY slot start (starts are
    # ascending and distinct — every slot has >= one chunk of capacity);
    # unrolled: dynamic indexing of the SMEM scalar ref is not supported
    is_first = c == starts_ref[0]
    for i in range(1, SLOTS):
        is_first |= c == starts_ref[i]

    @pl.when(is_first)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    _packed_accumulate(bin_ref, out_ref, g_ref[:], h_ref[:], m_ref[:],
                       C=C, K1=K1, FB=FB, PACK=PACK)


@functools.partial(jax.jit,
                   static_argnames=("num_bins_padded", "slots", "chunk",
                                    "interpret", "feature_block", "pack"))
def _hist_pallas_level(bT, g, h, m, start_chunks, num_bins_padded: int,
                       slots: int, chunk: int = None,
                       interpret: bool = False, feature_block: int = None,
                       pack: int = None):
    """(SLOTS, FP, B, 3) histograms of ALL slots in one kernel pass.
    ``bT``/``g``/``h``/``m`` are slot-partitioned with every slot starting
    at a chunk boundary (tail padding rows must carry zero g/h/m);
    ``start_chunks`` (slots,) i32 ascending first-chunk index per slot."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    FP, n = bT.shape
    C = min(chunk or default_chunk(), n)
    FB = feature_block or FEATURE_BLOCK
    assert n % C == 0 and FP % FB == 0
    K1 = num_bins_padded // 8
    PACK = _pack_for(K1, FB, pack)
    total_chunks = n // C
    starts = jnp.concatenate([
        jnp.asarray(start_chunks, jnp.int32),
        jnp.full((1,), total_chunks, jnp.int32)])

    def slot_of(c, starts_ref):
        s = jnp.int32(0)
        for i in range(1, slots):
            s += (c >= starts_ref[i]).astype(jnp.int32)
        return s

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(FP // FB, total_chunks),
        in_specs=[
            pl.BlockSpec((FB, C), lambda f, c, st: (f, c)),
            pl.BlockSpec((C,), lambda f, c, st: (c,)),
            pl.BlockSpec((C,), lambda f, c, st: (c,)),
            pl.BlockSpec((C,), lambda f, c, st: (c,)),
        ],
        # slot dimension squeezed: the kernel sees the same (FB, K1, 24)
        # block as _kernel/_range_kernel (a `.at[0]` view of a (1, FB, K1,
        # 24) block is a 24-wide minor-dimension slice Mosaic refuses)
        out_specs=pl.BlockSpec((None, FB, K1, 24),
                               lambda f, c, st: (slot_of(c, st), f, 0, 0)),
    )
    out = pl.pallas_call(
        functools.partial(_level_kernel, C=C, K1=K1, FB=FB, PACK=PACK,
                          SLOTS=slots),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((slots, FP, K1, 24), jnp.float32),
        interpret=interpret,
    )(starts, bT, g, h, m)
    return jax.vmap(lambda o: _epilogue(o, FP, K1, num_bins_padded))(out)


def _hist_level_xla(bT, g, h, m, slot_of_row, num_bins_padded: int,
                    slots: int):
    """Scatter reference of :func:`_hist_pallas_level` (CPU/tests): one
    scatter-add into (SLOTS, FP, B, 3) keyed by each row's slot."""
    FP, n = bT.shape
    vals = jnp.stack([g, h, m], -1).astype(jnp.bfloat16).astype(jnp.float32)
    hist = jnp.zeros((slots, FP, num_bins_padded, 3), jnp.float32)
    fidx = jnp.arange(FP, dtype=jnp.int32)[:, None]
    return hist.at[slot_of_row[None, :], fidx, bT.astype(jnp.int32), :].add(
        vals[None, :, :], mode="drop")


@functools.cache
@_eager_selftest
def _check_level_kernel(num_bins_padded: int, slots: int) -> None:
    """On-device check of the multi-leaf level kernel against the
    slot-keyed scatter, at the production chunk; raises KernelError."""
    C = default_chunk()
    caps = ([2, 1, 3] + [1] * max(slots - 3, 0))[:slots]
    bT, g, h, m, starts, slot_row = _level_check_inputs(
        2, num_bins_padded, caps, C)
    check_kernel(
        "_hist_pallas_level",
        dict(num_bins_padded=num_bins_padded, slots=slots, chunk=C,
             feature_block=FEATURE_BLOCK, rows=sum(caps) * C),
        lambda: _hist_pallas_level(bT, g, h, m, starts, num_bins_padded,
                                   slots),
        lambda: _hist_level_xla(bT, g, h, m, slot_row, num_bins_padded,
                                slots),
        rtol=1e-4, atol=1e-3)


def level_histograms(bT, g, h, m, start_chunks, slot_of_row,
                     num_bins_padded: int, slots: int):
    """(SLOTS, FP, B, 3) histograms of slot-partitioned rows in ONE pass:
    the multi-leaf Pallas kernel on TPU (chunk-aligned slots required;
    tail padding rows must carry zero g/h/m), the slot-keyed scatter on
    other backends or when SYNAPSEML_TPU_LEVEL=0 asks for it. A kernel
    that fails its check on TPU raises KernelError.

    CONTRACT (Pallas path; ADVICE r3): ``start_chunks`` must be strictly
    ascending with every slot owning >= 1 chunk of capacity — the kernel
    zero-initializes a slot's output block only when the grid reaches that
    slot's FIRST chunk, so a zero-capacity slot's block is never visited and
    returns uninitialized VMEM garbage. Callers must mask outputs by their
    own shard-uniform existence vector (grower_depthwise does: its
    ``cap_chunks`` floors every live slot at 1 and ``exists`` masks the
    gains). The XLA scatter has no such constraint."""
    if (jax.default_backend() == "tpu"
            and os.environ.get("SYNAPSEML_TPU_LEVEL", "1") != "0"):
        _check_level_kernel(num_bins_padded, slots)
        return _hist_pallas_level(bT, g, h, m, start_chunks,
                                  num_bins_padded, slots)
    return _hist_level_xla(bT, g, h, m, slot_of_row, num_bins_padded, slots)


def _hist_xla(bT, g, h, m, num_bins_padded: int):
    """Scatter-add reference with the same bf16 value rounding as the kernel."""
    FP, n = bT.shape
    vals = jnp.stack([g, h, m], -1).astype(jnp.bfloat16).astype(jnp.float32)
    hist = jnp.zeros((FP, num_bins_padded, 3), jnp.float32)
    fidx = jnp.arange(FP, dtype=jnp.int32)[:, None]
    return hist.at[fidx, bT.astype(jnp.int32), :].add(
        vals[None, :, :], mode="drop")


def _check_inputs(seed: int, num_bins_padded: int, n: int, fp: int = 8):
    """Per-feature random bins and distinct g/h/m channels, so cross-feature
    contamination or a channel swap fails the check. (The on-chip smoke and
    the chip test-suite draw their inputs here too.)"""
    import numpy as _np

    rng = _np.random.default_rng(seed)
    bT = jnp.asarray(rng.integers(0, num_bins_padded, size=(fp, n)),
                     jnp.int32)
    g = jnp.asarray(rng.normal(size=n).astype(_np.float32))
    h = jnp.asarray(rng.uniform(0.5, 2.0, size=n).astype(_np.float32))
    m = jnp.asarray((rng.uniform(size=n) > 0.25).astype(_np.float32))
    return bT, g * m, h * m, m


def _level_check_inputs(seed: int, num_bins_padded: int, caps, chunk: int,
                        fp: int = 8):
    """Slot-partitioned rows for the level kernel: slot i owns ``caps[i]``
    chunks and its last 37 rows are zero-valued tail padding. Returns
    (bT, g, h, m, start_chunks, slot_of_row)."""
    import numpy as _np

    bT, g, h, m = _check_inputs(seed, num_bins_padded, sum(caps) * chunk, fp)
    ends = _np.cumsum(caps)
    live = _np.ones(int(ends[-1]) * chunk, _np.float32)
    for e in ends:
        live[e * chunk - 37:e * chunk] = 0.0
    live = jnp.asarray(live)
    return (bT, g * live, h * live, m * live,
            jnp.asarray(ends - _np.asarray(caps), jnp.int32),
            jnp.asarray(_np.repeat(_np.arange(len(caps)),
                                   _np.asarray(caps) * chunk), jnp.int32))


@functools.cache
@_eager_selftest
def _check_hist_kernel(num_bins_padded: int) -> None:
    """One small on-device compile+run of the packed MXU kernel per bin
    width, at the PRODUCTION chunk and the requested bin width (which set
    K1/PACK — the lowering-relevant shapes); raises KernelError."""
    n = default_chunk()
    bT, g, h, m = _check_inputs(0, num_bins_padded, n)
    check_kernel(
        "_hist_pallas",
        dict(num_bins_padded=num_bins_padded, chunk=n,
             feature_block=FEATURE_BLOCK,
             pack=_pack_for(num_bins_padded // 8, FEATURE_BLOCK, None)),
        lambda: _hist_pallas(bT, g, h, m, num_bins_padded),
        lambda: _hist_xla(bT, g, h, m, num_bins_padded),
        rtol=1e-4, atol=1e-3)


@functools.cache
@_eager_selftest
def _check_range_kernel(num_bins_padded: int) -> None:
    """On-device check of the scalar-prefetch segmented kernel; raises
    KernelError."""
    import numpy as _np

    C = default_chunk()
    n = 4 * C
    bT, g, h, m = _check_inputs(1, num_bins_padded, n)
    # geometry satisfies the documented contract size >= length + chunk
    start, length, size = 1234, 2 * C - 57, 3 * C
    idx = _np.arange(n)
    sel = jnp.asarray(((idx >= start) & (idx < start + length)
                       ).astype(_np.float32))
    check_kernel(
        "_hist_pallas_range",
        dict(num_bins_padded=num_bins_padded, chunk=C, size=size, rows=n,
             feature_block=FEATURE_BLOCK),
        lambda: _hist_pallas_range(bT, g, h, m, start, length,
                                   num_bins_padded, size),
        lambda: _hist_xla(bT, g * sel, h * sel, m * sel, num_bins_padded),
        rtol=1e-4, atol=1e-3)


def segmented_histograms_available(num_bins_padded: int) -> bool:
    """Trace-time choice for the grower: the segmented kernel on the TPU
    backend unless SYNAPSEML_TPU_SEGMENTED=0 asks for the sliced path. A
    kernel that fails its check raises KernelError."""
    if jax.default_backend() != "tpu":
        return False
    if os.environ.get("SYNAPSEML_TPU_SEGMENTED", "1") == "0":
        return False
    _check_range_kernel(num_bins_padded)
    return True


def range_histogram(bT, g, h, m, start, length, num_bins_padded: int,
                    size: int):
    """Public segmented entry: histogram of rows [start, start+length) of
    the FULL arrays over a chunk-aligned static window of ``size`` rows —
    no dynamic_slice copy, no pre-kernel mask multiply (callers must have
    checked :func:`segmented_histograms_available`)."""
    return _hist_pallas_range(bT, g, h, m, start, length, num_bins_padded,
                              size)


def child_histogram(bT, g, h, m, num_bins_padded: int):
    """(FP, size) i32 bins + per-row grad/hess/weight-mask →
    (FP, num_bins_padded, 3) f32 histogram of [sum_grad, sum_hess, sum_mask].

    Rows with m == 0 (outside the leaf range / bagged out / padding) contribute
    nothing PROVIDED g and h are also zeroed for those rows (callers mask all
    three). The Pallas MXU kernel on TPU (KernelError if it fails its
    check), XLA scatter on other backends.
    """
    if jax.default_backend() == "tpu":
        _check_hist_kernel(num_bins_padded)
        return _hist_pallas(bT, g, h, m, num_bins_padded)
    return _hist_xla(bT, g, h, m, num_bins_padded)
