"""Pallas TPU stable-partition kernel — how a split moves a leaf's rows.

The grower keeps rows physically sorted by leaf (gbdt/grower.py); a split
stably partitions the leaf's bucket window of five arrays — the (FP, S) binned
block and the position, gradient, hessian and bag-mask vectors. XLA's way is
``argsort`` plus five gathers, and a TPU has no fast gather. A stable two-way
partition needs neither: every row's destination is a prefix count.

The kernel streams the window once, ``C`` rows a grid step. Rows of the first
class (before the leaf's range, or going left) go to the *left stream*, which
fills the output from position 0; rows of the second class (going right, or
past the range) go to the *right stream*, which fills it from the number of
first-class rows on. A chunk's rows are placed **by a product with a one-hot
matrix on the MXU**::

    dst[r]  = (stream offset mod C) + rank of r within its class and chunk
    PT[d, r] = 1{dst[r] == d}                       (4C, C)  bf16
    acc     += payload @ PT.T                       (PL, 4C) f32

``acc`` holds a two-tile window (tile = C positions) of each stream: a chunk
adds at most C rows to a stream, so it touches at most two tiles; when a
stream has passed a tile, the tile is written out and the window moves on.
The tile where the left stream ends and the right one begins is the sum of
the two partial tiles and is written last. The products are exact: every
payload row is fed as integers under 256, which bfloat16 holds exactly (bins
as they are, or as two byte planes where ``B > 256``; the four vectors
bit-cast to int32 as four byte planes each), each column of ``PT`` has one 1,
accumulation is float32, and the bytes are reassembled by shifts. No sort, no
gather, no lane-unaligned store; the result is bit-identical to the
``argsort`` path, which is this kernel's reference (:func:`partition_window_xla`)
and what every backend but the TPU runs.

A wide table is taken ``FEATURE_BLOCK`` features at a time (the outer grid
axis): the destinations depend on the class row alone, so every block places
its own rows of the bins by a ``PT`` it builds anew, and the first one
writes the four vectors. The kernel's VMEM is that of one block whatever the
width.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from .hist_kernel import (_check_inputs, _eager_selftest, check_kernel,
                          default_chunk)

PARTITION_CHUNK = 512   # rows per grid step (and positions per output tile)
FEATURE_BLOCK = 128     # most features per grid step: bounds the kernel's VMEM
VEC_ROWS = 8            # the vectors' words, stacked: pos, g, h, m, class
CLASS_ROW = 4


def partition_chunk(chunk: int) -> int:
    """Kernel rows per grid step: the largest divisor of the grower's row
    chunk that is at most PARTITION_CHUNK (windows are whole row chunks)."""
    c = min(PARTITION_CHUNK, chunk)
    while chunk % c:
        c -= 1
    return c


def feature_block(fp: int) -> int:
    """Features per grid step: ``fp`` (a multiple of 8) in the fewest equal
    blocks of at most FEATURE_BLOCK; the last may be short."""
    blocks = -(-fp // FEATURE_BLOCK)
    return -(-fp // (8 * blocks)) * 8


def _kernel(pref_ref, bin_ref, vec_ref, outb_ref, outv_ref, acc_ref, seam_ref,
            stb_ref, stv_ref, sem, *, C: int, FB: int, FP: int, NB: int,
            n: int):
    """Grid (feature blocks, window chunks), sequential: a block streams the
    whole window before the next begins. ``pref`` (n + 2,) i32 by scalar
    prefetch: the left stream's offset before every chunk, the number of
    first-class rows (its offset after the last), and the window's first
    chunk in the full arrays. bins (FB, C) i32 of the full block; vec
    (VEC_ROWS, C) i32 of the window's stacked words: pos, g, h, m and the
    class (1 = second). Outputs stay in HBM and are written a tile at a
    time by DMA: the block's rows of the bins, and with the first block the
    words."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    T = C
    PB = NB * FB                    # payload rows of the bins' byte planes
    nfb = -(-FP // FB)
    j, c = pl.program_id(0), pl.program_id(1)

    @pl.when(c == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        seam_ref[...] = jnp.zeros_like(seam_ref)

    oL, eL, nF = pref_ref[c], pref_ref[c + 1], pref_ref[n]
    oR = nF + c * C - oL
    eR = oR + C - (eL - oL)
    bL, bR, seam_t = oL // T, oR // T, nF // T

    # rank of every row within its class: one product with a triangle
    words = vec_ref[...]
    second = words[CLASS_ROW:CLASS_ROW + 1]                      # (1, C)
    tri = (lax.broadcasted_iota(jnp.int32, (C, C), 0)
           < lax.broadcasted_iota(jnp.int32, (C, C), 1)).astype(jnp.bfloat16)
    r1 = jnp.dot(jnp.broadcast_to(second, (8, C)).astype(jnp.float32)
                 .astype(jnp.bfloat16), tri,
                 preferred_element_type=jnp.float32)[0:1].astype(jnp.int32)
    lane = lax.broadcasted_iota(jnp.int32, (1, C), 1)
    dst = jnp.where(second == 1, 2 * T + (oR - bR * T) + r1,
                    (oL - bL * T) + lane - r1)
    PT = (lax.broadcasted_iota(jnp.int32, (4 * T, C), 0)
          == dst).astype(jnp.bfloat16)

    # payload: the bins' planes, then the four byte planes of the words
    # (eight rows a plane, so every slice of it is sublane-aligned)
    bins = bin_ref[...]
    planes = [bins] if NB == 1 else [bins & 255, bins >> 8]
    planes += [(words >> (8 * b)) & 255 for b in range(4)]
    payload = jnp.concatenate(planes, axis=0).astype(
        jnp.float32).astype(jnp.bfloat16)
    acc_ref[...] += lax.dot_general(payload, PT, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)

    def flush(tile, t):
        """Bytes back to words, and tile ``t`` of the window written out."""
        ti = tile.astype(jnp.int32)
        stb_ref[...] = (ti[0:FB] if NB == 1
                        else ti[0:FB] | (ti[FB:2 * FB] << 8))
        off = pl.multiple_of(t * T, T)
        row = pl.multiple_of(j * FB, 8)

        def put(rows, with_words):
            copies = [pltpu.make_async_copy(
                stb_ref.at[pl.ds(0, rows)],
                outb_ref.at[pl.ds(row, rows), pl.ds(off, T)], sem.at[0])]
            if with_words:
                v = [ti[PB + VEC_ROWS * b:PB + VEC_ROWS * (b + 1)]
                     for b in range(4)]
                stv_ref[...] = v[0] | (v[1] << 8) | (v[2] << 16) | (v[3] << 24)
                copies.append(pltpu.make_async_copy(
                    stv_ref, outv_ref.at[:, pl.ds(off, T)], sem.at[1]))
            for cp in copies:
                cp.start()
            for cp in copies:
                cp.wait()

        if nfb == 1:
            put(FP, True)
        else:                       # a DMA's size is static: one a case
            pl.when(j == 0)(lambda: put(FB, True))
            pl.when((j > 0) & (j < nfb - 1))(lambda: put(FB, False))
            pl.when(j == nfb - 1)(lambda: put(FP - (nfb - 1) * FB, False))

    def advance(lo):
        acc_ref[:, lo:lo + T] = acc_ref[:, lo + T:lo + 2 * T]
        acc_ref[:, lo + T:lo + 2 * T] = jnp.zeros((acc_ref.shape[0], T),
                                                  jnp.float32)

    @pl.when(eL // T > bL)
    def _():
        flush(acc_ref[:, 0:T], bL)
        advance(0)

    passed_r = eR // T > bR

    @pl.when(passed_r & (bR == seam_t))
    def _():                        # shared with the left stream: kept
        seam_ref[...] = acc_ref[:, 2 * T:3 * T]

    @pl.when(passed_r & (bR != seam_t))
    def _():
        flush(acc_ref[:, 2 * T:3 * T], bR)

    @pl.when(passed_r)
    def _():
        advance(2 * T)

    @pl.when((c == n - 1) & (nF < n * C))
    def _():
        flush(acc_ref[:, 0:T] + seam_ref[...], seam_t)


@functools.partial(jax.jit, static_argnames=("num_bins_padded", "chunk",
                                             "interpret"))
def _partition_pallas(second, first_row, bT, pos, g, h, m,
                      num_bins_padded: int, chunk: int,
                      interpret: bool = False):
    """Stable partition of the window ``[first_row, first_row + S)`` of the
    full arrays by ``second`` ((S,) bool; False rows first). ``first_row``
    and S are multiples of ``chunk``. Returns the window's (FP, S) i32 bins
    and (VEC_ROWS, S) i32 words of pos, g, h, m (rows 0-3)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    FP, Np = bT.shape
    S = second.shape[0]
    C = chunk
    assert S % C == 0 and Np % C == 0 and FP % 8 == 0
    n = S // C
    NB = 1 if num_bins_padded <= 256 else 2      # byte planes a bin
    FB = feature_block(FP)
    PL = NB * FB + 4 * VEC_ROWS
    key = second.astype(jnp.int32)
    cnt_first = C - key.reshape(n, C).sum(axis=1)
    word = lambda a: lax.bitcast_convert_type(
        lax.dynamic_slice(a, (first_row,), (S,)), jnp.int32)
    words = jnp.zeros((VEC_ROWS, S), jnp.int32).at[:CLASS_ROW + 1].set(
        jnp.stack([word(pos), word(g), word(h), word(m), key]))
    pref = jnp.concatenate([
        jnp.zeros(1, jnp.int32), jnp.cumsum(cnt_first, dtype=jnp.int32),
        (jnp.asarray(first_row, jnp.int32) // C)[None]])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(-(-FP // FB), n),
        in_specs=[
            pl.BlockSpec((FB, C), lambda j, c, p: (j, p[n + 1] + c)),
            pl.BlockSpec((VEC_ROWS, C), lambda j, c, p: (0, c)),
        ],
        out_specs=[pl.BlockSpec(memory_space=pl.ANY),
                   pl.BlockSpec(memory_space=pl.ANY)],
        scratch_shapes=[
            pltpu.VMEM((PL, 4 * C), jnp.float32),
            pltpu.VMEM((PL, C), jnp.float32),
            pltpu.VMEM((FB, C), jnp.int32),
            pltpu.VMEM((VEC_ROWS, C), jnp.int32),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    return pl.pallas_call(
        functools.partial(_kernel, C=C, FB=FB, FP=FP, NB=NB, n=n),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((FP, S), jnp.int32),
                   jax.ShapeDtypeStruct((VEC_ROWS, S), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="stable_partition_rows",
    )(pref, bT, words)


def partition_window_xla(second, first_row, bT, pos, g, h, m):
    """The kernel's reference, and the grower's own path off the chip: a
    stable ``argsort`` of the class and five gathers."""
    FP, _ = bT.shape
    S = second.shape[0]
    src = jnp.argsort(second, stable=True).astype(jnp.int32)
    win = lambda a: lax.dynamic_slice(a, (first_row,), (S,))[src]
    blk = lax.dynamic_slice(bT, (0, first_row), (FP, S))[:, src]
    return win(pos), win(g), win(h), win(m), blk


def partition_window(second, first_row, bT, pos, g, h, m,
                     num_bins_padded: int, chunk: int,
                     interpret: bool = False):
    """(pos, g, h, m, bins) of the window, stably partitioned by ``second``,
    through the kernel. It compiles for the TPU or raises; ``interpret`` is
    for the tests and the smoke's rehearsal off the chip, whose callers
    otherwise take :func:`partition_window_xla`."""
    outb, outv = _partition_pallas(
        second, first_row, bT, pos, g, h, m, num_bins_padded,
        partition_chunk(chunk), interpret=interpret)
    f32 = lambda r: lax.bitcast_convert_type(outv[r], jnp.float32)
    return outv[0], f32(1), f32(2), f32(3), outb


def _partition_check_inputs(seed: int, num_bins_padded: int, n: int, fp: int,
                            first_row: int, window: int, start: int,
                            length: int):
    """Arguments of :func:`partition_window` (less the two static ones) for
    a table of ``n`` rows: positions above 2^24, a bag mask with zeros, and
    about half of the rows of [start, start+length) going right. (The
    on-chip smoke and the chip test-suite draw their inputs here too.)"""
    import numpy as _np

    bT, g, h, m = _check_inputs(seed, num_bins_padded, n, fp)
    rng = _np.random.default_rng(seed)
    pos = jnp.asarray(rng.permutation(n).astype(_np.int32) + (1 << 24))
    idx = first_row + _np.arange(window)
    second = jnp.asarray((idx >= start + length) | (
        (idx >= start) & (rng.uniform(size=window) < 0.5)))
    return second, first_row, bT, pos, g, h, m


@functools.cache
@_eager_selftest
def _check_partition_kernel(num_bins_padded: int, fp: int) -> None:
    """On-device check of the kernel against argsort + gathers, bit for bit,
    at the production chunk: a window inside a longer table, a range
    unaligned at both ends; raises KernelError."""
    chunk = default_chunk()
    args = _partition_check_inputs(3, num_bins_padded, 6 * chunk, fp, chunk,
                                   4 * chunk, chunk + 777, 3 * chunk - 1001)
    bits = lambda out: [lax.bitcast_convert_type(x, jnp.int32) for x in out]
    check_kernel(
        "stable_partition_rows",
        dict(num_bins_padded=num_bins_padded, features_padded=fp,
             chunk=partition_chunk(chunk), window=4 * chunk,
             rows=6 * chunk),
        lambda: bits(partition_window(*args, num_bins_padded, chunk)),
        lambda: bits(partition_window_xla(*args)),
        rtol=0.0, atol=0.0)


def partition_kernel_available(num_bins_padded: int, fp: int) -> bool:
    """Trace-time choice for the grower: the kernel on the TPU backend, the
    XLA path elsewhere. A kernel that fails its check raises KernelError."""
    if jax.default_backend() != "tpu":
        return False
    _check_partition_kernel(num_bins_padded, fp)
    return True
