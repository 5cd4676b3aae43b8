"""Ring attention — sequence/context parallelism over the ``seq`` mesh axis.

The reference has NO long-context story (SURVEY.md §5.7: text DL truncates at
max_token_len=128); this framework makes sequence parallelism first-class so
the DL layer scales context length with chips. Design per Liu et al.
(Ring Attention with Blockwise Transformers) + the blockwise-parallel
formulation: Q stays resident per device; K/V blocks rotate around the ring
(``ppermute`` over ICI) while each device accumulates its queries' attention
with a numerically-stable online softmax (running max ``m``, normalizer ``l``,
unnormalized output ``o``). Compute for step t overlaps the collective for
step t+1 — XLA schedules the ppermute asynchronously on TPU.

Shapes follow flax convention: [batch, seq, heads, head_dim]; the seq axis is
sharded over the mesh's ``seq`` axis. Causal masking uses global positions
derived from each block's ring offset, so device boundaries are invisible to
the math.
"""

from __future__ import annotations

import sys
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..core.compat import shard_map
from .mesh import SEQ_AXIS


def _witness_observe(site, tree, expect=None):
    # dtype-witness probe (testing/dtypewitness.py): inert unless the
    # witness module is loaded — sys.modules lookup keeps product imports
    # free of the testing package
    w = sys.modules.get("synapseml_tpu.testing.dtypewitness")
    if w is not None and w.active():
        w.observe(site, tree, expect)


def _block_attention(q, k, v, m, l, o, q_offset, k_offset, causal, scale,
                     kv_len=None):
    """One blockwise online-softmax update.

    q: [B, Sq, H, D]; k/v: [B, Sk, H, D]; m,l: [B, H, Sq]; o: [B, Sq, H, D].
    Offsets are the blocks' global sequence starts (for causal masking).
    ``kv_len`` masks keys at global positions >= kv_len — the padded tail
    when a non-divisible sequence was padded up to the shard grid.
    """
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale  # [B, H, Sq, Sk]
    mask = None
    if causal:
        q_pos = q_offset + jnp.arange(q.shape[1])
        k_pos = k_offset + jnp.arange(k.shape[1])
        mask = q_pos[:, None] >= k_pos[None, :]
    if kv_len is not None:
        valid = ((k_offset + jnp.arange(k.shape[1])) < kv_len)[None, :]
        mask = valid if mask is None else mask & valid
    if mask is not None:
        s = jnp.where(mask[None, None], s, -jnp.inf)
    m_new = jnp.maximum(m, s.max(axis=-1))          # [B, H, Sq]
    # guard fully-masked rows (m_new = -inf): exp(-inf - -inf) -> use 0
    safe_m = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    p = jnp.exp(jnp.where(jnp.isfinite(s), s - safe_m[..., None], -jnp.inf))
    p = jnp.where(jnp.isnan(p), 0.0, p)
    correction = jnp.where(jnp.isfinite(m), jnp.exp(m - safe_m), 0.0)
    l_new = l * correction + p.sum(axis=-1)
    o_new = (o * correction.transpose(0, 2, 1)[..., None]
             + jnp.einsum("bhqk,bkhd->bqhd", p, v))
    return m_new, l_new, o_new


def _finalize(m, l, o):
    denom = jnp.where(l > 0, l, 1.0).transpose(0, 2, 1)[..., None]
    return o / denom


def attention_reference(q, k, v, causal: bool = False,
                        scale: Optional[float] = None,
                        kv_len: Optional[int] = None) -> jnp.ndarray:
    """Plain single-device attention (the correctness oracle for the ring).

    ``kv_len`` masks key positions >= kv_len (padding introduced when a
    non-divisible sequence was padded to the shard grid); rows of padded
    queries still normalize over the real keys, and the caller slices them
    off after unpadding.
    """
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    n_q, n_k = q.shape[1], k.shape[1]
    mask = None
    if causal:
        mask = jnp.arange(n_q)[:, None] >= jnp.arange(n_k)[None, :]
    if kv_len is not None:
        valid = (jnp.arange(n_k) < kv_len)[None, :]
        mask = valid if mask is None else mask & valid
    if mask is not None:
        s = jnp.where(mask[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def ring_self_attention(q, k, v, mesh: Mesh, causal: bool = False,
                        scale: Optional[float] = None,
                        axis: str = SEQ_AXIS,
                        use_flash: Optional[bool] = None,
                        flash_interpret: bool = False,
                        kv_len: Optional[int] = None) -> jnp.ndarray:
    """Exact self-attention with q/k/v sharded on ``axis`` over ``mesh``.

    Each of the R ring ranks holds S/R of the sequence; the result equals
    :func:`attention_reference` on the gathered sequence, bit-for-near-bit
    (online softmax is associative). Peak memory per device is O(S/R · S/R)
    per step instead of O(S²).

    ``use_flash`` runs each rank's per-step block update as the FUSED
    Pallas kernel (ops/attention_kernel.flash_attention_block — scores,
    masking, online-softmax rescale, and PV matmul in one VMEM program)
    instead of the XLA ops below. None = by backend: the kernel on TPU
    (KernelError if it fails its on-device check), the XLA ops elsewhere —
    both compute the identical update (equality-tested in
    tests/test_attention_kernel.py).
    """
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    if use_flash is None:
        use_flash = jax.default_backend() == "tpu"
    if kv_len is not None:
        # padded (non-divisible) sequences need the global key-validity mask,
        # which the fused block kernel does not plumb — XLA path only
        use_flash = False
    if use_flash:
        from ..ops.attention_kernel import (_check_flash_block_kernel,
                                            flash_attention_block)

        if not flash_interpret:
            _check_flash_block_kernel()
    ring = mesh.shape[axis]
    # batch rides the data axis when the mesh has one (dp × sp composition) —
    # each data-rank computes only its batch shard
    from .mesh import DATA_AXIS

    batch_axis = DATA_AXIS if (DATA_AXIS in mesh.shape and DATA_AXIS != axis
                               and q.shape[0] % mesh.shape[DATA_AXIS] == 0) \
        else None
    spec = P(batch_axis, axis, None, None)

    @partial(shard_map, mesh=mesh, in_specs=(spec,) * 3,
             out_specs=spec, check_vma=False)
    def _ring(q_blk, k_blk, v_blk):
        rank = jax.lax.axis_index(axis)
        s_local = q_blk.shape[1]
        q_offset = rank * s_local
        m0 = jnp.full(q_blk.shape[:1] + (q_blk.shape[2], s_local), -jnp.inf,
                      dtype=jnp.float32)
        l0 = jnp.zeros_like(m0)
        o0 = jnp.zeros(q_blk.shape, dtype=jnp.float32)
        perm = [(i, (i + 1) % ring) for i in range(ring)]

        def step(t, carry):
            k_cur, v_cur, m, l, o = carry
            # block currently held arrived from rank (rank - t) mod ring
            k_offset = ((rank - t) % ring) * s_local
            if use_flash:
                m, l, o = flash_attention_block(
                    q_blk.astype(jnp.float32), k_cur.astype(jnp.float32),
                    v_cur.astype(jnp.float32), m, l, o, q_offset, k_offset,
                    causal=causal, scale=scale,
                    interpret=flash_interpret)
            else:
                m, l, o = _block_attention(
                    q_blk.astype(jnp.float32), k_cur.astype(jnp.float32),
                    v_cur.astype(jnp.float32), m, l, o, q_offset, k_offset,
                    causal, scale, kv_len=kv_len)
            # rotate K/V to the next rank (overlaps next step's compute)
            k_nxt = jax.lax.ppermute(k_cur, axis, perm)
            v_nxt = jax.lax.ppermute(v_cur, axis, perm)
            return k_nxt, v_nxt, m, l, o

        _, _, m, l, o = jax.lax.fori_loop(
            0, ring, step, (k_blk, v_blk, m0, l0, o0))
        # contract: the softmax accumulators stay f32 regardless of the
        # (possibly bf16) q/k/v wire dtype; output returns at q's dtype
        _witness_observe("dl.seq.ring_acc", (m, l, o), expect="float32")
        out = _finalize(m, l, o).astype(q_blk.dtype)
        _witness_observe("dl.seq.ring_out", out)
        return out

    return _ring(q, k, v)


def blockwise_attention(q, k, v, block_size: int, causal: bool = False,
                        scale: Optional[float] = None) -> jnp.ndarray:
    """Single-device blockwise attention (the memory-efficient kernel the ring
    wraps): K/V consumed in ``block_size`` chunks with the same online
    softmax — O(S·block) memory instead of O(S²). Used for long sequences on
    one chip; the remat-style scan keeps XLA from materializing the full
    score matrix."""
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    n_k = k.shape[1]
    if n_k % block_size:
        raise ValueError(f"sequence {n_k} not divisible by block {block_size}")
    n_blocks = n_k // block_size
    kb = k.reshape(k.shape[0], n_blocks, block_size, *k.shape[2:])
    vb = v.reshape(v.shape[0], n_blocks, block_size, *v.shape[2:])

    m0 = jnp.full((q.shape[0], q.shape[2], q.shape[1]), -jnp.inf, jnp.float32)
    l0 = jnp.zeros_like(m0)
    o0 = jnp.zeros(q.shape, jnp.float32)

    def step(carry, blk):
        m, l, o = carry
        t, k_cur, v_cur = blk
        m, l, o = _block_attention(q.astype(jnp.float32),
                                   k_cur.astype(jnp.float32),
                                   v_cur.astype(jnp.float32),
                                   m, l, o, 0, t * block_size, causal, scale)
        return (m, l, o), None

    (m, l, o), _ = jax.lax.scan(
        step, (m0, l0, o0),
        (jnp.arange(n_blocks), kb.transpose(1, 0, 2, 3, 4),
         vb.transpose(1, 0, 2, 3, 4)))
    _witness_observe("dl.seq.block_acc", (m, l, o), expect="float32")
    out = _finalize(m, l, o).astype(q.dtype)
    _witness_observe("dl.seq.block_out", out)
    return out
