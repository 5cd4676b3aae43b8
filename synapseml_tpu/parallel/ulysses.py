"""Ulysses (DeepSpeed-style) sequence parallelism — all-to-all head scatter.

The second first-class long-context strategy next to ring attention
(parallel/ring_attention.py). Where ring attention keeps heads whole and
rotates K/V blocks around the ICI ring, Ulysses re-shards between the two
natural layouts with a single ``all_to_all`` each way:

    sequence-sharded [B, S/p, H,  D]   (how transformer blocks hold tokens)
      → head-sharded [B, S,   H/p, D]  (full sequence per device → EXACT
                                        attention, no online softmax)
      → back to sequence-sharded for the MLP that follows.

Comm volume per layer is 2 all-to-alls of the activation (vs ring's p-1
ppermutes of K/V); Ulysses wins when heads >= devices and the attention
kernel benefits from seeing the whole sequence (e.g. one flash/blockwise call
on the MXU), ring wins when S/p is still long or heads < devices. Both ride
ICI over the same ``seq`` mesh axis so they are interchangeable in a model.

The reference has NO sequence parallelism at all (SURVEY.md §5.7); this is
parity-plus, designed in from the start per the distributed-first mandate.
"""

from __future__ import annotations

from typing import Optional

import jax
from jax.sharding import Mesh, PartitionSpec as P

from ..core.compat import shard_map
from .mesh import DATA_AXIS, SEQ_AXIS
from .ring_attention import attention_reference


def ulysses_self_attention(q, k, v, mesh: Mesh, causal: bool = False,
                           scale=None, use_flash: Optional[bool] = None,
                           flash_interpret: bool = False,
                           kv_len: Optional[int] = None):
    """Self-attention over sequence-sharded inputs via all-to-all re-sharding.

    q/k/v: [B, S, H, D] GLOBAL shapes, sharded [data, seq, None, None] on
    ``mesh``. The number of heads H must be divisible by the seq-axis size.
    Returns the attention output with the same sharding as the inputs.

    ``use_flash`` runs the per-device full-sequence attention through the
    fused Pallas kernel (ops/attention_kernel.flash_attention) instead of
    the lax-composed reference. None = by backend: the kernel on TPU
    (checked on-device inside ``flash_attention``; KernelError if it fails),
    the reference elsewhere. ``kv_len`` masks padded key positions when a
    non-divisible sequence was padded to the shard grid (forces the
    reference path, which plumbs the mask).
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    sp = mesh.shape[SEQ_AXIS]
    if q.shape[2] % sp:
        raise ValueError(f"heads ({q.shape[2]}) must divide by the seq-axis "
                         f"size ({sp}) for Ulysses attention")
    if use_flash is None:
        use_flash = jax.default_backend() == "tpu"
    if kv_len is not None:
        use_flash = False
    if use_flash:
        from ..ops.attention_kernel import flash_attention

    def _ulysses(q_blk, k_blk, v_blk):
        # per-device blocks: [B_l, S/p, H, D]
        def seq_to_heads(x):
            # scatter heads, gather sequence: [B, S/p, H, D] -> [B, S, H/p, D]
            x = jax.lax.all_to_all(x, SEQ_AXIS, split_axis=2, concat_axis=1,
                                   tiled=True)
            return x

        def heads_to_seq(x):
            # inverse all-to-all: [B, S, H/p, D] -> [B, S/p, H, D]
            return jax.lax.all_to_all(x, SEQ_AXIS, split_axis=1,
                                      concat_axis=2, tiled=True)

        qh, kh, vh = seq_to_heads(q_blk), seq_to_heads(k_blk), seq_to_heads(v_blk)
        # full sequence per device -> exact attention: one fused flash call
        # on the MXU when available, the lax-composed oracle otherwise
        if use_flash:
            out = flash_attention(qh, kh, vh, causal=causal, scale=scale,
                                  interpret=flash_interpret)
        else:
            out = attention_reference(qh, kh, vh, causal=causal, scale=scale,
                                      kv_len=kv_len)
        return heads_to_seq(out)

    batch_axis = (DATA_AXIS if DATA_AXIS in mesh.shape
                  and q.shape[0] % mesh.shape[DATA_AXIS] == 0 else None)
    spec = P(batch_axis, SEQ_AXIS, None, None)
    fn = shard_map(_ulysses, mesh=mesh, in_specs=(spec, spec, spec),
                   out_specs=spec, check_vma=False)
    return fn(q, k, v)
