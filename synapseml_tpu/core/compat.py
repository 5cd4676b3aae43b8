"""Small shims over the jax API surface the framework shares.

``shard_map`` is ``jax.shard_map`` (``check_vma`` spelling); the alias keeps
one import site for the distributed path (collectives, ring attention, VW
sync passes, GBDT voting).
"""

from __future__ import annotations

import jax

def donate_argnums_if_supported(*argnums):
    """``donate_argnums`` to pass to ``jax.jit``, or ``()`` on CPU.

    Buffer donation is a silent no-op on CPU: jax logs a warning per call
    and keeps both buffers, which buries real warnings in CI logs and
    makes the donation path untested. Gating through this helper turns
    donation off where it cannot work and keeps the aliasing behaviour
    identical on TPU/GPU. Call it lazily (inside a cached jit factory,
    like ``BucketedRunner``) — at module import it would force backend
    initialisation.
    """
    if jax.default_backend() in ("cpu",):
        return ()
    return tuple(argnums)


shard_map = jax.shard_map
