"""Shape-bucketed, async-dispatch inference runtime (BucketedRunner).

Every inference surface in the repo — the serving micro-batcher
(io/serving.py), the distributed serving workers (io/distributed_serving.py),
ONNX batch inference (onnx/model.py) and GBDT predict/serving
(gbdt/boosting.py) — ultimately feeds variable-length micro-batches into a
jitted XLA program. On XLA hardware every distinct batch size is a fresh
compile, and with request-driven batch formation the observed sizes are
essentially arbitrary: a serving process quietly pays a multi-second compile
for batch size 17, then again for 18, then 23... while the profile shows
nothing but `jit_` compilations. Padded/misaligned shapes are a first-class
cost on TPUs (arXiv:2008.01040), and padding up to a small ladder of static
shapes is the standard fix.

:class:`BucketedRunner` wraps one callable with:

* **Bucket ladder** — batch dimension padded up to a geometric ladder of
  bucket sizes (1, 2, 4, ... ``max_batch_size`` by default), so the program
  compiles once per *bucket* instead of once per observed size. Batches
  larger than ``max_batch_size`` are chunked into full max-size buckets plus
  one bucketed tail. Padding repeats the last real row (a vectorized gather,
  never ``np.repeat`` row duplication), and outputs are sliced back to the
  real row count so padded rows can never leak into replies.
* **AOT warmup** — :meth:`warmup` compiles every bucket ahead of time
  (``jax.jit(...).lower(...).compile()`` on ShapeDtypeStructs — no example
  batch is executed) through :func:`core.compile_cache.enable_compile_cache`
  so the XLA executables persist across processes. After warmup the
  steady-state compile count is **zero** — asserted by the CI serving perf
  guard via the runner's counters.
* **Async dispatch** — :meth:`dispatch` launches the device computation for
  every chunk without blocking (jax's async dispatch) and returns a
  :class:`PendingBatch`; the host only synchronizes in
  :meth:`PendingBatch.result`, i.e. when replies are written. Input buffers
  are donated to XLA on backends that support donation (TPU/GPU), so the
  padded staging buffer is reused as the output allocation.
* **Counters** — per-bucket compile and hit counts (:meth:`stats`), the
  observability contract the serving bench and CI guard read.

The runner is deliberately framework-free: it takes any
``fn(*batch_leading_arrays) -> array | tuple`` and returns numpy. See
docs/serving-perf.md for the serving integration and tuning guidance.
"""

from __future__ import annotations

import sys
import threading
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

__all__ = ["BucketedRunner", "PendingBatch", "bucket_ladder"]


def _witness_observe(site, tree, expect=None):
    # dtype-witness probe (testing/dtypewitness.py): inert unless the
    # witness module is loaded — sys.modules lookup keeps product imports
    # free of the testing package
    w = sys.modules.get("synapseml_tpu.testing.dtypewitness")
    if w is not None and w.active():
        w.observe(site, tree, expect)


def bucket_ladder(max_batch_size: int, growth: float = 2.0,
                  min_bucket: int = 1) -> Tuple[int, ...]:
    """Geometric ladder of batch buckets: ``min_bucket`` multiplied by
    ``growth`` (rounded up, strictly increasing) until ``max_batch_size``,
    which is always the last rung."""
    if max_batch_size < 1:
        raise ValueError(f"max_batch_size must be >= 1, got {max_batch_size}")
    if not 1 <= min_bucket <= max_batch_size:
        raise ValueError(f"min_bucket must be in [1, {max_batch_size}], "
                         f"got {min_bucket}")
    if growth <= 1.0:
        raise ValueError(f"growth must be > 1.0, got {growth}")
    ladder: List[int] = []
    b = float(min_bucket)
    while b < max_batch_size:
        nxt = int(b) if b == int(b) else int(b) + 1
        if not ladder or nxt > ladder[-1]:
            ladder.append(nxt)
        b *= growth
    if not ladder or ladder[-1] != max_batch_size:
        ladder.append(max_batch_size)
    return tuple(ladder)


def _pad_to(arr: np.ndarray, bucket: int) -> np.ndarray:
    """Pad the leading dim up to ``bucket`` by repeating the last real row —
    one vectorized gather into a FRESH buffer (safe to donate; repeated rows
    keep the padded lanes numerically benign, e.g. no log(0) NaNs)."""
    n = arr.shape[0]
    if n == bucket:
        # fresh copy so donation can never invalidate a caller-owned buffer
        return np.ascontiguousarray(arr)
    idx = np.minimum(np.arange(bucket), n - 1)
    return arr[idx]


class PendingBatch:
    """Handle for dispatched-but-unsynchronized work. The device computation
    for every chunk is already in flight; :meth:`result` is the single host
    sync point (where serving writes replies)."""

    def __init__(self, chunks: List[Tuple[Any, int, int]], treedef,
                 single: bool, n_total: int):
        # chunks: (output leaves, real_rows, bucket) per dispatched chunk
        self._chunks = chunks
        self._treedef = treedef
        self._single = single
        self.num_rows = n_total

    def block_until_ready(self) -> "PendingBatch":
        import jax

        for leaves, _, _ in self._chunks:
            for leaf in leaves:
                jax.block_until_ready(leaf)
        return self

    def result(self):
        """Materialize to numpy, sliced to the real row count (padded rows
        never leak). Blocks until the device work completes."""
        per_leaf: List[List[np.ndarray]] = None
        for leaves, real, bucket in self._chunks:
            if per_leaf is None:
                per_leaf = [[] for _ in leaves]
            for slot, leaf in zip(per_leaf, leaves):
                host = np.asarray(leaf)
                if host.ndim and host.shape[0] == bucket:
                    host = host[:real]
                elif len(self._chunks) > 1:
                    raise ValueError(
                        "BucketedRunner: output leaf has no leading batch "
                        f"dimension (shape {host.shape}) but the input was "
                        "chunked; results cannot be concatenated")
                slot.append(host)
        outs = [parts[0] if len(parts) == 1 else np.concatenate(parts, axis=0)
                for parts in per_leaf]
        if self._single:
            return outs[0]
        import jax

        return jax.tree_util.tree_unflatten(self._treedef, outs)


class BucketedRunner:
    """Shared bucketing + AOT-warmup + async-dispatch execution layer.

    ``fn`` is any callable over one or more batch-leading arrays (all
    sharing the same leading dimension) returning an array or a tuple/list
    of arrays. Do NOT pre-wrap ``fn`` in ``jax.jit`` — the runner owns the
    jit boundary (it compiles one executable per bucket).

    ``donate=None`` (auto) donates input buffers on TPU/GPU backends and
    skips donation on CPU, where XLA does not implement it (avoiding a
    warning per compile).
    """

    def __init__(self, fn: Callable, max_batch_size: int = 64,
                 growth: Optional[float] = None, min_bucket: int = 1,
                 donate: Optional[bool] = None, pass_mask: bool = False,
                 name: Optional[str] = None):
        self.fn = fn
        self.max_batch_size = int(max_batch_size)
        # ladder geometry: an explicit growth bypasses auto-configuration;
        # None asks core/perfmodel, whose recorded ladder A/Bs can move the
        # factor off 2.0 only for a confidently matched workload — the
        # decision (or its fallback) is auditable via stats()["autoconfig"]
        self._autoconfig: Optional[dict] = None
        if growth is None:
            growth = self._auto_growth()
        self.buckets = bucket_ladder(self.max_batch_size, growth, min_bucket)
        self.donate = donate
        self.pass_mask = pass_mask
        self.name = name or getattr(fn, "__name__", "fn")
        self._jitted = None
        self._compiled: Dict[tuple, Any] = {}
        self._lock = threading.Lock()
        self._compile_counts: Dict[int, int] = {}
        self._hit_counts: Dict[int, int] = {}
        self._warmup_compiles = 0

    def _auto_growth(self) -> float:
        """Growth factor from the learned perf model (fallback 2.0)."""
        try:
            from . import perfmodel

            g, dec = perfmodel.suggest_bucket_growth(self.max_batch_size)
            self._autoconfig = dec.provenance()
            return g
        except Exception:  # model failure keeps 2.0
            return 2.0

    # --- bucket selection ------------------------------------------------
    def bucket_for(self, n: int) -> int:
        """Smallest ladder rung covering ``n`` (``max_batch_size`` for any
        larger chunked batch)."""
        if n < 1:
            raise ValueError(f"batch of {n} rows has no bucket")
        for b in self.buckets:
            if n <= b:
                return b
        return self.max_batch_size

    # --- compilation -----------------------------------------------------
    def _ensure_jitted(self) -> dict:
        """Lazy per-arity jit wrapper cache. Donation resolves here (needs
        the backend): input buffers are donated on TPU/GPU where XLA reuses
        them for outputs; CPU does not implement donation (a warning per
        compile), so auto mode skips it there."""
        import jax

        if self._jitted is None:
            donate = self.donate
            if donate is None:
                donate = jax.default_backend() not in ("cpu",)
            self._donate = bool(donate)
            self._jitted = {}
        return self._jitted

    @staticmethod
    def _spec_of(arr) -> Tuple[Tuple[int, ...], Any]:
        a = np.asarray(arr) if not hasattr(arr, "shape") else arr
        _witness_observe("core.bucketed.spec", a)
        return tuple(a.shape[1:]), np.dtype(getattr(a, "dtype", None) or
                                            np.asarray(arr).dtype)

    def _executable(self, bucket: int, specs: Tuple, *, warmup: bool = False):
        """Compiled executable for (bucket, arg specs); compiles on miss and
        counts it. ``specs`` is a tuple of (trailing-shape, dtype) per arg."""
        key = (bucket, specs)
        with self._lock:
            hit = self._compiled.get(key)
            if hit is not None:
                if not warmup:
                    self._hit_counts[bucket] = \
                        self._hit_counts.get(bucket, 0) + 1
                return hit
        import jax

        jits = self._ensure_jitted()
        nargs = len(specs) + (1 if self.pass_mask else 0)
        jfn = jits.get(nargs)
        if jfn is None:
            donate = tuple(range(len(specs))) if self._donate else ()
            jfn = jax.jit(self.fn, donate_argnums=donate)
            jits[nargs] = jfn
        avals = [jax.ShapeDtypeStruct((bucket,) + shape, dtype)
                 for shape, dtype in specs]
        if self.pass_mask:
            avals.append(jax.ShapeDtypeStruct((bucket,), np.bool_))
        compiled = jfn.lower(*avals).compile()
        with self._lock:
            # a racing thread may have compiled the same key; keep the first
            existing = self._compiled.get(key)
            if existing is not None:
                return existing
            self._compiled[key] = compiled
            self._compile_counts[bucket] = \
                self._compile_counts.get(bucket, 0) + 1
            if warmup:
                self._warmup_compiles += 1
        return compiled

    def warmup(self, *templates, persistent_cache: bool = True) -> dict:
        """AOT-compile EVERY bucket for the argument signature described by
        ``templates`` (one array-like per ``fn`` argument; only trailing
        dims and dtype matter — pass a single example row or a full batch).
        With ``persistent_cache`` the XLA executables also land in the
        on-disk jax compilation cache (core/compile_cache.py), so warmup
        cost is amortized across worker processes. Returns :meth:`stats`."""
        if not templates:
            raise ValueError("warmup needs one template array per fn "
                             "argument (trailing dims + dtype)")
        if persistent_cache:
            from .compile_cache import enable_compile_cache

            enable_compile_cache()
        specs = tuple(self._spec_of(t) for t in templates)
        for bucket in self.buckets:
            self._executable(bucket, specs, warmup=True)
        return self.stats()

    # --- execution -------------------------------------------------------
    def dispatch(self, *args) -> PendingBatch:
        """Launch the computation for ``args`` (batch-leading arrays, equal
        leading dim) WITHOUT blocking on the device: batches are padded to
        their bucket, chunked above ``max_batch_size``, and every chunk's
        executable is dispatched before any host sync. Call ``.result()``
        on the returned handle when (and only when) the replies are
        written."""
        import jax

        if not args:
            raise ValueError("dispatch needs at least one batch array")
        arrs = [a if isinstance(a, np.ndarray) else np.asarray(a)
                for a in args]
        n = arrs[0].shape[0]
        for a in arrs[1:]:
            if a.shape[0] != n:
                raise ValueError(
                    "dispatch arguments disagree on the batch dimension: "
                    f"{[a.shape[0] for a in arrs]}")
        if n == 0:
            raise ValueError("cannot dispatch an empty batch")
        specs = tuple(self._spec_of(a) for a in arrs)
        chunks: List[Tuple[Any, int, int]] = []
        treedef = single = None
        for start in range(0, n, self.max_batch_size):
            stop = min(start + self.max_batch_size, n)
            real = stop - start
            bucket = self.bucket_for(real)
            padded = [_pad_to(a[start:stop], bucket) for a in arrs]
            if self.pass_mask:
                padded.append(np.arange(bucket) < real)
            out = self._executable(bucket, specs)(*padded)
            single = not isinstance(out, (tuple, list))
            leaves, treedef = jax.tree_util.tree_flatten(out)
            chunks.append((leaves, real, bucket))
        return PendingBatch(chunks, treedef, single, n)

    def __call__(self, *args):
        """Synchronous convenience: ``dispatch(...).result()``."""
        return self.dispatch(*args).result()

    # --- observability ---------------------------------------------------
    def warm_buckets(self) -> List[int]:
        """Ascending bucket sizes holding at least one compiled executable —
        what a fabric worker advertises in its heartbeat so the gateway can
        prefer replicas whose AOT cache already covers a batch's bucket
        (docs/resilience.md, "Multi-host fabric"). Advisory: routing built
        on this must degrade to load-based selection when it is stale."""
        with self._lock:
            return sorted(self._compile_counts)

    def stats(self) -> dict:
        with self._lock:
            compiles = dict(sorted(self._compile_counts.items()))
            hits = dict(sorted(self._hit_counts.items()))
            out = {"name": self.name,
                   "buckets": list(self.buckets),
                   "compiles": compiles,
                   "hits": hits,
                   "warmup_compiles": self._warmup_compiles,
                   "total_compiles": sum(compiles.values()),
                   "total_hits": sum(hits.values())}
            if self._autoconfig is not None:
                out["autoconfig"] = self._autoconfig
            return out

    def reset_stats(self) -> None:
        """Zero the hit counters (compile counts describe the cache contents
        and are kept — a reset must not hide a later recompile)."""
        with self._lock:
            self._hit_counts = {}

    def __repr__(self) -> str:
        return (f"BucketedRunner({self.name!r}, buckets={list(self.buckets)},"
                f" compiled={len(self._compiled)})")


class RunnerFleet:
    """Per-tenant accounting over a SHARED runner pool.

    The multi-tenant serving fleet (docs/resilience.md, "Multi-tenant
    fleet") runs N tenants' models through one worker process and one
    on-disk compile cache; each tenant's handler carries its own
    :class:`BucketedRunner`, and this registry is the fleet-wide view:
    ``register(tenant, runner)``, ``warm_all()`` off the hot path, and
    :meth:`stats` — per-tenant compile/hit counters plus fleet totals, the
    numbers ``bench_multitenant`` and the shared-cache accounting test
    assert on. Thread-safe; runners stay owned by their handlers (this
    holds references, never copies)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._runners: Dict[str, BucketedRunner] = {}

    def register(self, tenant: str, runner: BucketedRunner
                 ) -> "RunnerFleet":
        with self._lock:
            self._runners[tenant] = runner
        return self

    def runner(self, tenant: str) -> Optional[BucketedRunner]:
        with self._lock:
            return self._runners.get(tenant)

    def tenants(self) -> List[str]:
        with self._lock:
            return sorted(self._runners)

    def warm_all(self, templates: Dict[str, tuple]) -> dict:
        """AOT-warm every registered runner whose tenant has a template
        tuple in ``templates`` (one array-like per runner argument);
        returns :meth:`stats` after the sweep."""
        with self._lock:
            items = list(self._runners.items())
        for tenant, runner in items:
            tmpl = templates.get(tenant)
            if tmpl is not None:
                runner.warmup(*tmpl)
        return self.stats()

    def stats(self) -> dict:
        """{"tenants": {tenant: runner stats}, "total_compiles",
        "total_hits"} — the shared-fleet accounting: compiles are what the
        fleet PAID (once per (runner, bucket, spec)), hits are what each
        tenant's traffic reused."""
        with self._lock:
            items = list(self._runners.items())
        per = {t: r.stats() for t, r in items}
        return {"tenants": per,
                "total_compiles": sum(s["total_compiles"]
                                      for s in per.values()),
                "total_hits": sum(s["total_hits"] for s in per.values())}
