"""Flax fine-tune engine — the Horovod/Lightning replacement.

The reference trains via horovod.spark.lightning TorchEstimator: one process per
executor, NCCL ring allreduce of gradients, petastorm reader feeding torch
DataLoaders (SURVEY.md §3.4). On TPU the whole stack collapses to one jitted
train step over a named-axis mesh: the batch is sharded on ``data``, and the
parameter/optimizer placement is an explicit ``in_shardings``/``out_shardings``
contract on that jit (docs/dl-scaling.md):

* ``param_sharding="replicated"`` — plain data parallel; XLA inserts the
  gradient psum over ICI (the NCCL-ring analog).
* ``param_sharding="zero"`` (alias ``"fsdp"``) — ZeRO-style (arXiv:2004.13336):
  params and optimizer moments are PINNED to 1/N shards over ``data``; XLA
  all-gathers params at use and reduce-scatters gradients, so each device
  updates only its slice and replicated-state memory stops capping batch size.
* ``param_sharding="pipeline"`` — MPMD pipeline parallelism over a ``stage``
  mesh axis (arXiv:2412.14374; dl/pipeline.py): per-stage programs with a
  GPipe microbatch schedule and circular stage→group placement.

Layer freezing mirrors LitDeepVisionModel._update_transfer_learning
(reference LitDeepVisionModel.py:56-110): a regex over parameter paths selects
trainable leaves; frozen leaves get zero updates via optax.masked.
"""

from __future__ import annotations

import contextlib
import dataclasses
import os
import re
import time
from typing import Callable, Iterator, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax.core import unfreeze
from flax import traverse_util
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core.checkpoint import (CheckpointStore, NonFiniteGuard,
                               NonFiniteLossError, preemption_point)
from ..core.compat import donate_argnums_if_supported
from ..core.logging import InstrumentationMeasures, record_failure
from ..parallel.elastic import current_watchdog
from ..parallel.mesh import DATA_AXIS, apply_tree_shardings, tree_shardings

# Batch-corruption hook for the chaos suite (testing/chaos.py installs it):
# called as hook(step, xb, yb) -> (xb, yb) on HOST batches before they are
# sharded, so an injected NaN reaches the loss exactly like bad input data
# would. Same global-hook pattern as parallel.collectives._CHAOS_HOOK.
_CHAOS_BATCH_HOOK = None


@dataclasses.dataclass
class TrainConfig:
    batch_size: int = 64
    max_epochs: int = 1
    learning_rate: float = 1e-3
    weight_decay: float = 0.0
    optimizer: str = "adam"            # adam | adamw | sgd | momentum
    lr_schedule: str = "constant"      # constant | cosine
    warmup_steps: int = 0
    grad_clip_norm: float = 0.0
    freeze_regex: Optional[str] = None  # param paths matching this are frozen
    compute_dtype: str = "float32"     # float32 | bfloat16
    seed: int = 0
    shuffle: bool = True
    steps_per_epoch: Optional[int] = None
    # mid-training checkpoint/resume (reference: Lightning/Horovod `store`
    # checkpoint dir + run-id resume, DeepVisionClassifier.py:86; SURVEY §5.4).
    # Checkpoints go through core/checkpoint.CheckpointStore: atomic writes,
    # a CRC32/SHA-256 manifest verified on load, keep-last-N retention, and
    # automatic fallback to the previous good snapshot on corruption.
    checkpoint_dir: Optional[str] = None
    save_every_epochs: int = 1
    resume: bool = True  # pick up from the latest checkpoint when present
    keep_checkpoints: int = 3  # retention: newest N epoch snapshots kept
    # policy on a non-finite training loss (core/checkpoint.NonFiniteGuard):
    # "raise" stops the run, "skip" drops the poisoned step, "rollback"
    # restores the last good checkpoint (requires checkpoint_dir)
    nonfinite_policy: str = "raise"
    # parameter placement over the mesh (module docstring / docs/dl-scaling.md):
    # "replicated" (plain data-parallel), "zero"/"fsdp" (ZeRO-sharded params +
    # optimizer moments over the data axis), or "pipeline" (MPMD stages over a
    # "stage" mesh axis; needs a dl.StageSequential model). The reference's
    # Horovod stack has none of these (SURVEY §2.2 "NOT PRESENT").
    # "auto" defers to core.perfmodel (recorded dl_param_sharding rows from
    # bench_dl_sharded); low confidence falls back to "replicated" and the
    # decision provenance lands in trainer.stats["autoconfig"].
    param_sharding: str = "replicated"  # replicated | zero | fsdp | pipeline | auto
    # microbatch gradient accumulation INSIDE train_step: the global batch is
    # split into accum_steps microbatches scanned sequentially, trading the
    # ZeRO all-gather count against live activation memory (one gather set
    # per step regardless of accum). batch_size must divide evenly. Note:
    # BatchNorm stats and the dropout stream see microbatches, so accum > 1
    # is not bit-identical to accum=1 for models with BN/dropout. 0 defers
    # the choice to core.perfmodel (fallback 1, provenance in stats).
    accum_steps: int = 1
    # host->device input pipeline depth (_prefetch): how many future batches
    # are sharded/device_put ahead of the step consuming them
    prefetch_batches: int = 2
    # donate params/opt_state buffers to the train_step jit (in-place update
    # on TPU/GPU via core.compat.donate_argnums_if_supported; no-op on CPU).
    # Only takes effect with nonfinite_policy="raise": "skip"/"rollback" must
    # read the pre-step state back after the step, which donation forbids.
    donate_buffers: bool = True
    # pipeline mode: microbatches in flight per global batch (0 -> one per
    # stage group) and the within-group param placement (replicated | zero)
    pipeline_microbatches: int = 0
    pipeline_param_sharding: str = "replicated"
    # pipeline schedule (docs/dl-scaling.md "Overlap schedule"):
    # "fill_drain" runs the full forward wavefront before backward (GPipe:
    # remat from saved stage inputs); "overlap" double-buffers each stage's
    # weights — fwd/bwd consume a once-per-batch gathered copy, the NEXT
    # batch's ZeRO all-gather is dispatched while the current backward is
    # still in flight, and backward is 1F1B and transpose-only (saved vjp
    # residuals, no forward recompute) — trading one replicated param copy
    # plus residual storage per group for the per-program weight traffic
    # and the remat flops. "auto" defers the choice to core.perfmodel
    # (analytic bubble model, displaced by recorded dl_pipeline_schedule
    # rows); provenance lands in trainer.stats["autoconfig"].
    pipeline_schedule: str = "fill_drain"  # fill_drain | overlap | auto
    # sequence parallelism (docs/dl-scaling.md "Sequence parallelism"): when
    # the mesh carries a "seq" axis (parallel.make_mesh({"seq": p, ...})),
    # TransformerLayerUnit self-attention runs seq-sharded — "ring" rotates
    # K/V blocks around the axis (P2P ppermute + online softmax), "ulysses"
    # re-shards seq<->heads with two all-to-alls and runs exact per-device
    # attention (needs heads % seq_shards == 0). "auto" defers the variant
    # to core.perfmodel.suggest_seq_attention (wire-byte prior, displaced by
    # recorded seq_attention rows from bench_dl_seq; fallback "ring"); the
    # SYNAPSEML_TPU_SEQ_ATTENTION env var overrides everything, and Decision
    # provenance lands in trainer.stats["autoconfig"]["seq_attention"].
    # seq_parallel=False ignores the seq axis entirely (attention unsharded).
    seq_parallel: bool = True
    seq_attention: str = "auto"  # auto | ring | ulysses


def _make_tx(cfg: TrainConfig, total_steps: int, trainable_mask=None):
    if cfg.lr_schedule == "cosine":
        sched = optax.warmup_cosine_decay_schedule(
            0.0, cfg.learning_rate, max(cfg.warmup_steps, 1),
            max(total_steps, cfg.warmup_steps + 1))
    else:
        sched = optax.linear_schedule(cfg.learning_rate, cfg.learning_rate, 1) \
            if cfg.warmup_steps == 0 else optax.warmup_cosine_decay_schedule(
                0.0, cfg.learning_rate, cfg.warmup_steps, total_steps, cfg.learning_rate)
    opts = {
        "adam": lambda: optax.adam(sched),
        "adamw": lambda: optax.adamw(sched, weight_decay=cfg.weight_decay),
        "sgd": lambda: optax.sgd(sched),
        "momentum": lambda: optax.sgd(sched, momentum=0.9),
    }
    if cfg.optimizer not in opts:
        raise ValueError(f"unknown optimizer {cfg.optimizer!r}")
    tx = opts[cfg.optimizer]()
    if cfg.grad_clip_norm > 0:
        tx = optax.chain(optax.clip_by_global_norm(cfg.grad_clip_norm), tx)
    if trainable_mask is not None:
        # mask AFTER the optimizer: adamw's weight decay contributes updates
        # even for zero gradients, so zeroing grads alone lets frozen params
        # decay — zero the final update on frozen leaves instead
        frozen = jax.tree.map(lambda t: not t, trainable_mask)
        tx = optax.chain(tx, optax.masked(optax.set_to_zero(), frozen))
    return tx


def freeze_mask(params, freeze_regex: Optional[str]):
    """True = trainable. Paths are '/'-joined flax param paths."""
    if not freeze_regex:
        return None
    pat = re.compile(freeze_regex)
    flat = traverse_util.flatten_dict(unfreeze(params))
    mask = {k: not pat.search("/".join(str(p) for p in k)) for k in flat}
    return traverse_util.unflatten_dict(mask)


class FlaxTrainer:
    """Generic supervised fine-tune loop for a flax module with optional
    BatchNorm state. Loss: softmax CE (classification) or MSE (labels float &
    num_classes==1)."""

    def __init__(self, model, config: TrainConfig, mesh: Optional[Mesh] = None,
                 loss: str = "softmax"):
        self.model = model
        self.cfg = config
        self.mesh = mesh
        self.loss = loss
        self.params = None
        self.batch_stats = None
        self.counters = {}
        self.measures = InstrumentationMeasures()   # a new one per fit

    # --- setup ----------------------------------------------------------
    def init(self, sample_x, jit: bool = False):
        """``jit``: initialise in one compiled program (a model whose
        eager forward pass would be hundreds of small programs)."""
        rng = jax.random.PRNGKey(self.cfg.seed)
        init = (jax.jit(self.model.init, static_argnames="train") if jit
                else self.model.init)
        variables = init(rng, jnp.asarray(sample_x[:1]), train=False)
        self.params = variables["params"]
        self.batch_stats = variables.get("batch_stats", {})
        # what the model counts about its own work (``sow`` into
        # "counters"): running sums that ride along with the train step
        self.counters = jax.tree.map(jnp.zeros_like,
                                     unfreeze(variables.get("counters", {})))
        return self

    def load_params(self, params, batch_stats=None):
        self.params = params
        if batch_stats is not None:
            self.batch_stats = batch_stats
        return self

    # --- data -----------------------------------------------------------
    def _batches(self, X, y, rng: np.random.Generator) -> Iterator[Tuple[np.ndarray, np.ndarray]]:
        """Shuffled fixed-size batches. When ``n >= batch_size`` the epoch
        tail (``n % batch_size`` rows) is DROPPED — every step sees a full
        batch so jit shapes stay static and per-device shards stay equal;
        with shuffling each epoch drops a different tail. Datasets smaller
        than one batch train on all rows each step instead."""
        n = len(X)
        if n == 0:
            raise ValueError("cannot train on an empty dataset")
        idx = rng.permutation(n) if self.cfg.shuffle else np.arange(n)
        bs = self.cfg.batch_size
        if n < bs:
            # fewer rows than one batch: train on all of them each step
            yield X[idx], y[idx]
            return
        limit = self.cfg.steps_per_epoch
        for s, start in enumerate(range(0, n - bs + 1, bs)):
            if limit and s >= limit:
                return
            sel = idx[start: start + bs]
            yield X[sel], y[sel]

    def _prefetch(self, batches, size: Optional[int] = None):
        """Host→device input pipelining (the petastorm-loader role,
        TPU-style): the next ``size`` batches (default
        ``cfg.prefetch_batches``) are sharded/device_put ahead of the step
        that consumes them, so the transfer overlaps the current step's
        compute (JAX dispatch is async; holding the arrays keeps the
        transfers in flight). Runs on the shared ingestion layer (io/ingest.py
        ChunkPump, synchronous-lookahead mode — the exact refill-before-
        yield deque semantics this method used to hand-roll; the gbdt
        out-of-core streamer and online drain share the same layer).
        ``_batches``'s epoch-tail drop is upstream of the pump and carries
        over unchanged (regression-tested in tests/test_oocore.py)."""
        from ..io.ingest import ChunkPump  # lazy: io/__init__ is heavy

        if size is None:
            size = self.cfg.prefetch_batches
        place = lambda b: (self._shard(b[0]), self._shard(b[1]))
        return iter(ChunkPump(batches, place=place, depth=max(size, 1),
                              threaded=False, name="dl-prefetch"))

    def _shard(self, arr):
        if self.mesh is None:
            return jnp.asarray(arr)
        spec = P(DATA_AXIS, *([None] * (np.ndim(arr) - 1)))
        if jax.process_count() > 1:
            # multi-host: ``arr`` is THIS process's slice of the global batch
            # (the Horovod per-worker shard analog); assemble the global array
            from ..parallel.mesh import to_global_rows

            return to_global_rows(self.mesh, spec, arr)
        return jax.device_put(jnp.asarray(arr), NamedSharding(self.mesh, spec))

    # --- auto configuration (core/perfmodel) -----------------------------
    def _resolve_autoconfig(self, cfg: TrainConfig) -> dict:
        """Resolve the ``param_sharding="auto"`` / ``accum_steps=0`` sentinels.

        Delegates to core.perfmodel (``suggest_param_sharding`` /
        ``suggest_accum_steps``) with the hand-tuned defaults
        (``"replicated"``, ``1``) as the low-confidence fallback.  Explicit
        values bypass the model entirely; Decision provenance is returned
        for ``trainer.stats["autoconfig"]`` so a fleet operator can audit
        predicted-vs-observed after the fit.
        """
        auto_sharding = cfg.param_sharding == "auto"
        auto_accum = int(cfg.accum_steps) == 0
        if not (auto_sharding or auto_accum):
            return {}
        info: dict = {}
        try:
            from ..core import perfmodel

            pbytes = int(sum(int(np.prod(p.shape)) * p.dtype.itemsize
                             for p in jax.tree.leaves(self.params)))
            devices = 1
            if self.mesh is not None:
                devices = int(dict(self.mesh.shape).get(DATA_AXIS, 1))
            if auto_sharding:
                arm, dec = perfmodel.suggest_param_sharding(
                    pbytes, int(cfg.batch_size), devices)
                if arm in ("zero", "fsdp", "pipeline") and self.mesh is None:
                    arm = "replicated"  # sharded state needs a mesh
                cfg.param_sharding = arm
                info["param_sharding"] = dec.provenance()
            if auto_accum:
                k, dec = perfmodel.suggest_accum_steps(
                    int(cfg.batch_size), pbytes, None)
                cfg.accum_steps = max(1, int(k))
                info["accum_steps"] = dec.provenance()
        except Exception:  # model failure must never block training
            if cfg.param_sharding == "auto":
                cfg.param_sharding = "replicated"
            if int(cfg.accum_steps) == 0:
                cfg.accum_steps = 1
        return info

    def _resolve_seq_attention(self, cfg: TrainConfig, X):
        """Resolve sequence-parallel attention routing for this fit.

        Returns ``(scope, info)``: the context manager the fit body traces
        its jits under (``backbones.seq_attention_scope``, or a nullcontext
        when the mesh carries no ``seq`` axis / ``seq_parallel=False``) and
        Decision provenance for ``stats["autoconfig"]``. The variant
        resolves as: ``SYNAPSEML_TPU_SEQ_ATTENTION`` env override >
        explicit ``cfg.seq_attention`` > ``perfmodel.suggest_seq_attention``
        (fallback "ring" — model failure never blocks training). Unknown
        variant names raise the structured :class:`ElasticUnsupportedError`
        carrying the dl-scaling SUPPORTED_MATRIX.
        """
        self._seq_variant = None
        if cfg.seq_attention not in ("auto", "ring", "ulysses"):
            from ..parallel.elastic import ElasticUnsupportedError
            from .pipeline import SUPPORTED_MATRIX

            raise ElasticUnsupportedError(
                f"seq attention variant {cfg.seq_attention!r}",
                matrix=SUPPORTED_MATRIX,
                hint="seq_attention must be one of: auto | ring | ulysses")
        from ..parallel.mesh import SEQ_AXIS

        sp = (int(dict(self.mesh.shape).get(SEQ_AXIS, 1))
              if self.mesh is not None else 1)
        if not cfg.seq_parallel or sp < 2:
            return contextlib.nullcontext(), {}
        env = os.environ.get("SYNAPSEML_TPU_SEQ_ATTENTION", "").strip().lower()
        info: dict = {}
        variant = cfg.seq_attention
        if env in ("ring", "ulysses"):
            variant = env
            info["seq_attention"] = {"arm": env, "source": "env",
                                     "fallback_used": False}
        elif variant == "auto":
            from .backbones import model_attention_heads

            heads = model_attention_heads(self.model)
            seq_len = int(np.asarray(X).shape[1]) if np.ndim(X) >= 2 else 0
            try:
                from ..core import perfmodel

                variant, dec = perfmodel.suggest_seq_attention(
                    float(seq_len or sp), float(heads or sp), float(sp),
                    batch=float(cfg.batch_size))
                info["seq_attention"] = dec.provenance()
            except Exception:  # model failure must never block training
                variant = "ring"
        else:
            info["seq_attention"] = {"arm": variant, "source": "explicit",
                                     "fallback_used": False}
        from .backbones import seq_attention_scope

        self._seq_variant = variant
        return seq_attention_scope(self.mesh, variant), info

    # --- train ----------------------------------------------------------
    def fit(self, X, y, valid: Optional[tuple] = None,
            log_fn: Optional[Callable] = None,
            step_fn: Optional[Callable] = None):
        """Train; ``self.history`` gets one entry an epoch, ``self.measures``
        the fit's span record (``self.stats["measures"]`` its report).

        ``step_fn(step_idx, loss, params, batch_stats, opt_state)`` is called
        after every accepted step, before the next is dispatched, with the
        device arrays as they are: the trainer neither waits nor copies for
        it. ``params`` and ``opt_state`` are DONATED to the next step
        (``donate_buffers``), so a hook that keeps them copies them inside
        the call (``jax.tree.map(jnp.copy, ...)`` or ``jax.device_get``).
        With ``step_fn=None`` the loop dispatches exactly what it did
        without the hook. Not available with ``param_sharding="pipeline"``.
        """
        cfg = self.cfg
        # seq routing is scoped around the WHOLE fit body: every jit traced
        # inside (train_step, the per-stage pipeline programs) picks up the
        # seq-sharded attention at trace time
        seq_scope, seq_info = self._resolve_seq_attention(cfg, X)
        self._seq_autoconfig = seq_info
        with seq_scope:
            if cfg.param_sharding == "pipeline":
                from .pipeline import fit_pipeline

                if step_fn is not None:
                    raise NotImplementedError(
                        "step_fn is not threaded through the pipeline "
                        "schedule (per-stage parameter trees); use "
                        "param_sharding replicated | zero")
                return fit_pipeline(self, X, y, valid=valid, log_fn=log_fn)
            return self._fit_spmd(X, y, valid=valid, log_fn=log_fn,
                                  step_fn=step_fn)

    def _fit_spmd(self, X, y, valid: Optional[tuple] = None,
                  log_fn: Optional[Callable] = None,
                  step_fn: Optional[Callable] = None):
        cfg = self.cfg
        measures = self.measures = InstrumentationMeasures()
        X = np.asarray(X)
        y = np.asarray(y)
        if self.params is None:
            self.init(X)
        autoconfig_info = self._resolve_autoconfig(cfg)
        autoconfig_info.update(getattr(self, "_seq_autoconfig", {}))
        if cfg.param_sharding not in ("replicated", "zero", "fsdp"):
            raise ValueError(
                f"unknown param_sharding {cfg.param_sharding!r}; expected "
                "replicated | zero | fsdp | pipeline | auto")
        n = len(X)
        steps_per_epoch = cfg.steps_per_epoch or max(n // cfg.batch_size, 1)
        total_steps = steps_per_epoch * cfg.max_epochs
        mask = freeze_mask(self.params, cfg.freeze_regex)
        tx = _make_tx(cfg, total_steps, mask)
        zero = cfg.param_sharding in ("zero", "fsdp")
        if zero and self.mesh is None:
            raise ValueError(
                f"param_sharding={cfg.param_sharding!r} requires a mesh")
        multiproc = self.mesh is not None and jax.process_count() > 1
        if multiproc:
            from ..parallel.mesh import (assert_equal_across_processes,
                                         local_mesh_devices)

            local_mesh_devices(self.mesh)   # mesh must span every process
            # unequal shards would desynchronize per-step collectives and
            # hang, not raise
            assert_equal_across_processes((len(X),), "local row count")
            # identical host-side params on every process:
            # apply_tree_shardings then places each process's blocks
            # (committed single-device arrays would clash)
            self.params = jax.tree.map(np.asarray, self.params)
            self.batch_stats = jax.tree.map(np.asarray, self.batch_stats)

        params, batch_stats = self.params, self.batch_stats or {}
        shardings = None
        mode = "zero" if zero else "replicated"
        if self.mesh is not None:
            # the explicit placement contract: params + optimizer moments
            # pinned to their shards (ZeRO) or the full mesh (replicated);
            # batch stats are tiny and stay replicated
            param_sh = tree_shardings(self.mesh, params, mode)
            bs_sh = tree_shardings(self.mesh, batch_stats, "replicated")
            params = apply_tree_shardings(params, param_sh)
            batch_stats = apply_tree_shardings(batch_stats, bs_sh)
            # moments born sharded: init runs under jit with out_shardings
            # pinned, so a full replicated copy never exists (and multi-host
            # needs the jit anyway — eager ops on global arrays don't fly)
            opt_sh = tree_shardings(self.mesh, jax.eval_shape(tx.init, params),
                                    mode)
            init_fn = jax.jit(tx.init, out_shardings=opt_sh)
            opt_state = init_fn(params)
            shardings = (param_sh, bs_sh, opt_sh)
        else:
            opt_state = tx.init(params)

        compute_dtype = jnp.bfloat16 if cfg.compute_dtype == "bfloat16" else jnp.float32
        has_bn = bool(self.batch_stats)
        zero_counters = self.counters
        mutable = (["batch_stats"] if has_bn else []) + (
            ["counters"] if zero_counters else [])
        model, loss_kind = self.model, self.loss

        def cast_in(xb):
            # only float inputs get the compute dtype; integer token ids must
            # stay integral for embedding lookups
            return xb.astype(compute_dtype) if jnp.issubdtype(xb.dtype, jnp.floating) else xb

        def loss_fn(params, batch_stats, xb, yb, rng, counters):
            variables = {"params": params}
            rngs = {"dropout": rng}
            if has_bn:
                variables["batch_stats"] = batch_stats
            if zero_counters:
                variables["counters"] = counters
            if mutable:
                logits, mutated = model.apply(variables, cast_in(xb),
                                              train=True, mutable=mutable,
                                              rngs=rngs)
                new_bs = mutated["batch_stats"] if has_bn else batch_stats
                counters = mutated.get("counters", counters)
            else:
                logits = model.apply(variables, cast_in(xb), train=True, rngs=rngs)
                new_bs = batch_stats
            logits = logits.astype(jnp.float32)
            if loss_kind == "softmax":
                loss = optax.softmax_cross_entropy_with_integer_labels(
                    logits, yb.astype(jnp.int32)).mean()
                acc = (logits.argmax(-1) == yb).mean()
            else:
                loss = jnp.mean((logits.squeeze(-1) - yb) ** 2)
                acc = -loss
            return loss, (new_bs, acc, counters)

        accum = max(int(cfg.accum_steps), 1)
        if cfg.batch_size % accum:
            raise ValueError(
                f"accum_steps={accum} must divide batch_size={cfg.batch_size}")

        def train_step(params, batch_stats, opt_state, xb, yb, step,
                       counters):
            rng = jax.random.fold_in(jax.random.PRNGKey(cfg.seed), step)
            if accum == 1:
                (loss, (new_bs, acc, counters)), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(params, batch_stats, xb, yb, rng,
                                           counters)
            else:
                # microbatch accumulation: grads summed in a scan carry (one
                # optimizer update and ONE ZeRO gather set per global batch)
                xmb = xb.reshape((accum, xb.shape[0] // accum) + xb.shape[1:])
                ymb = yb.reshape((accum, yb.shape[0] // accum) + yb.shape[1:])

                def micro(carry, inp):
                    bs, gacc, cnt = carry
                    xm, ym, i = inp
                    (l_m, (bs2, a_m, cnt)), g = jax.value_and_grad(
                        loss_fn, has_aux=True)(params, bs, xm, ym,
                                               jax.random.fold_in(rng, i), cnt)
                    return ((bs2, jax.tree.map(jnp.add, gacc, g), cnt),
                            (l_m, a_m))

                (new_bs, gsum, counters), (ls, accs) = jax.lax.scan(
                    micro, (batch_stats, jax.tree.map(jnp.zeros_like, params),
                            counters),
                    (xmb, ymb, jnp.arange(accum)))
                grads = jax.tree.map(lambda g: g / accum, gsum)
                loss, acc = ls.mean(), accs.mean()
            updates, opt_state = tx.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            return params, new_bs, opt_state, loss, acc, counters

        # "skip"/"rollback" read the pre-step state AFTER the step ran, so
        # donation is only legal under the default "raise" policy
        keep_prev = cfg.nonfinite_policy != "raise"
        donate = (donate_argnums_if_supported(0, 2)
                  if cfg.donate_buffers and not keep_prev else ())
        jit_kwargs: dict = {"donate_argnums": donate}
        if self.mesh is not None:
            rep = NamedSharding(self.mesh, P())
            row_sh = NamedSharding(self.mesh, P(DATA_AXIS))  # prefix spec
            jit_kwargs["in_shardings"] = (param_sh, bs_sh, opt_sh,
                                          row_sh, row_sh, None, rep)
            jit_kwargs["out_shardings"] = (param_sh, bs_sh, opt_sh, rep, rep,
                                           rep)
        train_step = jax.jit(train_step, **jit_kwargs)

        history = []
        step_idx = 0
        start_epoch = 0
        store = (CheckpointStore(cfg.checkpoint_dir,
                                 keep_last=max(cfg.keep_checkpoints, 1))
                 if cfg.checkpoint_dir else None)
        if store is not None and cfg.resume:
            restored = _restore_checkpoint(store, params, batch_stats,
                                           opt_state, shardings=shardings)
            if restored is not None:
                params, batch_stats, opt_state, start_epoch, placed = restored
                batch_stats = batch_stats or {}
                step_idx = start_epoch * steps_per_epoch
                if shardings is not None and not placed:
                    # legacy host-numpy restore: re-apply the placements
                    params = apply_tree_shardings(params, param_sh)
                    batch_stats = apply_tree_shardings(batch_stats, bs_sh)
                    opt_state = apply_tree_shardings(opt_state, opt_sh)
        self.stats = {"state_bytes_per_device":
                      per_device_state_bytes(params, opt_state)}
        if getattr(self, "_seq_variant", None):
            self.stats["seq_attention"] = self._seq_variant
        if autoconfig_info:
            self.stats["autoconfig"] = autoconfig_info
        guard = NonFiniteGuard(policy=cfg.nonfinite_policy,
                               counter_prefix="train")

        def batches_with_chaos(rng_e, base_step):
            for i, (xb, yb) in enumerate(self._batches(X, y, rng_e)):
                hook = _CHAOS_BATCH_HOOK
                if hook is not None:
                    xb, yb = hook(base_step + i, xb, yb)
                yield xb, yb

        compile_steps = self.stats["compile_steps"] = []
        cache_size = train_step._cache_size()

        def _synced_step(*a):
            out = train_step(*a)
            jax.block_until_ready(out[3])
            return out

        epoch = start_epoch
        while epoch < cfg.max_epochs:
            preemption_point("dl.epoch", epoch)
            # shuffle order derives from (seed, epoch), NOT a Generator
            # advanced across epochs: a resumed run replays epoch e with the
            # exact batch order of the uninterrupted run
            rng_e = np.random.default_rng([cfg.seed, epoch])
            losses = []
            nsteps = 0
            counters = zero_counters
            t0 = time.perf_counter()
            rolled_back = False
            batches = self._prefetch(
                batches_with_chaos(rng_e, epoch * steps_per_epoch))
            parts_before = {k: measures.spans.get(k, 0.0) for k in _STEP_PARTS}
            with measures.span("trainer.epoch") as epoch_span:
                while True:
                    with measures.span("trainer.step",
                                       step_num=step_idx) as step_span:
                        with measures.span("dataWait") as wait_span:
                            batch = next(batches, None)
                            if batch is None:    # the epoch's data is used up
                                wait_span.discard()
                                step_span.discard()
                                break
                        xb, yb = batch
                        prev = ((params, batch_stats, opt_state)
                                if keep_prev else None)
                        wd = current_watchdog()
                        with measures.span("dispatch"):
                            if wd is not None:
                                # elastic mode: the step AND its host sync
                                # (the blocking point a hung peer's psum
                                # actually stalls) run under the collective
                                # watchdog, so a lost rank surfaces as
                                # PeerLostError instead of an indefinite stall
                                out = wd.run(_synced_step, params, batch_stats,
                                             opt_state, xb, yb, step_idx,
                                             counters, op="dl.step")
                                wd.beat("dl.step", step_idx)
                            else:
                                out = train_step(params, batch_stats,
                                                 opt_state, xb, yb, step_idx,
                                                 counters)
                        (params, batch_stats, opt_state, loss, acc,
                         step_counters) = out
                        size = train_step._cache_size()
                        if size != cache_size:
                            measures.count("compiles", size - cache_size)
                            compile_steps.append(step_idx)
                            cache_size = size
                        with measures.span("lossSync"):
                            loss_value = float(loss)
                            action = guard.check(loss_value, step_idx)
                        counters = step_counters
                        if action == "skip":
                            # drop the poisoned update; the step index still
                            # advances so the dropout stream stays aligned
                            # with the data order
                            params, batch_stats, opt_state = prev
                            step_idx += 1
                            measures.count("skipped")
                            continue
                        if action == "rollback":
                            rolled_back = True
                            measures.count("rolledBack")
                            break
                        if step_fn is not None:
                            with measures.span("stepFn"):
                                step_fn(step_idx, loss, params, batch_stats,
                                        opt_state)
                        step_idx += 1
                        nsteps += 1
                        measures.count("steps")
                        measures.count("samples", len(xb))
                        losses.append(loss_value)
            if rolled_back:
                restored = (_restore_checkpoint(store, *prev,
                                                shardings=shardings)
                            if store is not None else None)
                if restored is None:
                    raise NonFiniteLossError(
                        "nonfinite_policy='rollback' found no checkpoint "
                        "to restore (set checkpoint_dir and let at least "
                        "one epoch complete, or use policy 'skip'/'raise')")
                params, batch_stats, opt_state, epoch, placed = restored
                batch_stats = batch_stats or {}
                if shardings is not None and not placed:
                    params = apply_tree_shardings(params, param_sh)
                    batch_stats = apply_tree_shardings(batch_stats, bs_sh)
                    opt_state = apply_tree_shardings(opt_state, opt_sh)
                step_idx = epoch * steps_per_epoch
                continue
            ep = {"epoch": epoch,
                  "loss": float(np.mean(losses)) if losses else float("nan"),
                  "steps": nsteps,
                  "seconds": time.perf_counter() - t0,
                  **_epoch_step_times(measures, parts_before,
                                      epoch_span.start_ns),
                  **_epoch_counters(measures, counters)}
            if valid is not None:
                with measures.span("trainer.validation"):
                    ep["val_acc"] = float(self.evaluate(
                        valid[0], valid[1], params=params,
                        batch_stats=batch_stats))
            history.append(ep)
            if log_fn:
                log_fn(ep)
            if store is not None and (epoch + 1) % cfg.save_every_epochs == 0:
                with measures.span("trainer.checkpointSave"):
                    _save_checkpoint(store, params, batch_stats, opt_state,
                                     epoch + 1, sharded=zero)
            epoch += 1
        self.stats["measures"] = measures.report()
        self.params, self.batch_stats = params, batch_stats
        self.history = history
        if autoconfig_info:
            # predicted-vs-observed audit trail for the perfmodel decisions
            autoconfig_info["observed_fit_s"] = round(
                sum(ep["seconds"] for ep in history), 6)
        return self

    # --- eval / predict ---------------------------------------------------
    def _forward_fn(self):
        # one jitted forward per trainer (variables passed as an argument so the
        # compile cache survives across predict calls and param updates)
        if not hasattr(self, "_fwd_cached"):
            model = self.model

            @jax.jit
            def fwd(variables, xb):
                return model.apply(variables, xb, train=False).astype(jnp.float32)

            self._fwd_cached = fwd
        return self._fwd_cached

    def predict_logits(self, X, batch_size: Optional[int] = None,
                       params=None, batch_stats=None):
        params = self.params if params is None else params
        batch_stats = self.batch_stats if batch_stats is None else batch_stats
        variables = {"params": params}
        if batch_stats:
            variables["batch_stats"] = batch_stats
        fwd_v = self._forward_fn()

        def fwd(xb):
            return fwd_v(variables, xb)

        bs = batch_size or self.cfg.batch_size
        outs = []
        X = np.asarray(X)
        if len(X) == 0:
            dummy = np.zeros((1,) + X.shape[1:], X.dtype if X.dtype != object else np.float32)
            return np.asarray(fwd(jnp.asarray(dummy)))[:0]
        for start in range(0, len(X), bs):
            xb = X[start: start + bs]
            pad = 0
            if len(xb) < bs and len(outs):   # keep shapes static for the jit cache
                pad = bs - len(xb)
                xb = np.concatenate([xb, np.repeat(xb[-1:], pad, axis=0)])
            o = np.asarray(fwd(jnp.asarray(xb)))
            outs.append(o[: len(o) - pad] if pad else o)
        return np.concatenate(outs)

    def evaluate(self, X, y, params=None, batch_stats=None) -> float:
        logits = self.predict_logits(X, params=params, batch_stats=batch_stats)
        if self.loss == "softmax":
            return float((logits.argmax(-1) == np.asarray(y)).mean())
        return -float(np.mean((logits.squeeze(-1) - np.asarray(y)) ** 2))


# the children of a ``trainer.step`` span, by their keys in the report, and
# the history entry each is summed into for its epoch
_STEP_PARTS = {"trainer.step/dataWait": "data_wait_s",
               "trainer.step/dispatch": "dispatch_s",
               "trainer.step/lossSync": "loss_sync_s"}


def _epoch_step_times(measures, parts_before: dict, epoch_start_ns: int) -> dict:
    """What one epoch adds to its ``history`` entry from the span record:
    the seconds its steps waited for data, dispatched and waited for the
    loss, and the median step (over the newest ``MAX_RECORDS`` records, which
    an epoch of more steps than that outruns)."""
    out = {field: measures.spans.get(key, 0.0) - parts_before[key]
           for key, field in _STEP_PARTS.items()}
    steps = [r.end_ns - r.start_ns for r in measures.records
             if r.name == "trainer.step" and r.start_ns >= epoch_start_ns]
    out["step_ms_p50"] = float(np.median(steps)) / 1e6 if steps else float("nan")
    return out


def _epoch_counters(measures, counters) -> dict:
    """The model's own counters of one epoch, read from the device once:
    ``{"counters": {name: sum, or the list of a vector's sums}}`` for the
    ``history`` entry, the scalars also added to ``count:<name>``; nothing
    for a model that counts nothing."""
    if not counters:
        return {}
    flat = {"/".join(k): np.asarray(v).tolist() for k, v in
            traverse_util.flatten_dict(jax.device_get(counters)).items()}
    for name, value in flat.items():
        if not isinstance(value, list):
            measures.count(name, value)
    return {"counters": flat}


def per_device_state_bytes(*trees) -> int:
    """Max over devices of the live state bytes resident per device, computed
    from each leaf's sharding (``shard_shape`` × itemsize). Allocator-stat
    independent, so it works on the forked-CPU test mesh where there is no
    HBM accounting — this is the number the ZeRO memory guard in ci.sh
    asserts on. Host (non-jax) leaves are ignored."""
    per_dev: dict = {}
    for tree in trees:
        for leaf in jax.tree.leaves(tree):
            if not isinstance(leaf, jax.Array):
                continue
            nbytes = (int(np.prod(leaf.sharding.shard_shape(leaf.shape)))
                      * leaf.dtype.itemsize)
            for d in leaf.sharding.device_set:
                per_dev[d] = per_dev.get(d, 0) + nbytes
    return max(per_dev.values()) if per_dev else 0


def _save_checkpoint(store: CheckpointStore, params, batch_stats, opt_state,
                     epoch: int, sharded: bool = False) -> None:
    """Epoch checkpoint (params + optimizer + batch stats) through the
    CheckpointStore — atomic write, digest manifest, keep-last-N retention
    (the Lightning-checkpoint analog, hardened).

    ``sharded=False`` writes one flax msgpack blob (replicated state).
    ``sharded=True`` writes the per-shard format of
    ``core.checkpoint.save_sharded_tree``: one npz of host-local shard blocks
    per process plus a pytree/sharding manifest, so ZeRO/pipeline state is
    saved without ever materializing a full copy on one host."""
    if sharded:
        from ..core.checkpoint import save_sharded_tree

        save_sharded_tree(
            store, epoch,
            {"params": params, "batch_stats": batch_stats or {},
             "opt_state": opt_state},
            meta={"kind": "dl-trainer", "epoch": int(epoch),
                  "format": "sharded"})
        return
    from flax.serialization import to_bytes

    blob = to_bytes({"params": params, "batch_stats": batch_stats or {},
                     "opt_state": opt_state, "epoch": epoch})
    store.save(epoch, {"state.msgpack": blob}, meta={"kind": "dl-trainer",
                                                     "epoch": int(epoch)})


def _restore_checkpoint(store: CheckpointStore, params, batch_stats,
                        opt_state, shardings=None):
    """(params, batch_stats, opt_state, next_epoch, placed) from the newest
    VERIFIED checkpoint, or None when the dir holds no usable one (missing,
    torn, or corrupt snapshots are counted and skipped by the store).
    ``placed`` says whether the leaves are already globally-sharded arrays
    (sharded-format restore with target ``shardings`` — resharding on load
    handles a changed mesh shape) or host numpy (legacy msgpack). A
    checkpoint whose pytree no longer matches the model raises a ValueError
    naming the fix instead of returning garbage params."""
    # the probe keeps only the small artifacts; shard npz files are verified
    # but not retained until the sharded loader knows which blocks it needs
    ckpt = store.load_latest(artifact_filter=lambda n: n in (
        "state.msgpack", "state.sharding.json"))
    if ckpt is None:
        return None
    template = {"params": params, "batch_stats": batch_stats or {},
                "opt_state": opt_state}
    if "state.sharding.json" in ckpt.artifacts:
        from ..core.checkpoint import (CheckpointError,
                                       load_sharded_from_checkpoint)

        sh_tree = None
        if shardings is not None:
            param_sh, bs_sh, opt_sh = shardings
            sh_tree = {"params": param_sh, "batch_stats": bs_sh or {},
                       "opt_state": opt_sh}
        try:
            tree = load_sharded_from_checkpoint(store, ckpt, template,
                                                shardings=sh_tree)
        except CheckpointError as e:
            record_failure("checkpoint.pytree_mismatch", base=ckpt.base,
                           error=str(e)[:200])
            raise ValueError(
                f"checkpoint {ckpt.base} in {store.dir} does not match the "
                "current model/optimizer structure (architecture or "
                f"optimizer changed since it was saved): {e}. Delete the "
                "checkpoint directory or set resume=False to train from "
                "scratch") from e
        epoch = int(ckpt.meta.get("epoch", ckpt.step))
        return (tree["params"], tree["batch_stats"] or None,
                tree["opt_state"], epoch, sh_tree is not None)
    blob_bytes = ckpt.artifacts.get("state.msgpack")
    if blob_bytes is None:
        record_failure("checkpoint.pytree_mismatch", base=ckpt.base,
                       reason="missing state.msgpack artifact")
        raise ValueError(
            f"checkpoint {ckpt.base} in {store.dir} has no trainer state "
            "artifact — it was written by something else; point "
            "checkpoint_dir at a fresh directory")
    from flax.serialization import from_bytes

    template["epoch"] = 0
    try:
        blob = from_bytes(template, blob_bytes)
        # from_bytes matches names, not shapes: a head that changed width
        # restores "successfully" with wrong-shaped arrays. Compare leaf
        # shapes explicitly so the failure is loud and immediate.
        for cur, new in zip(jax.tree_util.tree_leaves(template["params"]),
                            jax.tree_util.tree_leaves(blob["params"])):
            if getattr(cur, "shape", None) != getattr(new, "shape", None):
                raise ValueError(
                    f"parameter shape {getattr(new, 'shape', None)} in "
                    f"checkpoint != model shape {getattr(cur, 'shape', None)}")
    except Exception as e:
        record_failure("checkpoint.pytree_mismatch", base=ckpt.base,
                       error=str(e)[:200])
        raise ValueError(
            f"checkpoint {ckpt.base} in {store.dir} does not match the "
            "current model/optimizer structure (architecture or optimizer "
            f"changed since it was saved): {e}. Delete the checkpoint "
            "directory or set resume=False to train from scratch") from e
    return (blob["params"], blob["batch_stats"] or None, blob["opt_state"],
            int(blob["epoch"]), False)


def softmax_np(logits: np.ndarray) -> np.ndarray:
    """Numerically-stable softmax on host arrays (shared by the DL model
    transforms)."""
    z = logits - logits.max(-1, keepdims=True)
    e = np.exp(z)
    return e / e.sum(-1, keepdims=True)
