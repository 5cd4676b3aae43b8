"""Shared double-buffered host→device ingestion layer — ONE chunk pump for
every streaming consumer in the repo.

Three loops used to own three ad-hoc prefetch pipelines: the dl trainer's
``_prefetch`` deque (``TrainConfig.prefetch_batches``), the online loops'
drain-poll thread, and (new, the reason this module exists) the out-of-core
GBDT data plane (``gbdt/stream.py``), which re-streams the quantized feature
matrix from host memory once per tree level. They now share this layer:

:class:`ChunkPump`
    A bounded-depth chunk pipeline. ``place(chunk)`` (typically a sharded
    ``jax.device_put``) is applied to chunk ``k+1`` while the consumer
    computes on chunk ``k`` — JAX dispatch is async, so merely HOLDING the
    placed-but-unconsumed chunks keeps their host→device transfers in
    flight. Two drive modes:

    * ``threaded=False`` (dl default): a synchronous lookahead deque —
      exactly the seed ``_prefetch`` semantics, no thread, transfers overlap
      through async dispatch alone.
    * ``threaded=True`` (gbdt streaming): a named non-daemon producer thread
      pulls + places ahead of the consumer so the HOST side of a transfer
      (pageable-memory copy, binning, decompression) also overlaps compute.
      The thread is joined on EVERY exit path — ``__iter__`` closes the pump
      in a ``finally`` so early consumer exits (break, error, preemption)
      cannot leak it (tools/analysis resource-discipline scope).

    Every chunk boundary is a :func:`~synapseml_tpu.core.checkpoint.
    preemption_point` and an elastic-watchdog heartbeat (``phase=...``), so
    the pump composes with the PR 2 checkpoint machinery and the PR 10
    watchdogs for free: a ``ChaosPreemption`` kill lands BETWEEN chunks, the
    producer is joined, and the consumer's snapshot/resume contract applies.

:func:`pump_polling`
    The drain-poll skeleton the online loops run: drive a DESTRUCTIVE
    ``step()`` (e.g. ``FeedbackLog.drain`` + update) until ``stop`` is set,
    sleeping ``interval`` when idle. Deliberately NOT a lookahead pump:
    draining is destructive, and pre-draining in a producer thread would
    break the preemption-before-drain invariant (a kill at the update
    boundary must lose no event) — so the shared layer offers the polling
    shape as a first-class primitive instead of forcing lookahead on it.

Chunk geometry (:func:`stream_chunk_rows` / :func:`stream_depth`) resolves
explicit arg > ``SYNAPSEML_TPU_STREAM_CHUNK_ROWS`` / ``_STREAM_DEPTH`` env >
a one-time host→device bandwidth micro-probe recorded in the ``core/tuned.py``
measurement store, capped by the ``SYNAPSEML_TPU_STREAM_MEM_BUDGET`` byte
budget (the knob the out-of-core bench uses to simulate a 10x-undersized
device). See docs/out-of-core.md.
"""

from __future__ import annotations

import mmap as _mmap
import os
import queue
import threading
import time
from collections import deque
from typing import Callable, Iterable, Optional

import numpy as np

# Chunk-corruption hook for the chaos suite (testing/chaos.py installs it):
# called as hook(k, chunk) -> chunk on the PRODUCER side before placement, so
# an injected delay/truncation/kill exercises the exact path a slow or dying
# data source would. Same single-global-hook pattern as dl.trainer's
# _CHAOS_BATCH_HOOK.
_CHAOS_CHUNK_HOOK = None

# Disk-read corruption hook (testing/chaos.py installs it): called as
# hook(k, arr) -> arr on every chunk READ FROM DISK (DiskChunkSource and the
# StreamedDataset cache_dir readback) — a separate global from
# _CHAOS_CHUNK_HOOK so a disk fault does not double-fire through the pump's
# chunk hook. The hook may return a truncated array (torn read) or raise
# OSError(EIO) (dying disk); both surface loudly at the consumer.
_CHAOS_DISK_HOOK = None

_DONE = object()     # end-of-stream sentinel on the producer queue


class ChunkStreamError(RuntimeError):
    """The producer died mid-stream (source raised, or chaos killed it);
    re-raised on the consumer side at the next chunk boundary."""


class ChunkPump:
    """Bounded-depth host→device chunk pipeline over ``source``.

    ``source``: any iterable of host chunks. ``place``: chunk -> placed
    chunk (``jax.device_put`` / sharding; identity when None). ``depth``:
    chunks placed AHEAD of the one being consumed (double-buffering = 1+).
    ``phase``: when set, each boundary fires ``preemption_point(phase,
    step_base + k)`` and beats the installed elastic watchdog — the
    composition contract chaos tests rely on. ``step_base`` keeps boundary
    steps globally monotonic across the many pumps one training run opens
    (each level pass is a fresh pump), so a chaos kill targets a unique
    boundary.
    """

    def __init__(self, source: Iterable, place: Optional[Callable] = None,
                 depth: int = 2, threaded: bool = False,
                 phase: Optional[str] = None, step_base: int = 0,
                 name: str = "ingest"):
        if depth < 1:
            raise ValueError(f"depth must be >= 1, got {depth}")
        self._source = iter(source)
        self._place = place if place is not None else (lambda c: c)
        self.depth = int(depth)
        self.threaded = bool(threaded)
        self.phase = phase
        self.step_base = int(step_base)
        self.name = name
        self.chunks_produced = 0     # pulled from source (producer side)
        self.chunks_consumed = 0     # yielded to the consumer
        self._q: queue.Queue = queue.Queue(maxsize=self.depth)
        self._stop = threading.Event()
        self._err: Optional[BaseException] = None
        self._thread: Optional[threading.Thread] = None
        self._closed = False

    # -- producer side ----------------------------------------------------
    def _pull(self):
        """One produce step: next source chunk → chaos hook → place."""
        try:
            chunk = next(self._source)
        except StopIteration:
            return _DONE
        hook = _CHAOS_CHUNK_HOOK
        if hook is not None:
            chunk = hook(self.chunks_produced, chunk)
        # producer-private while the pump thread runs; the consumer only
        # reads it after _DONE arrives through _q, and the queue put/get
        # pair is the happens-before edge
        self.chunks_produced += 1  # lint-ok: thread-shared queue handoff
        return self._place(chunk)

    def _produce(self) -> None:
        try:
            while not self._stop.is_set():
                item = self._pull()
                if item is _DONE:
                    break
                while not self._stop.is_set():
                    try:
                        self._q.put(item, timeout=0.05)
                        break
                    except queue.Full:
                        continue
        except BaseException as e:  # noqa: BLE001 — ferried to the consumer
            # written before the finally-block puts _DONE; the consumer
            # reads it only after get() returns _DONE, so the queue
            # handoff publishes the error
            self._err = e  # lint-ok: thread-shared queue handoff
        finally:
            # always deliver end-of-stream; close() drains concurrently so
            # this can never deadlock against a vanished consumer
            while not self._stop.is_set():
                try:
                    self._q.put(_DONE, timeout=0.05)
                    break
                except queue.Full:
                    continue

    def _start(self) -> None:
        if self._thread is None and not self._closed:
            self._thread = threading.Thread(
                target=self._produce, name=f"chunk-pump.{self.name}")
            self._thread.start()

    def _sync_pull(self):
        """``_pull`` under the threaded-mode error contract: source/place
        failures surface as :class:`ChunkStreamError` in BOTH modes, so
        consumers never care which side of the thread the producer ran on."""
        try:
            return self._pull()
        except BaseException as e:  # noqa: BLE001 — same contract as _produce
            raise ChunkStreamError(
                f"chunk producer {self.name!r} died at chunk "
                f"{self.chunks_produced}: {e!r}") from e

    # -- consumer side ----------------------------------------------------
    def _boundary(self) -> None:
        """Chunk boundary: preemption point + watchdog heartbeat."""
        step = self.step_base + self.chunks_consumed
        if self.phase is not None:
            from ..core.checkpoint import preemption_point

            preemption_point(self.phase, step)
        from ..parallel.elastic import current_watchdog

        wd = current_watchdog()
        if wd is not None:
            wd.beat(self.phase or self.name, step)

    def __iter__(self):
        try:
            if self.threaded:
                self._start()
                while True:
                    item = self._q.get()
                    if item is _DONE:
                        if self._err is not None:
                            raise ChunkStreamError(
                                f"chunk producer {self.name!r} died at chunk "
                                f"{self.chunks_produced}: {self._err!r}"
                            ) from self._err
                        return
                    self._boundary()
                    yield item
                    self.chunks_consumed += 1
            else:
                # synchronous lookahead (the seed dl _prefetch semantics):
                # refill BEFORE yielding so the next transfer is dispatched
                # while the consumer computes on the popped chunk
                q: deque = deque()
                while len(q) < self.depth:
                    item = self._sync_pull()
                    if item is _DONE:
                        break
                    q.append(item)
                while q:
                    out = q.popleft()
                    item = self._sync_pull()
                    if item is not _DONE:
                        q.append(item)
                    self._boundary()
                    yield out
                    self.chunks_consumed += 1
        finally:
            self.close()

    def close(self) -> None:
        """Stop the producer and JOIN it (idempotent; called from every
        ``__iter__`` exit path and from ``__exit__``). The queue is drained
        while joining so a blocked ``put`` can never wedge the join."""
        self._stop.set()
        t = self._thread
        while t is not None and t.is_alive():
            try:
                self._q.get_nowait()
            except queue.Empty:
                pass
            t.join(0.05)
        self._thread = None
        self._closed = True

    def __enter__(self) -> "ChunkPump":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


def pump_polling(step: Callable[[], bool], stop: threading.Event,
                 interval: float,
                 on_error: Optional[Callable[[Exception], None]] = None
                 ) -> None:
    """Drive a destructive drain ``step`` until ``stop`` is set.

    ``step() -> bool`` returns whether it did work; idle iterations wait
    ``interval`` on the stop event. ``Exception`` from a step is routed to
    ``on_error`` (count + keep draining — a poisoned batch must not kill the
    loop); ``BaseException`` (notably ``PreemptionError``) propagates and
    kills the loop like a real SIGTERM would. This is the online loops'
    ``_run`` body hoisted into the shared ingestion layer — the polling
    shape, NOT a lookahead pump, because the step's drain is destructive and
    must stay behind its own preemption point."""
    while not stop.is_set():
        try:
            worked = step()
        except Exception as e:  # noqa: BLE001 — loop must outlive bad input
            if on_error is not None:
                on_error(e)
            worked = False
        if not worked:
            stop.wait(interval)


# ---------------------------------------------------------------------------
# Chunk geometry: explicit > env > measured micro-probe
# ---------------------------------------------------------------------------

_PROBE_BYTES = 4 << 20         # one device_put of 4 MiB prices the link
_TARGET_CHUNK_S = 8e-3         # chunk ≈ 8 ms of transfer: deep enough to
                               # amortize dispatch, shallow enough that
                               # depth×chunk stays a sliver of device memory
_MIN_CHUNK_ROWS = 1024
_MAX_CHUNK_ROWS = 1 << 20
_FALLBACK_CHUNK_ROWS = 65536


def _probe_h2d_bandwidth() -> float:
    """Measured host→device bytes/s (one-time; cached in the core/tuned.py
    measurement store under ``("h2d_bytes_per_s", platform)``)."""
    import jax
    import numpy as np

    buf = np.zeros(_PROBE_BYTES, np.uint8)
    jax.device_put(buf[:1024]).block_until_ready()      # warm the path
    t0 = time.perf_counter()
    jax.device_put(buf).block_until_ready()
    dt = max(time.perf_counter() - t0, 1e-9)
    return _PROBE_BYTES / dt


def mem_budget_bytes() -> Optional[int]:
    """The simulated device-memory cap for streaming chunk state
    (``SYNAPSEML_TPU_STREAM_MEM_BUDGET``, bytes), or None. The out-of-core
    bench sets this to dataset_bytes/10 to prove ≥10x-beyond-memory
    training on CPU hosts that have no real HBM wall."""
    v = os.environ.get("SYNAPSEML_TPU_STREAM_MEM_BUDGET")
    if not v:
        return None
    return max(int(v), 1)


_LAST_CHUNK_DECISION = None


def last_chunk_decision():
    """Provenance dict of the most recent model-resolved chunk geometry
    (``core.perfmodel.suggest_chunk_rows``), or None when the probe branch
    has not run (explicit/env bypass) or the model was unavailable."""
    return _LAST_CHUNK_DECISION


def _perfmodel_chunk_rows(row_bytes: int, depth: int, fallback_rows: int,
                          h2d_bps) -> int:
    global _LAST_CHUNK_DECISION
    try:
        from ..core import perfmodel

        rows, dec = perfmodel.suggest_chunk_rows(
            row_bytes, int(depth), int(fallback_rows), h2d_bps=h2d_bps)
        _LAST_CHUNK_DECISION = dec.provenance()
        return int(rows)
    except Exception:
        return int(fallback_rows)


def stream_chunk_rows(row_bytes: int, explicit: Optional[int] = None,
                      depth: int = 2,
                      read_bps: Optional[float] = None) -> int:
    """Rows per streamed chunk for rows of ``row_bytes`` each.

    Resolution: ``explicit`` arg > ``SYNAPSEML_TPU_STREAM_CHUNK_ROWS`` env >
    bandwidth micro-probe (chunk ≈ ``_TARGET_CHUNK_S`` of measured link
    time). ``read_bps``, when given (disk-backed sources), is the measured
    disk read bandwidth: a chunk crosses disk→host then host→device
    serially, so the probe branch prices the HARMONIC combination of the two
    links rather than the h2d link alone. Whatever wins is then capped so
    ``(depth+1)`` in-flight chunks fit the
    ``SYNAPSEML_TPU_STREAM_MEM_BUDGET`` byte budget when one is set."""
    from ..core import tuned as _tuned

    global _LAST_CHUNK_DECISION
    _LAST_CHUNK_DECISION = None   # set again iff the probe branch runs
    row_bytes = max(int(row_bytes), 1)
    rows = explicit
    if rows is None:
        env = os.environ.get("SYNAPSEML_TPU_STREAM_CHUNK_ROWS")
        if env:
            rows = int(env)
    if rows is None:
        plat = _tuned.initialized_platform()
        bw = None
        if plat is None:
            rows = _FALLBACK_CHUNK_ROWS
        else:
            bw = _tuned.measured_or(("h2d_bytes_per_s", plat),
                                    _probe_h2d_bandwidth)
            if read_bps:
                # disk feeds the link back-to-back per chunk: effective
                # bytes/s is the series combination of the two stages
                bw = 1.0 / (1.0 / bw + 1.0 / float(read_bps))
            rows = int(bw * _TARGET_CHUNK_S / row_bytes)
        # the [min, max] clamp disciplines only the PROBE estimate — an
        # explicit/env value is operator intent and wins as given
        rows = min(max(rows, _MIN_CHUNK_ROWS), _MAX_CHUNK_ROWS)
        # recorded io_chunk_rows rows (bench_oocore_gbdt) can displace the
        # probe formula; without a measured match the formula IS the model's
        # analytic optimum, so this is identity
        rows = _perfmodel_chunk_rows(row_bytes, depth, rows, bw)
    rows = max(int(rows), 1)
    budget = mem_budget_bytes()
    if budget is not None:
        cap = budget // (row_bytes * (int(depth) + 1))
        rows = max(min(rows, cap), 1)
    return rows


def stream_depth(explicit: Optional[int] = None) -> int:
    """In-flight chunk depth: explicit > ``SYNAPSEML_TPU_STREAM_DEPTH`` env >
    2 (double buffering)."""
    if explicit is not None:
        return max(int(explicit), 1)
    env = os.environ.get("SYNAPSEML_TPU_STREAM_DEPTH")
    if env:
        return max(int(env), 1)
    return 2


# ---------------------------------------------------------------------------
# Disk-backed chunk source: mmap'd .npy / raw-uint8 reader
# ---------------------------------------------------------------------------

def _disk_hook(k, arr):
    hook = _CHAOS_DISK_HOOK
    return arr if hook is None else hook(k, arr)


def _npy_header(f):
    """``(shape, dtype, data_offset)`` of an open ``.npy`` file (versions
    1.0/2.0, C-order only — the layouts ``np.save`` actually writes)."""
    version = np.lib.format.read_magic(f)
    if version == (1, 0):
        shape, fortran, dtype = np.lib.format.read_array_header_1_0(f)
    elif version == (2, 0):
        shape, fortran, dtype = np.lib.format.read_array_header_2_0(f)
    else:
        raise ValueError(f"unsupported .npy format version {version}")
    if fortran:
        raise ValueError(".npy file is Fortran-ordered; the disk chunk "
                         "source needs C-order rows")
    return shape, dtype, f.tell()


def _probe_disk_bandwidth(path: str) -> float:
    """Measured disk→host bytes/s for ``path``'s filesystem: one sequential
    read of up to ``_PROBE_BYTES``. An upper bound when the page cache is
    warm — acceptable, because a warm cache means the disk stage genuinely
    is that fast for this stream."""
    n = min(os.path.getsize(path), _PROBE_BYTES)
    t0 = time.perf_counter()
    with open(path, "rb") as f:
        f.read(max(int(n), 1))
    dt = max(time.perf_counter() - t0, 1e-9)
    return max(int(n), 1) / dt


def read_chunk_file(path: str, k: int = 0):
    """Read one whole cached ``.npy`` chunk file through the chaos disk hook
    — the training-time readback path for ``StreamedDataset(cache_dir=...)``
    spilled chunks. Returns a fresh host array (never a live mmap view)."""
    with open(path, "rb") as f:
        shape, dtype, off = _npy_header(f)
        mm = _mmap.mmap(f.fileno(), 0, access=_mmap.ACCESS_READ)
        flat = np.frombuffer(mm, dtype=dtype,
                             count=int(np.prod(shape)), offset=off)
        try:
            out = np.array(flat.reshape(shape))
        finally:
            # frombuffer holds an exported pointer into the map: drop it
            # before close() or it raises BufferError
            del flat
            mm.close()
    return _disk_hook(int(k), out)


class DiskChunkSource:
    """Memory-mapped on-disk chunk reader — host RAM stops being the ceiling.

    A callable usable directly as ``StreamedDataset(batches=...)``: each call
    opens ``path``, maps it read-only, and yields ``(X, y, w)`` row-chunk
    tuples (``y``/``w`` are ``None`` unless ``labels``/``weights`` arrays
    were given — labels are 1/F the stream and stay in RAM). Two layouts:

    * ``.npy`` (default): header parsed for shape/dtype; must be a C-order
      2-D ``(rows, features)`` array.
    * raw: a headerless binary of ``rows × num_features`` elements of
      ``dtype`` (default uint8) — pass ``num_features`` (and ``dtype`` for
      non-uint8), set ``raw=True``.

    Each yielded chunk is COPIED out of the map (the map is closed when the
    generator exits, so no view may escape), and routed through the chaos
    disk hook so the fault suite can inject torn reads / EIO exactly where a
    real disk would. ``read_bytes_per_s`` is a cached one-time sequential
    micro-probe of the backing filesystem; ``StreamedDataset.prepare`` folds
    it into the chunk-geometry pricing.
    """

    def __init__(self, path: str, rows_per_chunk: int = _FALLBACK_CHUNK_ROWS,
                 raw: bool = False, num_features: Optional[int] = None,
                 dtype=None, labels=None, weights=None):
        self.path = os.fspath(path)
        self.rows_per_chunk = max(int(rows_per_chunk), 1)
        self.raw = bool(raw)
        self.labels = labels
        self.weights = weights
        if self.raw:
            if num_features is None:
                raise ValueError("raw disk source needs num_features")
            self._dtype = np.dtype(dtype if dtype is not None else np.uint8)
            itemsize = self._dtype.itemsize * int(num_features)
            n = os.path.getsize(self.path) // itemsize
            self._shape = (int(n), int(num_features))
            self._offset = 0
        else:
            if num_features is not None or dtype is not None:
                raise ValueError("num_features/dtype are raw-layout knobs; "
                                 ".npy files carry their own header")
            with open(self.path, "rb") as f:
                shape, dt, off = _npy_header(f)
            if len(shape) != 2:
                raise ValueError(f".npy disk source must be 2-D (rows, "
                                 f"features), got shape {shape}")
            self._shape, self._dtype, self._offset = shape, dt, off
        self.n_rows, self.num_features = int(self._shape[0]), int(self._shape[1])
        self._read_bps: Optional[float] = None

    @property
    def read_bytes_per_s(self) -> float:
        if self._read_bps is None:
            from ..core import tuned as _tuned

            plat = _tuned.initialized_platform()
            if plat is not None:
                self._read_bps = float(_tuned.measured_or(
                    ("disk_read_bytes_per_s", plat),
                    lambda: _probe_disk_bandwidth(self.path)))
            else:
                self._read_bps = _probe_disk_bandwidth(self.path)
        return self._read_bps

    def __call__(self):
        n, F, R = self.n_rows, self.num_features, self.rows_per_chunk
        f = open(self.path, "rb")
        try:
            mm = _mmap.mmap(f.fileno(), 0, access=_mmap.ACCESS_READ)
            flat = np.frombuffer(mm, dtype=self._dtype,
                                 count=n * F, offset=self._offset)
            arr = flat.reshape(n, F)
            try:
                for k, a in enumerate(range(0, n, R)):
                    X = _disk_hook(k, np.array(arr[a:a + R]))
                    c = int(X.shape[0])       # hook may tear the read short
                    sl = slice(a, a + c)
                    y = None if self.labels is None else self.labels[sl]
                    w = None if self.weights is None else self.weights[sl]
                    yield (X, y, w)
            finally:
                # frombuffer holds an exported pointer into the map: drop
                # every view before close() or it raises BufferError
                del flat, arr
                mm.close()
        finally:
            f.close()
