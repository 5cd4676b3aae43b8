"""Measured engine defaults: the tune→flip→bench loop's persistence layer.

The GBDT engine ships several hot-loop designs whose relative speed is a
property of the chip, not the code (docs/perf_notes.md). ``tools/perf_tune.py``
measures them ON REAL TPU and writes the winner to ``docs/tuned_defaults.json``;
this module is the read side consumed by ``BoosterConfig`` /
``ops.hist_kernel`` default resolution, so one tune pass flips the shipped
defaults for every subsequent run — no code edit, no human in the loop.

Precedence (highest wins): explicit constructor arg > ``SYNAPSEML_TPU_*`` env
var > tuned file > hardcoded fallback.

The tuned file is applied ONLY when the current process is actually running
the TPU backend: the measurements are chip facts, and CPU tests must not
change behavior based on a mutable artifact. The backend check never
*initializes* a backend: a chip belongs to one process, and constructing a
config object in a launcher must not take it from the child that will train.
An uninitialized backend reads as "not TPU" and the fallback wins; the
training path initializes jax first and ``BoosterConfig`` re-resolves then,
so the file takes effect exactly where it is valid.

Reference analog: LightGBM ships per-device tuned kernel parameters the same
way (its GPU tree learner's auto-tuned work-group sizes); the reference's JVM
layer has no equivalent because its native binaries are pre-tuned.
"""

from __future__ import annotations

import functools
import json
import os
from typing import Optional

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_PATH = os.path.join(_REPO, "docs", "tuned_defaults.json")

# keys a tuned file may set, with the values the engine accepts — the write
# side (tools/perf_tune.py) and read side (BoosterConfig.__post_init__)
# validate against the same table, so a corrupt/hand-edited file fails loud
ALLOWED = {
    "partition_impl": ("sort", "sort32", "scan", "scatter"),
    "row_layout": ("partition", "masked", "gather"),
    "use_segmented": (True, False),
    "hist_chunk": int,
    # features packed per MXU dot (ops/hist_kernel._pack_for clamps to the
    # tile constraints; the tuner pins this only on a measured win)
    "hist_pack": int,
    # out-of-core ingest geometry (io/ingest.py): rows per streamed chunk
    # and in-flight chunk depth, resolved env > tuned file > the h2d
    # bandwidth micro-probe recorded in the measurement store
    "stream_chunk_rows": int,
    "stream_depth": int,
}


def _path() -> str:
    return os.environ.get("SYNAPSEML_TPU_TUNED_DEFAULTS", DEFAULT_PATH)


def initialized_platform() -> Optional[str]:
    """The platform of an ALREADY-initialized jax backend ("tpu"/"cpu"/...),
    or None when no backend is initialized. Never initializes one (module
    docstring): ``jax.default_backend()`` would, and jax has no public
    "is a backend up?" query, hence the one private import — the single
    shared copy; bench.record_measurement uses it too."""
    from jax._src import xla_bridge

    if not xla_bridge.backends_are_initialized():
        return None
    import jax

    return jax.default_backend()


def backend_is_tpu() -> bool:
    return initialized_platform() == "tpu"


@functools.lru_cache(maxsize=4)
def _load(path: str) -> dict:
    # deliberate trace-time read: tuned defaults must be resolved while the
    # kernel is being built, and the lru_cache bounds it to once per path
    try:
        with open(path) as f:  # lint-ok: blocking-io
            data = json.load(f)
        return data if isinstance(data, dict) else {}
    except (OSError, json.JSONDecodeError):
        return {}


def _value_ok(key: str, v) -> bool:
    """Type-exact validity for one tuned value. bool is an int subclass, so
    both directions need explicit guards: hist_chunk=true must not become
    chunk=1, and use_segmented=1 must not pass as a bool."""
    allowed = ALLOWED[key]
    if allowed is int:
        return isinstance(v, int) and not isinstance(v, bool) and v > 0
    if all(isinstance(a, bool) for a in allowed):
        return isinstance(v, bool)
    return v in allowed


def validated_values(raw: dict) -> dict:
    """The subset of ``raw`` that is a known key with an in-range value —
    the single filter both the read side (tuned_engine_defaults) and the
    write-side merge (tools/perf_tune.py) apply, so a corrupt entry the
    reader silently drops can never crash a later merged write."""
    return {key: raw[key] for key in ALLOWED
            if key in raw and _value_ok(key, raw[key])}


def current_file_values(path: str = None) -> dict:
    """Validated values currently in the tuned file, ignoring provenance and
    the backend gate (for write-side merges and change detection)."""
    p = path or _path()
    if p in ("", "0", "off"):
        return {}
    return validated_values(_load(p))


def tuned_engine_defaults() -> dict:
    """The validated tuned-default mapping for THIS process, or {} when no
    file exists, the env disables it, or the backend is not (yet) TPU."""
    path = _path()
    if path in ("", "0", "off"):
        return {}
    if not backend_is_tpu():
        return {}
    return validated_values(_load(path))


def tuned_default(key: str, env_var: str, fallback):
    """One field's resolved default: env var > tuned file > fallback.
    String env values are returned as-is (validation happens in the consumer's
    __post_init__ so typos fail with a message naming the variable)."""
    v = os.environ.get(env_var)
    if v is not None and v != "":
        return v
    return tuned_engine_defaults().get(key, fallback)


# ---------------------------------------------------------------------------
# In-process measurement store. Unlike the tuned FILE above (chip facts,
# persisted, TPU-gated), these are probe results valid only for the current
# process+mesh — link bandwidth, selection timing — consumed by the
# distributed-GBDT router and core/perfmodel. First caller pays the probe;
# later boosters on the same mesh read the cached number.
#
# Probe results computed by ``measured_or`` are additionally persisted to a
# small TTL'd disk cache (docs/probe_cache.json by default) so repeated CI
# runs on the same machine don't re-pay the probes. Keys embed the mesh
# fingerprint (device strings), so a cpu cache entry can never serve a tpu
# mesh. ``put_measurement`` deliberately does NOT persist: it is the test
# injection hook, and an injected fake must never leak across processes.
# ---------------------------------------------------------------------------

_MEASUREMENTS: dict = {}

PROBE_CACHE_PATH = os.path.join(_REPO, "docs", "probe_cache.json")
PROBE_CACHE_TTL_S = 24 * 3600.0


def _probe_cache_path() -> Optional[str]:
    p = os.environ.get("SYNAPSEML_TPU_PROBE_CACHE", PROBE_CACHE_PATH)
    return None if p in ("", "0", "off") else p


def _probe_cache_ttl() -> float:
    try:
        return float(os.environ.get("SYNAPSEML_TPU_PROBE_CACHE_TTL_S",
                                    PROBE_CACHE_TTL_S))
    except ValueError:
        return PROBE_CACHE_TTL_S


def _key_str(key) -> str:
    """Canonical string form of a (possibly nested-tuple) cache key."""
    def listify(k):
        if isinstance(k, (tuple, list)):
            return [listify(x) for x in k]
        return k
    try:
        return json.dumps(listify(key), sort_keys=True)
    except (TypeError, ValueError):
        return repr(key)


def _read_probe_cache(path: str) -> dict:
    try:
        with open(path) as f:  # host-side cache read, never under trace
            data = json.load(f)
        return data if isinstance(data, dict) else {}
    except (OSError, json.JSONDecodeError):
        return {}


def _disk_probe_get(key):
    """A fresh (within-TTL) persisted probe value, or None."""
    path = _probe_cache_path()
    if path is None:
        return None
    entry = _read_probe_cache(path).get(_key_str(key))
    if not isinstance(entry, dict) or "value" not in entry:
        return None
    import time
    try:
        if time.time() - float(entry.get("ts", 0)) > _probe_cache_ttl():
            return None
    except (TypeError, ValueError):
        return None
    return entry["value"]


def _disk_probe_put(key, value) -> None:
    path = _probe_cache_path()
    if path is None:
        return
    try:
        json.dumps(value)
    except (TypeError, ValueError):
        return  # only JSON-representable probe results persist
    import time
    try:
        cache = _read_probe_cache(path)
        cache[_key_str(key)] = {"value": value, "ts": time.time()}
        tmp = f"{path}.{os.getpid()}.tmp"
        with open(tmp, "w") as f:
            json.dump(cache, f, indent=1, sort_keys=True)
            f.write("\n")
        os.replace(tmp, path)
    except OSError:
        pass  # persistence is best-effort; the in-process cache still holds


def mesh_fingerprint(mesh) -> tuple:
    """Hashable identity of a mesh for probe caching: axis layout plus the
    participating device strings (stable across Mesh-object recreation in one
    process, distinct across different device subsets)."""
    axes = tuple((str(k), int(v)) for k, v in dict(mesh.shape).items())
    devs = tuple(str(d) for d in mesh.devices.flat)
    return axes + devs


def measured_or(key, compute):
    """Get-or-measure: return the cached value for ``key``, running
    ``compute()`` (and caching its result) on the first call. Keys should
    start with a metric name and include ``mesh_fingerprint(mesh)``.
    Computed results also land in the TTL'd disk cache; a fresh persisted
    value short-circuits the probe entirely."""
    if key not in _MEASUREMENTS:
        persisted = _disk_probe_get(key)
        if persisted is not None:
            _MEASUREMENTS[key] = persisted
        else:
            _MEASUREMENTS[key] = compute()
            _disk_probe_put(key, _MEASUREMENTS[key])
    return _MEASUREMENTS[key]


def get_measurement(key, default=None):
    return _MEASUREMENTS.get(key, default)


def put_measurement(key, value) -> None:
    _MEASUREMENTS[key] = value


def clear_measurements() -> None:
    """Test hook: forget all probe results (forces re-measurement). Clears
    the persisted disk cache too — "clear" must mean the next probe really
    runs, not that it is re-read from disk."""
    _MEASUREMENTS.clear()
    path = _probe_cache_path()
    if path is not None:
        try:
            os.remove(path)
        except OSError:
            pass


def write_tuned_defaults(values: dict, provenance: dict,
                         path: str = None) -> Optional[str]:
    """Write the measured winners atomically (tmp + replace). Unknown keys
    and out-of-range values are refused — the write side enforces the same
    table the read side trusts. Returns the path written, or None when the
    operator disabled the mechanism (SYNAPSEML_TPU_TUNED_DEFAULTS=0) — the
    write side honors the same sentinel the read side checks."""
    path = path or _path()
    if path in ("", "0", "off"):
        return None
    clean = {}
    for key, v in values.items():
        allowed = ALLOWED.get(key)
        if allowed is None:
            raise ValueError(f"unknown tuned-default key: {key!r}")
        if not _value_ok(key, v):
            want = ("positive int (not bool)" if allowed is int
                    else f"one of {allowed} (type-exact)")
            raise ValueError(f"tuned default {key}={v!r}: want {want}")
        clean[key] = v
    clean["provenance"] = dict(provenance)
    tmp = f"{path}.{os.getpid()}.tmp"
    with open(tmp, "w") as f:
        json.dump(clean, f, indent=1, sort_keys=True)
        f.write("\n")
    os.replace(tmp, path)
    _load.cache_clear()
    return path
