"""What start-up can observe: the platform query and the measurement store.

Two things other layers share. ``initialized_platform`` answers "which
backend is up?" WITHOUT bringing one up: a chip belongs to one
process, and a launcher that only builds a config must not take it from the
child that will train. The in-process measurement store (``measured_or`` …)
caches micro-probe results (link bandwidth, host-to-device bandwidth,
selection timing) per process and, with a TTL, on disk.

Nothing here chooses an engine default: the grower decides its split step
from the backend and the static shapes (gbdt/grower.py), and the remaining
``SYNAPSEML_TPU_*`` knobs are read where they are used.
"""

from __future__ import annotations

import json
import os
from typing import Optional

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


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


# ---------------------------------------------------------------------------
# In-process measurement store: probe results valid only for the current
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
