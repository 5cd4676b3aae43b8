"""Test harness: in-process SPMD on a virtual 8-device CPU mesh.

The analog of the reference's `local[*]` TestBase (core/.../core/test/base/
TestBase.scala:28-104): Spark local mode runs N partition-tasks in one JVM, which
exercises the whole distributed path without a cluster; here a forked CPU
platform with 8 XLA host devices exercises mesh sharding + collectives without a
TPU pod (SURVEY.md §4 "implication for the rebuild").

MUST run before any jax import: sets XLA_FLAGS and pins the platform to cpu
(unit tests never use the chip; tests/test_tpu_e2e.py under
SYNAPSEML_TPU_E2E=1 is the exception and skips the pin).
"""

import os
import tempfile

# tests probe on virtual cpu meshes and sometimes inject fake probe values;
# none of that may land in (or be served from) the repo's persisted probe
# cache, so every test session gets a throwaway cache file
os.environ["SYNAPSEML_TPU_PROBE_CACHE"] = os.path.join(
    tempfile.mkdtemp(prefix="synapseml-tpu-test-probes."), "probe_cache.json")
# perfmodel training rows likewise: test workloads must rank against rows
# they wrote themselves, never against the committed bench journal
os.environ["SYNAPSEML_TPU_PERF_ROWS"] = os.path.join(
    tempfile.mkdtemp(prefix="synapseml-tpu-test-perfrows."), "rows.jsonl")

_TPU_E2E = os.environ.get("SYNAPSEML_TPU_E2E") == "1"
if not _TPU_E2E:
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8")
    os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402

if not _TPU_E2E:
    jax.config.update("jax_platforms", "cpu")
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from synapseml_tpu.core.compile_cache import enable_compile_cache  # noqa: E402

# persistent executable cache: repeat suite runs skip XLA recompiles
enable_compile_cache()

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session", autouse=True)
def _lock_witness():
    """Opt-in runtime lock-order witness: when SYNAPSEML_TPU_LOCK_WITNESS
    names a report path, wrap every project lock created during the session
    and write the observed acquisition-order graph at exit.
    `python -m synapseml_tpu.testing.lockwitness <report>` diffs it against
    the static lock-order graph (docs/static-analysis.md)."""
    path = os.environ.get("SYNAPSEML_TPU_LOCK_WITNESS")
    if not path:
        yield
        return
    from synapseml_tpu.testing.lockwitness import LockWitness

    witness = LockWitness().install()
    try:
        yield
    finally:
        witness.uninstall()
        witness.write(path)


@pytest.fixture(scope="session", autouse=True)
def _dtype_witness():
    """Opt-in runtime dtype witness: when SYNAPSEML_TPU_DTYPE_WITNESS names
    a report path, activate the `_witness_observe` probes in the product
    modules and write the observed per-site dtype sets (plus any expect=
    contract violations) at exit.
    `python -m synapseml_tpu.testing.dtypewitness <report>` diffs it against
    the static dtype-flow prediction (tools/analysis/dtypemodel.py)."""
    path = os.environ.get("SYNAPSEML_TPU_DTYPE_WITNESS")
    if not path:
        yield
        return
    from synapseml_tpu.testing.dtypewitness import DtypeWitness

    witness = DtypeWitness().install()
    try:
        yield
    finally:
        witness.uninstall()
        witness.write(path)


@pytest.fixture(scope="session")
def eight_devices():
    devs = jax.devices()
    if len(devs) < 8:
        pytest.skip("needs 8 virtual CPU devices (XLA_FLAGS not applied early enough)")
    return devs[:8]


@pytest.fixture(scope="session")
def binary_data():
    from sklearn.datasets import load_breast_cancer
    from sklearn.model_selection import train_test_split

    X, y = load_breast_cancer(return_X_y=True)
    return train_test_split(X.astype(np.float32), y.astype(np.float32),
                            test_size=0.3, random_state=42)


@pytest.fixture(scope="session")
def regression_data():
    from sklearn.datasets import load_diabetes
    from sklearn.model_selection import train_test_split

    X, y = load_diabetes(return_X_y=True)
    return train_test_split(X.astype(np.float32), y.astype(np.float32),
                            test_size=0.3, random_state=42)
