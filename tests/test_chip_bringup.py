"""What the first run on the chip changed, checked on the CPU.

* On the TPU backend a kernel that cannot compile, or disagrees with its
  reference, raises ``KernelError`` naming the kernel — no gate returns
  ``False``/``"xla"`` and no caller quietly takes the XLA path.
* All six Pallas kernels compile for a chip-less ``v5e:2x2`` topology (this
  installation's libtpu compiles without a device; interpret mode, which the
  other kernel tests use, does not check tiling).
* The compile cache can be placed from outside.
* ``chip_smoke.py`` and ``bench.py`` refuse to report without a TPU, and
  ``bench.py``'s parent never touches jax before starting a child.
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args, cwd=REPO, env=None, timeout=300):
    e = dict(os.environ, JAX_PLATFORMS="cpu")
    e.pop("XLA_FLAGS", None)
    e.update(env or {})
    return subprocess.run([sys.executable] + args, cwd=cwd, env=e,
                          capture_output=True, text=True, timeout=timeout)


# ---------------------------------------------------------------------------
# kernel gates raise on the TPU backend
# ---------------------------------------------------------------------------

@pytest.fixture
def backend_says_tpu(monkeypatch):
    """The CPU cannot lower a TPU kernel, so with the backend reported as
    TPU every kernel is 'a kernel made to fail'."""
    from synapseml_tpu.ops import attention_kernel as ak
    from synapseml_tpu.ops import hist_kernel as hk
    from synapseml_tpu.ops import partition_kernel as pk

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cached = (hk._check_hist_kernel, hk._check_range_kernel,
              hk._check_level_kernel, ak._check_flash_kernel,
              ak._check_flash_block_kernel, pk._check_partition_kernel)
    for c in cached:
        c.cache_clear()
    yield
    for c in cached:
        c.cache_clear()


def _hist_args(n=4096, fp=8, b=256):
    rng = np.random.default_rng(0)
    return (jnp.asarray(rng.integers(0, b, size=(fp, n)), jnp.int32),
            jnp.asarray(rng.normal(size=n), jnp.float32),
            jnp.ones(n, jnp.float32), jnp.ones(n, jnp.float32))


def _qkv(s=256, h=4):
    rng = np.random.default_rng(0)
    return tuple(jnp.asarray(rng.normal(size=(1, s, h, 64)), jnp.float32)
                 for _ in range(3))


def _gates():
    from synapseml_tpu.ops import attention_kernel as ak
    from synapseml_tpu.ops import hist_kernel as hk
    from synapseml_tpu.ops import partition_kernel as pk
    from synapseml_tpu.parallel import make_mesh
    from synapseml_tpu.parallel.ring_attention import ring_self_attention
    from synapseml_tpu.parallel.ulysses import ulysses_self_attention

    mesh = make_mesh({"data": 1, "seq": 4}, devices=jax.devices()[:4])
    return {
        "child_histogram": ("_hist_pallas", lambda: hk.child_histogram(
            *_hist_args(), 256)),
        "segmented_histograms_available": (
            "_hist_pallas_range",
            lambda: hk.segmented_histograms_available(256)),
        "partition_kernel_available": (
            "stable_partition_rows",
            lambda: pk.partition_kernel_available(256, 32)),
        "level_histograms": ("_hist_pallas_level", lambda: hk.level_histograms(
            *_hist_args(), jnp.asarray([0, 1], jnp.int32),
            jnp.zeros(4096, jnp.int32), 256, 2)),
        "flash_attention": ("_flash_forward",
                            lambda: ak.flash_attention(*_qkv())),
        "ring_self_attention": ("flash_attention_block",
                                lambda: ring_self_attention(*_qkv(), mesh)),
        "ulysses_self_attention": ("_flash_forward",
                                   lambda: ulysses_self_attention(*_qkv(),
                                                                  mesh)),
    }


@pytest.mark.parametrize("gate", ["child_histogram",
                                  "segmented_histograms_available",
                                  "partition_kernel_available",
                                  "level_histograms", "flash_attention",
                                  "ring_self_attention",
                                  "ulysses_self_attention"])
def test_gate_raises_and_names_the_kernel(backend_says_tpu, gate):
    from synapseml_tpu.ops.hist_kernel import KernelError

    kernel, call = _gates()[gate]
    with pytest.raises(KernelError) as ei:
        call()
    msg = str(ei.value)
    assert f"Pallas kernel {kernel} (" in msg
    assert "failed to compile or run" in msg
    # static shapes and the compiler's own words are in the message
    assert "=" in msg.split("(", 1)[1].split(")", 1)[0]
    assert type(ei.value.__cause__).__name__ in msg


def test_disagreeing_kernel_raises(backend_says_tpu, monkeypatch):
    from synapseml_tpu.ops import hist_kernel as hk

    monkeypatch.setattr(hk, "_hist_pallas",
                        lambda bT, g, h, m, B, **_:
                        hk._hist_xla(bT, g, h, m, B) + 1.0)
    with pytest.raises(hk.KernelError,
                       match=r"_hist_pallas \(.*disagrees with its XLA "
                             r"reference.*max \|diff\| = 1"):
        hk.child_histogram(*_hist_args(), 256)


def test_explicit_env_choices_remain(backend_says_tpu, monkeypatch):
    """SYNAPSEML_TPU_SEGMENTED=0 / SYNAPSEML_TPU_LEVEL=0 are choices, taken
    without compiling the kernel they decline."""
    from synapseml_tpu.ops import hist_kernel as hk

    monkeypatch.setenv("SYNAPSEML_TPU_SEGMENTED", "0")
    assert hk.segmented_histograms_available(256) is False
    monkeypatch.setenv("SYNAPSEML_TPU_LEVEL", "0")
    bT, g, h, m = _hist_args()
    slot = jnp.asarray(np.arange(4096) // 2048, jnp.int32)
    got = hk.level_histograms(bT, g, h, m, jnp.asarray([0, 1], jnp.int32),
                              slot, 256, 2)
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(hk._hist_level_xla(bT, g, h, m, slot,
                                                       256, 2)))


# ---------------------------------------------------------------------------
# the kernels compile for the chip (no chip needed)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no libtpu in this environment
        pytest.skip(f"no chip-less TPU topology here: {e}")
    sh = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=sh)


@pytest.mark.parametrize("fp", [32, 136])
def test_hist_kernels_compile_for_v5e(v5e, fp):
    from synapseml_tpu.ops import hist_kernel as hk

    C, n = 2048, 8 * 2048
    rows = (v5e((fp, n), jnp.int32),) + (v5e((n,), jnp.float32),) * 3
    i32 = v5e((), jnp.int32)
    hk._hist_pallas.lower(*rows, 256, chunk=C).compile()
    hk._hist_pallas_range.lower(*rows, i32, i32, 256, 4 * C,
                                chunk=C).compile()
    hk._hist_pallas_level.lower(*rows, v5e((5,), jnp.int32), 256, 5,
                                chunk=C).compile()


@pytest.mark.parametrize("fp,bins,window", [(32, 256, 3_500_032),
                                            (32, 256, 4096),
                                            (136, 1024, 6144),
                                            (1024, 256, 6144),
                                            (1024, 1024, 6144),
                                            (2048, 256, 6144),
                                            (2048, 1024, 6144)])
def test_partition_kernel_compiles_for_v5e(v5e, fp, bins, window):
    """The benchmark cell's shapes (its smallest bucket and the whole
    table); a table whose last feature block is short, with two byte planes
    a bin; and tables 1,024 and 2,048 features wide, whose VMEM is one
    feature block's."""
    from synapseml_tpu.ops import partition_kernel as pk

    n = max(window, 8 * 2048)
    vec = lambda dt: v5e((n,), dt)
    pk._partition_pallas.lower(
        v5e((window,), jnp.bool_), v5e((), jnp.int32),
        v5e((fp, n), jnp.int32), vec(jnp.int32), vec(jnp.float32),
        vec(jnp.float32), vec(jnp.float32), num_bins_padded=bins,
        chunk=pk.partition_chunk(2048)).compile()


@pytest.mark.parametrize("shape,dt", [((1, 4096, 8, 64), jnp.bfloat16),
                                      ((2, 300, 4, 64), jnp.float32)])
@pytest.mark.parametrize("causal", [False, True])
def test_flash_kernels_compile_for_v5e(v5e, shape, dt, causal):
    import functools

    from synapseml_tpu.ops import attention_kernel as ak

    x = v5e(shape, dt)
    ak._flash_forward.lower(x, x, x, causal, 0.125, 128, 128,
                            False).compile()
    B, S, H, _ = shape
    ml, i32 = v5e((B, H, S), jnp.float32), v5e((), jnp.int32)
    jax.jit(functools.partial(ak.flash_attention_block, causal=causal,
                              scale=0.125)).lower(
        x, x, x, ml, ml, v5e(shape, jnp.float32), i32, i32).compile()


@pytest.mark.parametrize("boundaries,out_dtype", [(254, jnp.uint8),
                                                  (512, jnp.uint16)])
@pytest.mark.parametrize("has_categorical", [False, True])
def test_dense_binning_has_no_full_size_temporary_on_v5e(
        v5e, boundaries, out_dtype, has_categorical):
    """``apply_bins`` at the benchmark cell's size: the float32 rows in, the
    bins out, and beside them nothing of (N, F) in the device's memory."""
    from synapseml_tpu.ops.quantize import COMPARE_MAX_BOUNDARIES, _apply_bins

    assert boundaries <= COMPARE_MAX_BOUNDARIES
    n, f = 3_500_000, 28
    per_feature = lambda dt: v5e((f,), dt)
    compiled = _apply_bins.lower(
        v5e((n, f), jnp.float32), v5e((f, boundaries), jnp.float32),
        per_feature(jnp.int32), per_feature(jnp.int32),
        per_feature(jnp.bool_), per_feature(jnp.bool_), v5e((), jnp.float32),
        has_categorical=has_categorical, out_dtype=out_dtype).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < n * f


# ---------------------------------------------------------------------------
# compile cache placement
# ---------------------------------------------------------------------------

@pytest.fixture
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_cache_dir_untouched_when_env_places_it(monkeypatch,
                                                restore_cache_dir):
    from synapseml_tpu.core.compile_cache import enable_compile_cache
    from synapseml_tpu.core.inference import BucketedRunner

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/placed/outside")
    jax.config.update("jax_compilation_cache_dir", "/placed/outside")
    assert enable_compile_cache() == "/placed/outside"
    BucketedRunner(lambda x: x + 1.0, max_batch_size=2).warmup(
        np.zeros((1, 2), np.float32))
    assert jax.config.jax_compilation_cache_dir == "/placed/outside"


def test_cache_dir_fixed_when_env_unset(monkeypatch, restore_cache_dir):
    from synapseml_tpu.core import compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    jax.config.update("jax_compilation_cache_dir", None)
    assert compile_cache.enable_compile_cache() == os.path.join(REPO,
                                                                ".jax_cache")
    assert compile_cache.enable_compile_cache() == compile_cache.DEFAULT_DIR


def test_env_places_the_cache_in_a_fresh_process(tmp_path):
    """jax itself reads JAX_COMPILATION_CACHE_DIR; nothing overrides it."""
    r = _run(["-c", "from synapseml_tpu.core.compile_cache import "
                    "enable_compile_cache as e; import jax; print(e()); "
                    "print(jax.config.jax_compilation_cache_dir)"],
             env={"JAX_COMPILATION_CACHE_DIR": str(tmp_path)})
    assert r.returncode == 0, r.stderr
    assert r.stdout.split() == [str(tmp_path)] * 2


# ---------------------------------------------------------------------------
# no chip, no result
# ---------------------------------------------------------------------------

def test_chip_smoke_fails_on_cpu(tmp_path):
    r = _run([os.path.join(REPO, "chip_smoke.py"), "--out", str(tmp_path)])
    assert r.returncode != 0
    assert '"ok"' not in r.stdout and "no TPU" in r.stderr


def test_chip_smoke_alone_in_a_directory_fails(tmp_path):
    import shutil

    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    r = _run(["chip_smoke.py"], cwd=str(tmp_path), env={"PYTHONPATH": ""})
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


def test_bench_without_a_chip_reports_nothing():
    r = _run(["bench.py"])
    assert r.returncode != 0
    assert '"value"' not in r.stdout and "needs a TPU" in r.stderr
    r = _run(["bench.py", "--only", "bench_serving"])
    assert r.returncode != 0 and '"value"' not in r.stdout


_PARENT = """
import json, subprocess, sys
sys.argv = ["bench.py", "--all"]
import bench
seen = []
def fake_run(cmd, **kw):
    name = cmd[-1]
    seen.append([name, "jax" in sys.modules])
    ok = name != %(fail)r
    line = json.dumps({"metric": name, "value": 1.0, "platform": "tpu",
                       "device_kind": "fake", "device_count": 1})
    return subprocess.CompletedProcess(cmd, 0 if ok else 1,
                                       stdout=line + "\\n", stderr="boom")
subprocess.run = fake_run
rc = bench.main()
print(json.dumps({"rc": rc, "seen": seen}))
"""


@pytest.mark.parametrize("fail,want_rc", [(None, 0),
                                          ("bench_flash_attention", 1)])
def test_bench_parent_stays_off_jax_and_fails_with_a_workload(fail, want_rc):
    r = _run(["-c", _PARENT % {"fail": fail}])
    assert r.returncode == 0, r.stderr
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["rc"] == want_rc
    assert out["seen"][0][0] == "bench_gbdt"      # the primary is a child too
    assert len(out["seen"]) > 20
    assert not any(has_jax for _, has_jax in out["seen"])
    result = json.loads(r.stdout.strip().splitlines()[-2])
    assert result["platform"] == "tpu" and result["device_count"] == 1
    errors = [e["metric"] for e in result["extras"] if "error" in e]
    assert errors == ([fail] if fail else [])


# ---------------------------------------------------------------------------
# gang workers: the platform is an argument, not an accident of the env
# ---------------------------------------------------------------------------

def test_gang_platform_is_explicit(monkeypatch, tmp_path):
    from synapseml_tpu.automl.scheduler import GangCandidatePool

    envs = []

    class FakeProc:
        pid = 0

        def __init__(self, cmd, env=None, **_):
            envs.append(env)

        def poll(self):
            return None

        def kill(self):
            pass

        terminate = kill

        def wait(self, timeout=None):
            return 0

    monkeypatch.setattr(subprocess, "Popen", FakeProc)
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")   # what a chip host exports
    with GangCandidatePool(world_size=2, spool_dir=str(tmp_path)) as pool:
        assert pool.platform == "cpu"
    assert [e["JAX_PLATFORMS"] for e in envs] == ["cpu", "cpu"]
