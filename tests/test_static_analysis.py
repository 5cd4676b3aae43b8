"""Fixture + live-tree tests for tools/analysis (the static-analysis suite).

Every analyzer gets at least one must-flag and one must-not-flag fixture
(the must-not cases encode the false-positive guards: static_argnames,
``ensure_compile_time_eval`` trace escapes, guarded-caller lock propagation,
``sorted()`` after ``os.listdir`` accumulation, ...). The live-tree test is
the CI gate contract: the checked-in tree must be baseline-clean.
"""

import subprocess
import sys
import textwrap

import pytest

from tools.analysis.analyzers import (Context, blocking_io, cycles,
                                      determinism, drift, imports, locks,
                                      names, recompile, trace_safety)
from tools.analysis.core import REPO, Project


def _ctx(tmp_path, files):
    for rel, src in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
    project = Project.from_targets(sorted(files), repo=str(tmp_path))
    return Context(project)


# ---------------------------------------------------------------- trace-safety

def test_trace_safety_flags_branch_on_traced_value(tmp_path):
    ctx = _ctx(tmp_path, {"synapseml_tpu/mod.py": """\
        import jax
        import jax.numpy as jnp

        @jax.jit
        def f(x):
            y = jnp.sum(x)
            if y > 0:
                return y
            return -y
        """})
    found = trace_safety.run(ctx)
    assert len(found) == 1
    assert found[0].line == 7
    assert "Python `if`" in found[0].message


def test_trace_safety_flags_through_helper_call_edge(tmp_path):
    ctx = _ctx(tmp_path, {"synapseml_tpu/mod.py": """\
        import jax

        @jax.jit
        def outer(x):
            return _helper(x)

        def _helper(v):
            return bool(v)
        """})
    found = trace_safety.run(ctx)
    assert len(found) == 1
    assert "`bool()`" in found[0].message
    assert "_helper" in found[0].message


def test_trace_safety_ignores_static_argnames_and_shapes(tmp_path):
    ctx = _ctx(tmp_path, {"synapseml_tpu/mod.py": """\
        from functools import partial

        import jax

        @partial(jax.jit, static_argnames=("mode",))
        def g(x, mode):
            if mode == "nearest":
                return x
            return x * 2

        @jax.jit
        def h(x):
            if x.shape[0] > 4:
                return x[:4]
            return x
        """})
    assert trace_safety.run(ctx) == []


def test_trace_safety_respects_compile_time_eval_escape(tmp_path):
    # a decorator whose wrapper enters jax.ensure_compile_time_eval() runs
    # the body eagerly — never flagged
    ctx = _ctx(tmp_path, {"synapseml_tpu/mod.py": """\
        import functools

        import jax
        import jax.numpy as jnp

        def _eager(fn):
            @functools.wraps(fn)
            def wrapper(*a, **k):
                with jax.ensure_compile_time_eval():
                    return fn(*a, **k)
            return wrapper

        @_eager
        def _selftest():
            arr = jnp.zeros((2,))
            return bool(arr.sum() == 0)

        @jax.jit
        def train(x):
            _selftest()
            return x
        """})
    assert trace_safety.run(ctx) == []


def test_trace_safety_tuple_return_taint_is_per_element(tmp_path):
    # helper returns (shape-derived static, traced array): branching on the
    # static element is fine, np.asarray on the traced one is not
    ctx = _ctx(tmp_path, {"synapseml_tpu/mod.py": """\
        import jax
        import jax.numpy as jnp
        import numpy as np

        def _split(x):
            pad = x.shape[0] % 8
            return pad, jnp.sum(x)

        @jax.jit
        def f(x):
            pad, total = _split(x)
            if pad:
                total = total + pad
            return np.asarray(total)
        """})
    found = trace_safety.run(ctx)
    assert len(found) == 1
    assert "np.asarray" in found[0].message
    assert found[0].line == 14


# ------------------------------------------------------------------- recompile

def test_recompile_flags_jit_then_call(tmp_path):
    ctx = _ctx(tmp_path, {"synapseml_tpu/mod.py": """\
        import jax
        import jax.numpy as jnp

        def scores(a, b):
            return jax.jit(jnp.matmul)(a, b)
        """})
    found = recompile.run(ctx)
    assert len(found) == 1
    assert "rebuilt on every evaluation" in found[0].message


def test_recompile_flags_jit_in_loop(tmp_path):
    ctx = _ctx(tmp_path, {"synapseml_tpu/mod.py": """\
        import jax

        def compile_all(fns, x):
            outs = []
            for fn in fns:
                g = jax.jit(fn)
                outs.append(g(x))
            return outs
        """})
    found = recompile.run(ctx)
    assert len(found) == 1
    assert "inside a loop" in found[0].message


def test_recompile_flags_jitted_call_in_serving_handler(tmp_path):
    # R5: a jitted callee fed request-sized micro-batches from a serving
    # handler recompiles once per observed batch size
    ctx = _ctx(tmp_path, {"synapseml_tpu/srv.py": """\
        import jax
        import jax.numpy as jnp

        from synapseml_tpu.io.serving import ServingServer

        @jax.jit
        def predict(x):
            return jnp.tanh(x)

        def handler(df):
            return predict(df["value"])

        server = ServingServer(handler)
        """})
    found = recompile.run(ctx)
    assert len(found) == 1
    assert "every distinct batch size" in found[0].message
    assert "BucketedRunner" in found[0].message


def test_recompile_flags_factory_built_serving_handler(tmp_path):
    # the handler is returned by a local factory: defs nested in the factory
    # are scanned too (the bench/_gbdt_serving_handler construction shape)
    ctx = _ctx(tmp_path, {"synapseml_tpu/srv.py": """\
        import jax
        import jax.numpy as jnp

        from synapseml_tpu.io.serving import ServingServer

        @jax.jit
        def score(x):
            return jnp.tanh(x)

        def build_handler(scale):
            def handler(df):
                return score(df["value"]) * scale

            return handler

        server = ServingServer(handler=build_handler(2.0))
        """})
    found = recompile.run(ctx)
    assert len(found) == 1
    assert "ServingServer handler" in found[0].message


def test_recompile_allows_runner_backed_serving_handler(tmp_path):
    # routed through BucketedRunner: the runner owns the jit boundary, the
    # handler's call resolves to no traced project function — R5 stays quiet
    ctx = _ctx(tmp_path, {"synapseml_tpu/srv.py": """\
        import numpy as np

        from synapseml_tpu.core.inference import BucketedRunner
        from synapseml_tpu.io.serving import ServingServer

        def _affine(x):
            return x * 2.0 + 1.0

        runner = BucketedRunner(_affine, max_batch_size=64)

        def handler(df):
            return runner(np.asarray(df["value"]))

        server = ServingServer(handler)
        """})
    assert recompile.run(ctx) == []


def test_recompile_flags_jitted_call_in_batch_surface_method(tmp_path):
    # R5 extended scope: `_scores`/`_transform` under explainers/ and
    # recommendation/ are request-sized batch surfaces — a direct jitted
    # call there is one compile per observed batch size
    ctx = _ctx(tmp_path, {"synapseml_tpu/recommendation/rec.py": """\
        import jax
        import jax.numpy as jnp

        @jax.jit
        def _matmul(a, b):
            return a @ b

        class RecModel:
            def _scores(self, aff, sim):
                return _matmul(aff, sim)
        """})
    found = recompile.run(ctx)
    assert len(found) == 1
    assert "request-sized batch surface" in found[0].message
    assert "every distinct batch size" in found[0].message
    assert "BucketedRunner" in found[0].message


def test_recompile_allows_runner_backed_batch_surface(tmp_path):
    # the batch surface goes through a BucketedRunner: the call resolves to
    # no traced project function, and the same method name OUTSIDE the
    # explainers/recommendation dirs is not a batch surface at all
    ctx = _ctx(tmp_path, {
        "synapseml_tpu/explainers/expl.py": """\
            import numpy as np

            from synapseml_tpu.core.inference import BucketedRunner

            def _solve(x):
                return x * 2.0

            runner = BucketedRunner(_solve, max_batch_size=64)

            class Expl:
                def _transform(self, df):
                    return runner(np.asarray(df["value"]))
            """,
        "synapseml_tpu/train/mod.py": """\
            import jax
            import jax.numpy as jnp

            @jax.jit
            def _step(x):
                return jnp.tanh(x)

            class Trainer:
                def _transform(self, df):
                    return _step(df["value"])
            """})
    assert recompile.run(ctx) == []


def test_recompile_allows_hoisted_and_cached_wrappers(tmp_path):
    ctx = _ctx(tmp_path, {"synapseml_tpu/mod.py": """\
        import jax
        import jax.numpy as jnp

        _matmul = jax.jit(jnp.matmul)

        def ok(a, b):
            return _matmul(a, b)

        def warm(fns, x, cache):
            for fn in fns:
                cache[fn] = jax.jit(fn)
        """})
    assert recompile.run(ctx) == []


# ----------------------------------------------------------------- determinism

def test_determinism_flags_wall_clock_and_unseeded_rng(tmp_path):
    ctx = _ctx(tmp_path, {"synapseml_tpu/gbdt/sampler.py": """\
        import time

        import numpy as np

        def fingerprint():
            return time.time()

        def draw():
            return np.random.default_rng()
        """})
    msgs = [f.message for f in determinism.run(ctx)]
    assert len(msgs) == 2
    assert any("time.time" in m for m in msgs)
    assert any("default_rng" in m for m in msgs)


def test_determinism_flags_order_sensitive_listdir(tmp_path):
    ctx = _ctx(tmp_path, {"synapseml_tpu/core/checkpoint.py": """\
        import os

        def latest(d):
            for f in os.listdir(d):
                if f.endswith(".ckpt"):
                    return f
            return None
        """})
    found = determinism.run(ctx)
    assert len(found) == 1
    assert "os.listdir" in found[0].message


def test_determinism_allows_seeded_sorted_and_out_of_scope(tmp_path):
    ctx = _ctx(tmp_path, {
        "synapseml_tpu/gbdt/sampler.py": """\
            import os
            import time

            import numpy as np

            def draw(seed):
                return np.random.default_rng(seed)

            def duration():
                return time.monotonic()

            def steps(d):
                out = []
                for f in os.listdir(d):
                    out.append(f)
                return sorted(out)
            """,
        # wall clock outside the resume-guarantee scope is not this
        # analyzer's business
        "synapseml_tpu/ops/timer.py": """\
            import time

            def stamp():
                return time.time()
            """})
    assert determinism.run(ctx) == []


# ----------------------------------------------------------------------- locks

def test_locks_flags_mixed_discipline_write(tmp_path):
    ctx = _ctx(tmp_path, {"synapseml_tpu/io/serving.py": """\
        import threading

        _LOCK = threading.Lock()
        _COUNTS = {}

        def locked(k):
            with _LOCK:
                _COUNTS[k] = 1

        def unlocked(k):
            _COUNTS[k] = 2
        """})
    found = locks.run(ctx)
    assert len(found) == 1
    assert found[0].line == 11
    assert "_COUNTS" in found[0].message


def test_locks_guarded_caller_and_init_are_clean(tmp_path):
    # _open writes without holding the lock lexically, but its only call
    # site holds it — the guarded-caller fixpoint must not flag it
    ctx = _ctx(tmp_path, {"synapseml_tpu/core/resilience.py": """\
        import threading

        class Breaker:
            def __init__(self):
                self._lock = threading.Lock()
                self._state = "closed"

            def trip(self):
                with self._lock:
                    self._open()

            def _open(self):
                self._state = "open"

            def reset(self):
                with self._lock:
                    self._state = "closed"
        """})
    assert locks.run(ctx) == []


# ----------------------------------------------------------------- blocking-io

def test_blocking_io_flags_sleep_inside_jit(tmp_path):
    ctx = _ctx(tmp_path, {"synapseml_tpu/mod.py": """\
        import time

        import jax

        @jax.jit
        def f(x):
            time.sleep(0.1)
            return x
        """})
    found = blocking_io.run(ctx)
    assert len(found) == 1
    assert "time.sleep" in found[0].message


def test_blocking_io_ignores_untraced_functions(tmp_path):
    ctx = _ctx(tmp_path, {"synapseml_tpu/mod.py": """\
        def read(path):
            with open(path) as fh:
                return fh.read()
        """})
    assert blocking_io.run(ctx) == []


# ------------------------------------------------------------- ported analyzers

def test_undefined_names_flags_unbound_load(tmp_path):
    ctx = _ctx(tmp_path, {"synapseml_tpu/mod.py": """\
        def f():
            return zzz_missing
        """})
    found = names.run(ctx)
    assert len(found) == 1
    assert "zzz_missing" in found[0].message


def test_undefined_names_accepts_any_scope_binding(tmp_path):
    ctx = _ctx(tmp_path, {"synapseml_tpu/mod.py": """\
        def make():
            value = 3
            return value

        def use():
            return value if False else 0
        """})
    assert names.run(ctx) == []


def test_unused_imports_flags_and_exempts(tmp_path):
    ctx = _ctx(tmp_path, {
        "synapseml_tpu/mod.py": """\
            import os
            import sys

            def f():
                return sys.platform
            """,
        "synapseml_tpu/__init__.py": """\
            import os
            """})
    found = imports.run(ctx)
    assert len(found) == 1
    assert "'os'" in found[0].message
    assert found[0].path == "synapseml_tpu/mod.py"


def test_import_cycles_flags_top_level_cycle_only(tmp_path):
    ctx = _ctx(tmp_path, {
        "synapseml_tpu/a.py": "import synapseml_tpu.b\n",
        "synapseml_tpu/b.py": "import synapseml_tpu.a\n"})
    found = cycles.run(ctx)
    assert len(found) == 1
    assert "import cycle" in found[0].message

    ctx = _ctx(tmp_path / "lazy", {
        "synapseml_tpu/a.py": "import synapseml_tpu.b\n",
        "synapseml_tpu/b.py": ("def g():\n"
                               "    import synapseml_tpu.a\n"
                               "    return synapseml_tpu.a\n")})
    assert cycles.run(ctx) == []


# --------------------------------------------------------------- codegen-drift

def test_codegen_drift_flags_missing_rendered_file(monkeypatch):
    import synapseml_tpu.codegen as codegen

    real = codegen.render_stubs()
    fake = dict(real)
    fake["zz_not_on_disk.pyi"] = "# nothing renders this\n"
    monkeypatch.setattr(codegen, "render_stubs", lambda package=None: fake)
    found = drift.run(None)
    assert any("zz_not_on_disk.pyi" in f.path and "missing" in f.message
               for f in found)


def test_codegen_drift_clean_on_committed_tree():
    assert drift.run(None) == []


# ------------------------------------------- fingerprints, suppression, gating

def test_fingerprints_survive_line_drift(tmp_path):
    src = "def f():\n    return zzz_missing\n"
    ctx1 = _ctx(tmp_path / "one", {"synapseml_tpu/mod.py": src})
    f1 = ctx1.project.finalize(names.run(ctx1))
    ctx2 = _ctx(tmp_path / "two",
                {"synapseml_tpu/mod.py": "# a new leading comment\n" + src})
    f2 = ctx2.project.finalize(names.run(ctx2))
    assert f1[0].fingerprint == f2[0].fingerprint
    assert f1[0].line != f2[0].line


def test_inline_suppression_filters_findings(tmp_path):
    ctx = _ctx(tmp_path, {"synapseml_tpu/mod.py": """\
        def f():
            return zzz_missing  # lint-ok: undefined-names
        """})
    assert ctx.project.finalize(names.run(ctx)) == []
    # a different analyzer id on the same line still reports
    ctx2 = _ctx(tmp_path / "other", {"synapseml_tpu/mod.py": """\
        def f():
            return zzz_missing  # lint-ok: locks
        """})
    assert len(ctx2.project.finalize(names.run(ctx2))) == 1


def test_cli_exits_nonzero_on_fixture_corpus(tmp_path):
    (tmp_path / "synapseml_tpu").mkdir()
    bad = tmp_path / "synapseml_tpu" / "mod.py"
    bad.write_text("def f():\n    return zzz_missing\n")
    proc = subprocess.run(
        [sys.executable, "tools/analysis/run.py", "--repo", str(tmp_path)],
        cwd=REPO, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 1
    assert "synapseml_tpu/mod.py:2:" in proc.stdout
    assert "undefined-names" in proc.stdout


@pytest.mark.slow
def test_live_tree_is_baseline_clean():
    from tools.analysis.run import main

    assert main([]) == 0
