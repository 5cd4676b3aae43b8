"""Kernel checks must be callable from INSIDE an active jit trace.

The grower reaches ``child_histogram`` / ``segmented_histograms_available``
while tracing (under ``lax.switch`` inside the fused boosting scan), so the
``functools.cache``d on-device kernel checks can be FIRST-invoked mid-trace.
Under an ambient trace every jnp op produces tracers — without the
``ensure_compile_time_eval`` escape (ops/hist_kernel._eager_selftest) the
``np.asarray`` comparisons raise TracerArrayConversionError. Observed
on-chip 2026-08-02: a bench's first ``train_booster`` trace died exactly
there.

The CPU cannot compile a TPU kernel, so each kernel entry point is replaced
by its XLA reference here: what is under test is the check machinery (input
construction, trace escape, comparison), which must then PASS mid-trace.

Reference analog: LightGBM's GPU tree learner probes its OpenCL kernels once
at setup, never during graph construction — the JAX design must make the
mid-trace probe safe instead, because trace time IS setup time here.
"""

import jax
import jax.numpy as jnp
import pytest


@pytest.fixture
def kernels_as_references(monkeypatch):
    from synapseml_tpu.ops import attention_kernel as ak
    from synapseml_tpu.ops import hist_kernel as hk
    from synapseml_tpu.parallel.ring_attention import _block_attention

    def hist(bT, g, h, m, B, **_):
        return hk._hist_xla(bT, g, h, m, B)

    def hist_range(bT, g, h, m, start, length, B, size, **_):
        idx = jnp.arange(bT.shape[1])
        sel = ((idx >= start) & (idx < start + length)).astype(jnp.float32)
        return hk._hist_xla(bT, g * sel, h * sel, m * sel, B)

    def hist_level(bT, g, h, m, start_chunks, B, slots, **_):
        chunk = jnp.arange(bT.shape[1]) // hk.default_chunk()
        slot = jnp.searchsorted(start_chunks, chunk, side="right") - 1
        return hk._hist_level_xla(bT, g, h, m, slot.astype(jnp.int32), B,
                                  slots)

    def flash(q, k, v, causal, scale, block_q, block_k, interpret):
        return ak._xla_fallback(q, k, v, causal, scale, block_k)

    def flash_block(q, k, v, m, l, o, q_offset, k_offset, causal, scale,
                    **_):
        m, l, o = _block_attention(q, k, v, m, l, o, q_offset, k_offset,
                                   causal, scale)
        return jnp.maximum(m, ak._NEG_INF), l, o

    monkeypatch.setattr(hk, "_hist_pallas", hist)
    monkeypatch.setattr(hk, "_hist_pallas_range", hist_range)
    monkeypatch.setattr(hk, "_hist_pallas_level", hist_level)
    monkeypatch.setattr(ak, "_flash_forward", flash)
    monkeypatch.setattr(ak, "flash_attention_block", flash_block)
    checks = (lambda: hk._check_hist_kernel(256),
              lambda: hk._check_range_kernel(256),
              lambda: hk._check_level_kernel(256, 4),
              ak._check_flash_kernel, ak._check_flash_block_kernel)
    cached = (hk._check_hist_kernel, hk._check_range_kernel,
              hk._check_level_kernel, ak._check_flash_kernel,
              ak._check_flash_block_kernel)
    for c in cached:
        c.cache_clear()
    yield checks
    for c in cached:     # verdicts about the stand-ins must not outlive them
        c.cache_clear()


def test_checks_pass_inside_jit_trace(kernels_as_references):
    ran = []

    def f(x):
        for check in kernels_as_references:
            assert check() is None      # a plain verdict, never a tracer
            ran.append(check)
        return x + 1.0

    jax.jit(f)(jnp.ones(4))
    assert len(ran) == 5


def test_check_inside_switch_branch_trace(kernels_as_references):
    """The exact shape of the on-chip failure: first check call from a
    ``lax.switch`` branch body mid-trace."""
    hist_check, range_check = kernels_as_references[:2]

    def branch(x):
        hist_check()
        range_check()
        return x * 2.0

    def f(x):
        return jax.lax.switch(0, [branch, lambda x: x], x)

    out = jax.jit(f)(jnp.ones(3))
    assert float(out[0]) == 2.0


def test_check_verdict_is_cached_once_per_process(kernels_as_references,
                                                  monkeypatch):
    from synapseml_tpu.ops import hist_kernel as hk

    calls = []
    real = hk.check_kernel
    monkeypatch.setattr(hk, "check_kernel",
                        lambda *a, **k: (calls.append(a[0]), real(*a, **k)))
    hk._check_hist_kernel(256)
    hk._check_hist_kernel(256)
    assert calls == ["_hist_pallas"]
