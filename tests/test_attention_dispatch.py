"""Dispatch-level wiring of attention_core (CPU-checkable pieces)."""

import numpy as np

import jax
import jax.numpy as jnp

import smdistributed_modelparallel_tpu as smp
from smdistributed_modelparallel_tpu.ops import attention as A


def test_block_size_config_resolution():
    """pallas_attn_block_{q,k}: explicit arg > config > per-path default."""
    from smdistributed_modelparallel_tpu.ops.pallas_attention import (
        resolve_blocks,
    )

    smp.init({})  # defaults: no block overrides
    assert resolve_blocks(None, None) == (256, 512)
    assert resolve_blocks(None, None, default_k=256) == (256, 256)
    smp.init({"pallas_attn_block_q": 128, "pallas_attn_block_k": 256})
    assert resolve_blocks(None, None) == (128, 256)
    assert resolve_blocks(None, None, default_k=256) == (128, 256)
    assert resolve_blocks(512, None) == (512, 256)


def test_block_size_config_rejects_unaligned():
    from smdistributed_modelparallel_tpu.utils.exceptions import ConfigError
    import pytest

    with pytest.raises(ConfigError, match="multiple of 128"):
        smp.init({"pallas_attn_block_q": 300})


def test_pallas_gate_rejects_mixed_dtypes(monkeypatch):
    """The real _pallas_ok gate: uniform dtypes pass, mixed fail (the
    kernel MXU dots run on the operand dtype). Backend faked to 'tpu' so
    the dtype clause is actually reached on the CPU test host."""
    monkeypatch.setattr(A.jax, "default_backend", lambda: "tpu")
    monkeypatch.delenv("SMP_DISABLE_PALLAS_ATTN", raising=False)
    q = jnp.zeros((1, 128, 2, 8), jnp.bfloat16)
    v32 = jnp.zeros((1, 128, 2, 8), jnp.float32)
    assert A._pallas_ok(q, q, q)
    assert not A._pallas_ok(q, q, v32)


def test_mixed_dtype_takes_jnp_path():
    # On a mixed-dtype call the jnp path runs (off-TPU here, but the gate
    # test above pins the dtype clause) and promotes to the wider dtype.
    q = jnp.zeros((1, 128, 2, 8), jnp.bfloat16)
    v = jnp.zeros((1, 128, 2, 8), jnp.float32)
    out = A.attention_core(q, q, v, causal=True)
    assert out.dtype == v.dtype


def test_flash_kernel_runs_in_a_manual_region_on_a_mesh(monkeypatch):
    """GSPMD cannot partition a Mosaic kernel, and on the chip a bare
    ``pallas_call`` under a multi-device jit does not lower at all (found
    compiling the pp=2 x tp=2 step for a described v5e 2x2). On a mesh the
    kernel therefore runs in a full-manual ``shard_map`` region — batch
    over the data axes, heads over tp. Here: the interpreted kernel on the
    CPU mesh, backend faked to 'tpu', against the jnp path — values and
    gradients, bare and under the pipeline executors' ``vmap`` over
    stages."""
    import pytest

    from smdistributed_modelparallel_tpu.backend.state import state
    from smdistributed_modelparallel_tpu.ops import pallas_attention

    if jax.device_count() < 8:
        pytest.skip("needs the 8-device CPU mesh")
    monkeypatch.delenv("SMP_DISABLE_PALLAS_ATTN", raising=False)
    monkeypatch.setattr(pallas_attention, "FORCE_INTERPRET", True)
    smp.init({"tensor_parallel_degree": 2, "pipeline_parallel_degree": 2,
              "ddp": True, "microbatches": 2})
    assert dict(state.mesh.shape)["rdp"] == 2
    q, k, v = (
        jax.random.normal(jax.random.key(i), (4, 128, 4, 16), jnp.float32)
        for i in range(3)
    )

    def run(kernel, stacked):
        def loss(q, k, v):
            out = A.attention_core(q, k, v, causal=True, use_pallas=kernel)
            return jnp.sum(out * jnp.cos(out)), out

        fn = jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True)
        args = (q, k, v)
        if stacked:
            fn = jax.vmap(fn)
            args = tuple(jnp.stack([a, 0.5 * a]) for a in args)
        jitted = jax.jit(fn)
        with jax.set_mesh(state.mesh):
            text = jitted.lower(*args).as_text()
            (_, out), grads = jitted(*args)
        return out, grads, text

    for stacked in (False, True):
        want, want_g, ref_text = run(False, stacked)
        with monkeypatch.context() as m:
            m.setattr(A.jax, "default_backend", lambda: "tpu")
            got, got_g, text = run(True, stacked)
        assert "manual_computation" in text
        assert "manual_computation" not in ref_text
        np.testing.assert_allclose(got, want, atol=2e-5)
        for g, w in zip(got_g, want_g):
            np.testing.assert_allclose(g, w, atol=2e-5)
