"""Dispatch-level wiring of attention_core (CPU-checkable pieces)."""

import functools
import re

import numpy as np
import pytest

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
    gradients, bare and under the pipeline executors' ``stage_vmap`` over
    stages, which names pp: the region then takes the stage dim split
    over pp (each rank its own stage's rows), where a plain ``vmap`` left
    it whole and every pp rank ran both stages."""
    from smdistributed_modelparallel_tpu.backend.state import state
    from smdistributed_modelparallel_tpu.ops import pallas_attention
    from smdistributed_modelparallel_tpu.parallel.pipeline import stage_vmap

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
            fn = stage_vmap(fn, 2)
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
        if stacked:
            # Forward and backward regions alike: q/k/v/dO enter as ONE
            # stage's [B / rdp, T, H / tp, hd] rows.
            stage_dims = re.findall(
                r"%arg\d+: tensor<(\d+)x2x128x2x16xf32>", text
            )
            assert len(stage_dims) >= 7 and set(stage_dims) == {"1"}
        np.testing.assert_allclose(got, want, atol=2e-5)
        for g, w in zip(got_g, want_g):
            np.testing.assert_allclose(g, w, atol=2e-5)


# The executors that map model code over the stage axis, by the config that
# selects each; ``None`` is the fill-drain executor under a forward-only step.
_STAGE_EXECUTORS = {
    "plain": {},
    "virtual_v2": {"virtual_pipeline_degree": 2},
    "zero_bubble": {"pipeline": "zero_bubble"},
    "zero_bubble_stash": {"pipeline": "zero_bubble",
                          "recompute": "stash_weight"},
    "fill_drain_forward": None,
}


def _pipelined_step(executor_cfg):
    """One step of a 4-layer LM (T 128, so the flash gate passes) at
    pp 2 x tp 2 x rdp 2 through the executor ``executor_cfg`` selects:
    (losses [M], logits, grads or None, compiled HLO text)."""
    from smdistributed_modelparallel_tpu.models.transformer_lm import (
        TransformerLM,
    )
    from tests.models import softmax_xent

    smp.reset()
    smp.init(dict(executor_cfg or {}, pipeline_parallel_degree=2,
                  tensor_parallel_degree=2, ddp=True, microbatches=4))
    model = smp.DistributedModel(TransformerLM(
        vocab_size=32, max_len=128, d_model=32, n_layers=4, n_heads=2,
    ))
    ids = jax.random.randint(jax.random.key(0), (8, 128), 0, 32)

    @smp.step
    def step(model, batch):
        logits = model(batch)
        loss = jnp.mean(softmax_xent(logits[:, :-1], batch[:, 1:]))
        if executor_cfg is not None:
            model.backward(loss)
        return loss, logits

    loss, logits = step(model, ids).stack()
    grads = jax.device_get(model.grads) if executor_cfg is not None else None
    (runner,) = step._cache.values()
    compiled = runner.holder.get("compiled")
    if compiled is None:
        pytest.skip("AOT step executable unavailable on this backend")
    return np.asarray(loss), np.asarray(logits), grads, compiled.as_text()


@functools.lru_cache(maxsize=None)
def _jnp_path_step():
    """The reference: the same step on the jnp attention path (the CPU's own
    dispatch), through the plain 1F1B executor."""
    return _pipelined_step({})[:3]


@pytest.mark.parametrize("executor", list(_STAGE_EXECUTORS))
def test_attention_in_a_stage_stays_on_its_pipeline_rank(executor,
                                                          monkeypatch):
    """Every executor runs a tick's stages as one ``stage_vmap`` over the
    pp-sharded stage axis. The flash kernel's manual region inside must take
    that axis split over pp: left unnamed it entered whole, so q, k, v and
    dO were all-gathered over pp, every chip ran the kernels on both stages'
    rows, and the backward's transpose was a psum over pp (PR 28: 8.6% of
    the four-chip step in traffic, and the kernels twice). From the compiled
    step: no all-gather and no all-reduce over pp under the attention
    module, and the region's operands hold one stage."""
    from smdistributed_modelparallel_tpu.backend.state import state
    from smdistributed_modelparallel_tpu.ops import pallas_attention
    from smdistributed_modelparallel_tpu.utils import hlo_audit

    if jax.device_count() < 8:
        pytest.skip("needs the 8-device CPU mesh")
    want_loss, want_logits, want_grads = _jnp_path_step()
    monkeypatch.delenv("SMP_DISABLE_PALLAS_ATTN", raising=False)
    monkeypatch.setattr(pallas_attention, "FORCE_INTERPRET", True)
    monkeypatch.setattr(A.jax, "default_backend", lambda: "tpu")
    loss, logits, grads, text = _pipelined_step(_STAGE_EXECUTORS[executor])
    mesh = state.mesh
    monkeypatch.undo()

    op_names = dict(re.findall(
        r"(?m)^\s*(?:ROOT )?%?([\w.\-]+) = .*op_name=\"([^\"]*)\"", text
    ))
    # Since PR 39 the attention's core traces under a scope of its own
    # between the module's name and the kernels' manual region.
    in_region = [n for n in op_names.values()
                 if "/attn/smp/attn/core/shard_map/" in n]
    assert any("smp_flash_fwd" in n for n in in_region)
    if grads is not None:
        assert any("smp_flash_bwd_dq" in n for n in in_region)
        assert any("smp_flash_bwd_dkv" in n for n in in_region)
    strays = [
        (name, rec["op"], rec["axis"], rec["bytes"])
        for name, rec in hlo_audit.op_records(text, mesh).items()
        if rec.get("op") in ("all-gather", "all-reduce")
        and "pp" in rec["axis"] and "/attn/" in op_names.get(name, "")
    ]
    assert strays == []
    # Per device the region holds [stage, B / rdp, T, H / tp, hd] rows, one
    # stage of one sequence of one head, and scores [stage, 1, T, T]: no
    # array in it leads with both stages.
    stage_dims = set(re.findall(
        r"= \w+\[(\d+),1,128,[\d,]+\][^\n]*/attn/smp/attn/core/shard_map/",
        text
    ))
    assert stage_dims == {"1"}

    np.testing.assert_allclose(logits, want_logits, rtol=1e-3, atol=2e-5)
    np.testing.assert_allclose(loss, want_loss, rtol=1e-4, atol=1e-5)
    if grads is not None:
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_allclose(
                a, b, rtol=1e-3, atol=2e-5
            ),
            grads, want_grads,
        )


def test_no_region_inside_a_stage_names_pp():
    """``stage_vmap`` gives pp to the stage dim of every ``shard_map``
    region a stage holds, and ``shard_map`` refuses a region whose own specs
    name the vmap's axis ("spmd_axis_name cannot appear in shard_map
    in_specs"). The kernels' and layers' modules therefore never name pp;
    the executors alone do (``parallel/pipeline*.py``). The one exception
    is outside every stage: the untied LM head, which the executors run on
    the last stage's output, splits its vocabulary over tp and pp
    (``nn/transformer._lm_head_vocab_split``, PR 30)."""
    import ast
    import pathlib

    root = pathlib.Path(smp.__file__).parent
    naming_pp = [
        str(path.relative_to(root))
        for sub in ("ops", "nn") for path in sorted((root / sub).rglob("*.py"))
        if "PP_AXIS" in path.read_text()
    ]
    assert naming_pp == ["nn/transformer.py"]
    tree = ast.parse((root / "nn" / "transformer.py").read_text())
    (split,) = [node for node in ast.walk(tree)
                if isinstance(node, ast.FunctionDef)
                and node.name == "_lm_head_vocab_split"]
    uses = [node.lineno for node in ast.walk(tree)
            if isinstance(node, ast.Name) and node.id == "PP_AXIS"]
    assert uses and all(
        split.lineno <= line <= split.end_lineno for line in uses
    )
