"""Overlapped tensor parallelism (``tp_overlap: "ring"``) —
ops/collective_matmul.py + the fused QKV / bias+GELU Pallas kernels.

Coverage map:
- config surface: the SMP_TP_OVERLAP env alias, schema options, and the
  canonicalization rules (inert at tp=1, does not compose with cp > 1);
- THE acceptance gate: tp=2 train-step parity (losses/grads/updated
  params) between ``tp_overlap: off`` and ``ring``, the X-ray's
  decomposed-ppermute census attributed to the tp axis, the parked-hop
  double-buffering evidence, ZERO residual layer-path tp all-gathers,
  zero replication findings, the committed ``tp_overlap_tp2`` golden,
  and the ``smp_tp_overlap_*`` gauges;
- the neutered-constraint detector e2e: a ring-requested program whose
  decomposition did not lower must carry a ``missing_tp_ring`` finding;
- Pallas-vs-reference numerics in interpret mode (bias+GELU forward and
  backward, fused matmul+bias forward and backward, odd shapes through
  the padding paths);
- fused-kernel parity (slow tier): ring + fused QKV + fused bias+GELU at
  tp=2, fused QKV at tp=1 (the no-ring dispatch), each vs the unfused
  baseline, with the trace-time dispatch counters;
- composition (slow tier): pp2 x tp2 ring parity, the indivisible-
  sequence GSPMD fallback (correct AND flagged), health-cheap sentinel;
- the GSPMD resharding census pin (satellite): back-to-back tp linear
  pairs on the ``off`` path compile to exactly their tp all-reduces —
  ``shard_activation`` re-constraining an already-sharded activation
  inserts ZERO tp all-gathers (nn/linear.py module docstring);
- satellites: step-cache/exec-cache knob facts (defaults omitted,
  stored-meta flip -> reject) and the telemetry_report
  "-- tp overlap --" section golden.
"""

import importlib.util
import io
import json
import os

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import smdistributed_modelparallel_tpu as smp
from smdistributed_modelparallel_tpu.backend.config import ModelParallelConfig
from smdistributed_modelparallel_tpu.backend.state import state
from smdistributed_modelparallel_tpu.backend.topology import TP_AXIS
from smdistributed_modelparallel_tpu.nn.cross_entropy import (
    vocab_parallel_cross_entropy,
)
from smdistributed_modelparallel_tpu.nn.linear import (
    ColumnParallelLinear,
    DistributedLinear,
)
from smdistributed_modelparallel_tpu.nn.transformer import (
    DistributedTransformerLMHead,
)
from smdistributed_modelparallel_tpu.ops import collective_matmul
from smdistributed_modelparallel_tpu.ops import pallas_gelu
from smdistributed_modelparallel_tpu.ops import pallas_qkv
from smdistributed_modelparallel_tpu.utils import hlo_audit
from smdistributed_modelparallel_tpu.utils import telemetry as tel
from smdistributed_modelparallel_tpu.utils.exceptions import ConfigError

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SCRIPTS = os.path.join(_REPO, "scripts")

# The canonical model/config: identical to the golden generator's
# (tests/goldens/generate_hlo_fingerprints.py "tp_overlap_tp2").
TINY = dict(
    num_layers=2, num_attention_heads=4, attention_head_size=8,
    hidden_size=32, intermediate_size=64, vocab_size=96, num_positions=32,
    causal_mask_size=32, pre_layernorm=True, post_layernorm=False,
    final_layernorm=True, attention_dropout_prob=0.0,
    hidden_dropout_prob=0.0, embedding_dropout_prob=0.0,
)
TP2 = {"microbatches": 2, "ddp": True, "tensor_parallel_degree": 2}


def _load_script(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(_SCRIPTS, f"{name}.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _train(cfg, steps=2, model_kwargs=None, seq=16):
    smp.shutdown()
    smp.init(cfg)
    kwargs = dict(TINY)
    kwargs.update(model_kwargs or {})
    model = smp.DistributedModel(DistributedTransformerLMHead(**kwargs))
    opt = smp.DistributedOptimizer(optax.sgd(0.1), model)

    @smp.step
    def train_step(model, ids):
        logits = model(ids)
        loss = jnp.mean(
            vocab_parallel_cross_entropy(logits[:, :-1], ids[:, 1:])
        )
        model.backward(loss)
        return loss

    ids = jax.random.randint(
        jax.random.key(0), (4, seq), 0, kwargs["vocab_size"]
    )
    losses = []
    for _ in range(steps):
        out = train_step(model, ids)
        losses.append(float(out.reduce_mean()))
        opt.step()
    return losses, model, train_step


def _np_tree(tree):
    return {
        str(path): np.asarray(leaf)
        for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]
    }


def _assert_trees_close(a, b, atol):
    assert a.keys() == b.keys()
    for k in a:
        np.testing.assert_allclose(a[k], b[k], atol=atol, err_msg=k)


def _metric_series(name):
    return tel.telemetry.report()["metrics"].get(
        name, {"series": []}
    )["series"]


def _gauge(name, **labels):
    for s in _metric_series(name):
        if all(s["labels"].get(k) == v for k, v in labels.items()):
            return s["value"]
    return None


# ----------------------------------------------------------------------
# Config surface
# ----------------------------------------------------------------------


class TestConfig:
    def test_defaults(self):
        cfg = ModelParallelConfig({})
        assert cfg.tp_overlap == "off"
        assert cfg.fused_qkv is False

    def test_schema_rejects_unknown_mode(self):
        with pytest.raises(ConfigError):
            ModelParallelConfig({"tp_overlap": "banana"})

    def test_env_alias(self, monkeypatch):
        monkeypatch.setenv("SMP_TP_OVERLAP", "ring")
        assert ModelParallelConfig({}).tp_overlap == "ring"
        # Explicit config wins over the env alias.
        assert ModelParallelConfig({"tp_overlap": "off"}).tp_overlap == "off"
        monkeypatch.setenv("SMP_TP_OVERLAP", "off")
        assert ModelParallelConfig({}).tp_overlap == "off"
        monkeypatch.setenv("SMP_TP_OVERLAP", "garbage")
        with pytest.raises(ConfigError):
            ModelParallelConfig({})

    def test_mode_canonicalization(self):
        # tp=1: the ring cannot change the program -> "off" (an idle knob
        # never moves a cache key).
        cfg = ModelParallelConfig({"tp_overlap": "ring"})
        assert collective_matmul.tp_overlap_mode(cfg) == "off"
        cfg = ModelParallelConfig(
            {"tp_overlap": "ring", "tensor_parallel_degree": 2, "ddp": True}
        )
        assert collective_matmul.tp_overlap_mode(cfg) == "ring"
        # cp > 1: the ring owns the sequence axis -> "off" (warned once).
        cfg = ModelParallelConfig({
            "tp_overlap": "ring", "tensor_parallel_degree": 2,
            "context_parallel_degree": 2, "ddp": True,
        })
        assert collective_matmul.tp_overlap_mode(cfg) == "off"


# ----------------------------------------------------------------------
# End-to-end acceptance gate: parity + the X-ray evidence + the golden
# ----------------------------------------------------------------------


class TestTpOverlapGate:
    def test_parity_and_xray_gate(self):
        """THE acceptance test: at tp=2, ``tp_overlap: ring`` must
        (a) match the GSPMD path bit-for-tolerance on losses/grads/
        updated params, (b) compile a program whose tp collectives are
        decomposed ppermute rings (census attributed to the tp axis)
        with parked-hop double-buffering evidence, (c) leave ZERO
        synchronous tp all-gathers on the layer-block path and zero
        replication findings, (d) publish the ``smp_tp_overlap_*``
        gauges, and (e) match the committed golden fingerprint."""
        base_l, base_model, _ = _train(TP2)
        base_grads = _np_tree(base_model.grads)
        base_params = _np_tree(base_model.params)

        ring_l, model, train_step = _train(dict(TP2, tp_overlap="ring"))
        np.testing.assert_allclose(base_l, ring_l, atol=2e-5)
        _assert_trees_close(base_grads, _np_tree(model.grads), atol=2e-5)
        _assert_trees_close(base_params, _np_tree(model.params), atol=2e-5)

        # (b) the decomposed ring: tp-attributed collective-permutes,
        # hops parked in loop carries (consumed only by the NEXT
        # iteration's partial matmul).
        audit = hlo_audit.of_step_function(train_step)
        assert audit.tp_overlap is not None
        block = audit.tp_overlap
        assert block["ring_permute_ops"] > 0
        assert block["ring_permute_bytes"] > 0
        assert block["parked_hops"] > 0
        assert audit.collective_count("collective-permute", TP_AXIS) > 0

        # (c) the overlap claim holds structurally: no synchronous tp
        # all-gathers survive on the layer path (embed/head/optimizer
        # boundary collectives are reported separately and allowed) and
        # the column/row matmuls left no reduce-scatters behind either.
        assert block["tp_allgather_ops"] == 0
        assert block["tp_reduce_scatter_ops"] == 0
        assert block["overlap_evidence"] is True
        assert audit.findings == []

        # (d) the published gauges mirror the block.
        assert _gauge("smp_tp_overlap_evidence", step=audit.name) == 1.0
        assert _gauge(
            "smp_tp_overlap_ring_permute_ops", step=audit.name
        ) == block["ring_permute_ops"]

        # (e) committed golden (SEMANTIC_FIELDS diff, tp_overlap block
        # included).
        from tests.conftest import assert_matches_hlo_golden

        assert_matches_hlo_golden(audit, "tp_overlap_tp2")

    def test_neutered_ring_detector(self, monkeypatch):
        """Detector e2e: force every ring call site to fall back (the
        neutered-constraint class — a silently-not-lowered decomposition)
        while the config still claims ``ring``; the X-ray must flag
        ``missing_tp_ring`` instead of letting the overlap claim stand."""
        monkeypatch.setattr(
            collective_matmul, "tp_overlap_active", lambda: False
        )
        _, _, train_step = _train(dict(TP2, tp_overlap="ring"), steps=1)
        audit = hlo_audit.of_step_function(train_step)
        assert audit.tp_overlap is not None
        assert audit.tp_overlap["ring_permute_ops"] == 0
        assert audit.tp_overlap["overlap_evidence"] is False
        kinds = {f.get("kind") for f in audit.findings}
        assert "missing_tp_ring" in kinds

    def test_tp_ring_expected_false_skips_the_block(self):
        """Program families the ring never lowers into by design (the
        serving engine's decode/prefill programs) audit with
        ``tp_ring_expected=False``: no tp_overlap block, no
        missing_tp_ring false alarm — while the default still audits."""
        smp.shutdown()
        smp.init(dict(TP2, tp_overlap="ring"))
        compiled = jax.jit(lambda x: x * 2.0).lower(
            jnp.ones((4,), jnp.float32)
        ).compile()
        audit = hlo_audit.audit_compiled(
            "ringless", compiled, publish=False, persist=False,
            tp_ring_expected=False,
        )
        assert audit.tp_overlap is None
        assert not any("tp" in (f.get("kind") or "") for f in audit.findings)
        audit = hlo_audit.audit_compiled(
            "ringless", compiled, publish=False, persist=False,
        )
        assert audit.tp_overlap is not None
        assert {f.get("kind") for f in audit.findings} >= {"missing_tp_ring"}


# ----------------------------------------------------------------------
# Pallas kernels vs reference (interpret mode; odd shapes hit padding)
# ----------------------------------------------------------------------


class TestPallasNumerics:
    def test_bias_gelu_forward_matches_reference(self):
        x = jax.random.normal(jax.random.key(0), (5, 37), jnp.float32)
        b = jax.random.normal(jax.random.key(1), (37,), jnp.float32)
        got = pallas_gelu.bias_gelu(x, b, True)
        want = pallas_gelu.reference_bias_gelu(x, b)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), atol=1e-6
        )
        # Matches flax's tanh-approximate gelu too (the jnp path the
        # unfused layers take).
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(nn.gelu(x + b, approximate=True)),
            atol=1e-5,
        )

    def test_bias_gelu_grads_match_reference(self):
        x = jax.random.normal(jax.random.key(2), (4, 19), jnp.float32)
        b = jax.random.normal(jax.random.key(3), (19,), jnp.float32)

        def f_kernel(x, b):
            return jnp.sum(pallas_gelu.bias_gelu(x, b, True) ** 2)

        def f_ref(x, b):
            return jnp.sum(pallas_gelu.reference_bias_gelu(x, b) ** 2)

        gx, gb = jax.grad(f_kernel, argnums=(0, 1))(x, b)
        rx, rb = jax.grad(f_ref, argnums=(0, 1))(x, b)
        np.testing.assert_allclose(np.asarray(gx), np.asarray(rx), atol=1e-5)
        np.testing.assert_allclose(np.asarray(gb), np.asarray(rb), atol=1e-5)

    def test_bias_gelu_ok_gates_on_activation_and_backend(self, monkeypatch):
        monkeypatch.setattr(pallas_gelu, "FORCE_INTERPRET", True)
        assert pallas_gelu.bias_gelu_ok("gelu")
        assert pallas_gelu.bias_gelu_ok("gelu_new")
        assert not pallas_gelu.bias_gelu_ok("relu")
        monkeypatch.setattr(pallas_gelu, "FORCE_INTERPRET", False)
        # On the CPU test backend the kernel stays off without the hook.
        assert not pallas_gelu.bias_gelu_ok("gelu")

    def test_matmul_bias_forward_matches_reference(self):
        x = jax.random.normal(jax.random.key(4), (9, 33), jnp.float32)
        w = jax.random.normal(jax.random.key(5), (33, 17), jnp.float32)
        b = jax.random.normal(jax.random.key(6), (17,), jnp.float32)
        got = pallas_qkv.matmul_bias(x, w, b, interpret=True)
        want = pallas_qkv.reference_matmul_bias(x, w, b)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), atol=1e-5
        )
        got_nb = pallas_qkv.matmul_bias(x, w, interpret=True)
        want_nb = pallas_qkv.reference_matmul_bias(x, w)
        np.testing.assert_allclose(
            np.asarray(got_nb), np.asarray(want_nb), atol=1e-5
        )

    def test_matmul_bias_grads_match_reference(self):
        x = jax.random.normal(jax.random.key(7), (6, 21), jnp.float32)
        w = jax.random.normal(jax.random.key(8), (21, 13), jnp.float32)
        b = jax.random.normal(jax.random.key(9), (13,), jnp.float32)

        def f_kernel(x, w, b):
            return jnp.sum(pallas_qkv.matmul_bias(x, w, b, interpret=True) ** 2)

        def f_ref(x, w, b):
            return jnp.sum(pallas_qkv.reference_matmul_bias(x, w, b) ** 2)

        gk = jax.grad(f_kernel, argnums=(0, 1, 2))(x, w, b)
        gr = jax.grad(f_ref, argnums=(0, 1, 2))(x, w, b)
        for a, r in zip(gk, gr):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(r), atol=1e-4
            )

    def test_fused_qkv_ok_needs_ring_at_tp(self, monkeypatch):
        monkeypatch.setattr(pallas_qkv, "FORCE_INTERPRET", True)
        assert pallas_qkv.fused_qkv_ok(32, ring=False, tp=1)
        assert pallas_qkv.fused_qkv_ok(32, ring=True, tp=2)
        # A tp-sharded kernel cannot enter a plain pallas_call: at tp > 1
        # only the ring's manual region may dispatch.
        assert not pallas_qkv.fused_qkv_ok(32, ring=False, tp=2)
        monkeypatch.setattr(pallas_qkv, "FORCE_INTERPRET", False)
        assert not pallas_qkv.fused_qkv_ok(32, ring=False, tp=1)


# ----------------------------------------------------------------------
# Fused-kernel parity (slow tier: extra end-to-end compiles)
# ----------------------------------------------------------------------


class TestFusedParity:
    def test_ring_plus_fusions_parity_tp2(self, monkeypatch):
        """The "ring + fusions" rung: fused QKV inside the ring's partial
        matmuls + the fused bias+GELU region, vs the plain GSPMD/unfused
        baseline — parity on losses/grads/params, dispatch counted."""
        monkeypatch.setattr(pallas_qkv, "FORCE_INTERPRET", True)
        monkeypatch.setattr(pallas_gelu, "FORCE_INTERPRET", True)
        base_l, base_model, _ = _train(TP2)
        base_grads = _np_tree(base_model.grads)
        base_params = _np_tree(base_model.params)

        fused_l, model, train_step = _train(
            dict(TP2, tp_overlap="ring", fused_qkv=True),
            model_kwargs={"fused_bias_gelu": True},
        )
        np.testing.assert_allclose(base_l, fused_l, atol=2e-5)
        _assert_trees_close(base_grads, _np_tree(model.grads), atol=2e-5)
        _assert_trees_close(base_params, _np_tree(model.params), atol=2e-5)
        # The overlapped structure survives the kernel swap.
        audit = hlo_audit.of_step_function(train_step)
        assert audit.tp_overlap["overlap_evidence"] is True
        assert audit.tp_overlap["tp_allgather_ops"] == 0
        # Trace-time dispatch decisions were counted on the pallas path.
        assert (_gauge("smp_fused_kernel_dispatch_total",
                       kernel="qkv", path="pallas") or 0) >= 1
        assert (_gauge("smp_fused_kernel_dispatch_total",
                       kernel="bias_gelu", path="pallas") or 0) >= 1

    def test_fused_qkv_parity_tp1(self, monkeypatch):
        """fused_qkv without the ring (tp=1): one Pallas matmul against
        the concatenated [D, 3*H*hd] kernel, bias in the epilogue."""
        monkeypatch.setattr(pallas_qkv, "FORCE_INTERPRET", True)
        base_l, base_model, _ = _train({"microbatches": 2})
        fused_l, model, _ = _train({"microbatches": 2, "fused_qkv": True})
        np.testing.assert_allclose(base_l, fused_l, atol=2e-5)
        _assert_trees_close(
            _np_tree(base_model.params), _np_tree(model.params), atol=2e-5
        )


# ----------------------------------------------------------------------
# Composition (slow tier)
# ----------------------------------------------------------------------


class TestComposition:
    def test_pp2_composition_parity(self):
        """pp2 x tp2 with the ring: parity vs the single-stage baseline,
        pp permutes intact alongside the tp ring hops, zero findings."""
        base_l, base_model, _ = _train(
            {"microbatches": 4, "ddp": True}, model_kwargs={"num_layers": 4}
        )
        ring_l, model, train_step = _train(
            {"microbatches": 4, "ddp": True, "tensor_parallel_degree": 2,
             "pipeline_parallel_degree": 2, "tp_overlap": "ring"},
            model_kwargs={"num_layers": 4},
        )
        np.testing.assert_allclose(base_l, ring_l, atol=1e-4)
        _assert_trees_close(
            _np_tree(base_model.params), _np_tree(model.params), atol=1e-4
        )
        audit = hlo_audit.of_step_function(train_step)
        assert audit.collective_count("collective-permute", "pp") > 0
        assert audit.tp_overlap["ring_permute_ops"] > 0
        assert audit.tp_overlap["tp_allgather_ops"] == 0
        assert audit.findings == []

    def test_indivisible_seq_falls_back_correct_and_flagged(self):
        """S=17 at tp=2: the ring cannot decompose (warned once), the
        layers keep the GSPMD einsums — numerics stay correct AND the
        X-ray honestly reports the overlap claim as unmet."""
        base_l, base_model, _ = _train(TP2, seq=17)
        ring_l, model, train_step = _train(
            dict(TP2, tp_overlap="ring"), seq=17
        )
        np.testing.assert_allclose(base_l, ring_l, atol=2e-5)
        _assert_trees_close(
            _np_tree(base_model.params), _np_tree(model.params), atol=2e-5
        )
        audit = hlo_audit.of_step_function(train_step)
        assert audit.tp_overlap["ring_permute_ops"] == 0
        assert audit.tp_overlap["overlap_evidence"] is False
        assert "missing_tp_ring" in {f.get("kind") for f in audit.findings}

    def test_health_cheap_composition(self, monkeypatch):
        """ring x SMP_HEALTH_CHECK=cheap: the deferred sentinel rides the
        overlapped program (losses finite, step 0 checked after step 1's
        lazy fetch)."""
        from smdistributed_modelparallel_tpu.utils import health

        monkeypatch.setenv("SMP_HEALTH_CHECK", "cheap")
        losses, _, _ = _train(dict(TP2, tp_overlap="ring"))
        assert all(np.isfinite(losses))
        assert 0 in health.monitor.checked_steps


class TestStageAxis:
    def test_ring_regions_take_the_stage_vmaps_axis(self):
        """The pipeline executors map a tick's stages with ``stage_vmap``,
        which names pp. ``shard_map`` then puts pp on the batched dim of a
        region's specs and traces its body one stage at a time, whether or
        not the region is manual over pp: the tp rings, manual over tp
        alone, failed verification there. ``manual_axes`` adds the vmap's
        axis, so each pp rank runs its own stage's ring. Fast-tier twin of
        ``TestComposition.test_pp2_composition_parity``: both rings, values
        and gradients against the plain products."""
        from smdistributed_modelparallel_tpu.parallel.pipeline import (
            stage_vmap,
        )

        smp.shutdown()
        smp.init(dict(TP2, pipeline_parallel_degree=2, tp_overlap="ring"))
        ks = jax.random.split(jax.random.key(0), 3)
        x = jax.random.normal(ks[0], (2, 2, 8, 16))     # [stage, B, S, D]
        w_col = jax.random.normal(ks[1], (2, 16, 12))
        w_row = jax.random.normal(ks[2], (2, 12, 16))

        def ring(x, w_col, w_row):
            h = collective_matmul.ring_ag_matmul(x, w_col)
            return jnp.sum(jnp.sin(collective_matmul.ring_rs_matmul(h, w_row)))

        def plain(x, w_col, w_row):
            return jnp.sum(jnp.sin((x @ w_col) @ w_row))

        def run(fn):
            return jax.jit(stage_vmap(
                jax.value_and_grad(fn, argnums=(0, 1, 2)), 2
            ))(x, w_col, w_row)

        (got, got_g), (want, want_g) = run(ring), run(plain)
        np.testing.assert_allclose(got, want, rtol=1e-5)
        for g, w in zip(got_g, want_g):
            np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-4)


# ----------------------------------------------------------------------
# GSPMD resharding census pin (satellite): back-to-back tp layers
# ----------------------------------------------------------------------


class TestGspmdReshardPin:
    def test_back_to_back_pairs_have_no_resharding_gathers(self):
        """On the existing GSPMD path (tp_overlap off), two chained
        [column -> row] tp pairs compile to exactly their reduction
        collectives: ``shard_activation`` re-constraining an activation
        that already carries the matching sharding is FREE — zero tp
        all-gathers, zero tp collective-permutes (nn/linear.py module
        docstring records the probe)."""
        smp.shutdown()
        smp.init(TP2)

        class Stack(nn.Module):
            @nn.compact
            def __call__(self, x):
                for i in range(2):
                    x = ColumnParallelLinear(64, name=f"col{i}")(x)
                    x = DistributedLinear(32, name=f"row{i}")(x)
                return x

        mod = Stack()
        x = jax.random.normal(jax.random.key(0), (4, 16, 32))
        from flax.core import meta

        with jax.set_mesh(state.mesh):
            params = meta.unbox(mod.init(jax.random.key(1), x)["params"])
            compiled = (
                jax.jit(lambda p, x: mod.apply({"params": p}, x))
                .lower(params, x).compile()
            )
        text = compiled.as_text()
        census = hlo_audit.collective_census(text, mesh=state.mesh)

        def tp_count(op):
            return (census.get(op, {}).get("axes", {})
                    .get(TP_AXIS, {}).get("count", 0))

        # One reduction per row-parallel layer, nothing else on tp: the
        # chained constraints inserted no resharding collectives.
        assert tp_count("all-gather") == 0
        assert tp_count("collective-permute") == 0
        assert tp_count("all-reduce") + tp_count("reduce-scatter") == 2


# ----------------------------------------------------------------------
# Step-cache / exec-cache knob facts
# ----------------------------------------------------------------------


class TestCacheKnobs:
    def test_knob_facts_present_when_on(self):
        from smdistributed_modelparallel_tpu.utils import exec_cache

        smp.shutdown()
        smp.init(dict(TP2, tp_overlap="ring", fused_qkv=True))
        knobs = exec_cache._knob_facts()
        assert knobs["tp_overlap"] == "ring"
        assert knobs["fused_qkv"] is True

    def test_defaults_omit_the_facts(self):
        """Pre-knob disk entries keep verifying: the default config
        contributes NO tp_overlap/fused_qkv facts (and an idle ring —
        tp=1 — canonicalizes away entirely)."""
        from smdistributed_modelparallel_tpu.utils import exec_cache

        smp.shutdown()
        smp.init({"microbatches": 2, "ddp": True})
        knobs = exec_cache._knob_facts()
        assert "tp_overlap" not in knobs
        assert "fused_qkv" not in knobs
        # Ring requested at tp=1: inert, canonicalized to off.
        smp.shutdown()
        smp.init({"microbatches": 2, "tp_overlap": "ring"})
        assert "tp_overlap" not in exec_cache._knob_facts()

    def test_inert_fused_qkv_omitted(self):
        """fused_qkv at tp > 1 WITHOUT the ring cannot change the
        program (fused_qkv_ok never passes there) — canonicalized out of
        the knob facts so it never invalidates a warm start; at tp=1 it
        engages directly and the fact stays."""
        from smdistributed_modelparallel_tpu.utils import exec_cache

        smp.shutdown()
        smp.init(dict(TP2, fused_qkv=True))
        assert "fused_qkv" not in exec_cache._knob_facts()
        assert not collective_matmul.fused_qkv_effective()
        smp.shutdown()
        smp.init({"microbatches": 2, "fused_qkv": True})
        assert exec_cache._knob_facts().get("fused_qkv") is True
        assert collective_matmul.fused_qkv_effective()
        # use_pallas_kernels off: the gate can never pass -> inert.
        smp.shutdown()
        smp.init({"microbatches": 2, "fused_qkv": True,
                  "use_pallas_kernels": False})
        assert "fused_qkv" not in exec_cache._knob_facts()

    def test_knob_flip_is_a_verified_miss(self, tmp_path, monkeypatch):
        """A disk entry whose stored tp_overlap knob differs from the
        live one is a verified miss (reject_version), and pre-knob
        entries (no tp_overlap fact at all) keep verifying at the
        default — the PR-12/13 contract."""
        from smdistributed_modelparallel_tpu.utils import exec_cache

        smp.shutdown()
        smp.init(dict(TP2))
        monkeypatch.setenv(exec_cache.ENV, "on")
        monkeypatch.setenv(exec_cache.DIR_ENV, str(tmp_path / "cache"))
        f = jax.jit(lambda x: x * 2.0)
        x = jnp.ones((4,), jnp.float32)
        lowered = f.lower(x)
        sha = exec_cache.module_hash(lowered)
        path = exec_cache.store("step", "k" * 16, lowered.compile(),
                                module_sha=sha)
        assert path
        loaded, _ = exec_cache.load("step", "k" * 16, module_sha=sha)
        assert loaded is not None
        meta_path = os.path.join(path, "meta.json")
        with open(meta_path) as fh:
            meta = json.load(fh)
        # Stored pre-knob: the default omits the fact entirely.
        assert "tp_overlap" not in meta["knobs"]
        # Flip the LIVE knob on: the pre-knob entry belongs to the other
        # program -> rejected (version skew), entry kept on disk.
        smp.shutdown()
        smp.init(dict(TP2, tp_overlap="ring"))
        loaded, _ = exec_cache.load("step", "k" * 16, module_sha=sha)
        assert loaded is None
        assert os.path.exists(path)
        # Back at the default the same entry verifies again — idle knobs
        # never invalidate caches.
        smp.shutdown()
        smp.init(dict(TP2))
        loaded, _ = exec_cache.load("step", "k" * 16, module_sha=sha)
        assert loaded is not None

    def test_step_key_moves_with_the_knobs(self):
        """The in-memory step key's tp_overlap tuple: () at defaults
        (byte-identical to pre-knob builds), present once either knob
        engages — flipping it changes the disk key hash too."""
        from smdistributed_modelparallel_tpu.utils import exec_cache

        base = ((), "shapes...")
        ring = ((("ring", False),), "shapes...")
        fused = ((("off", True),), "shapes...")
        assert (exec_cache.stable_key_hash(base)
                != exec_cache.stable_key_hash(ring))
        assert (exec_cache.stable_key_hash(ring)
                != exec_cache.stable_key_hash(fused))


# ----------------------------------------------------------------------
# telemetry_report "-- tp overlap --" section (golden)
# ----------------------------------------------------------------------


def _gauge_family(series):
    return {"kind": "gauge", "help": "", "series": series}


class TestTpReportSection:
    def _report(self, with_counters=True):
        lab = {"step": "step"}
        gauges = {
            "smp_tp_overlap_ring_permute_ops": [({**lab}, 11)],
            "smp_tp_overlap_ring_permute_bytes": [({**lab}, 20488)],
            "smp_tp_overlap_parked_hops": [({**lab}, 6)],
            "smp_tp_overlap_tp_allgather_ops": [({**lab}, 0)],
            "smp_tp_overlap_tp_reduce_scatter_ops": [({**lab}, 0)],
            "smp_tp_overlap_tp_allreduce_ops": [({**lab}, 14)],
            "smp_tp_overlap_evidence": [({**lab}, 1.0)],
        }
        metrics = {
            name: _gauge_family([
                {"labels": labels, "value": value}
                for labels, value in series
            ])
            for name, series in gauges.items()
        }
        if with_counters:
            metrics["smp_fused_kernel_dispatch_total"] = {
                "kind": "counter", "help": "", "series": [
                    {"labels": {"kernel": "qkv", "path": "pallas"},
                     "value": 2},
                    {"labels": {"kernel": "bias_gelu", "path": "pallas"},
                     "value": 2},
                ],
            }
        return {
            "meta": {"pid": 1, "phase": "run/step"},
            "metrics": metrics,
        }

    GOLDEN = (
        "\n-- tp overlap --\n"
        "step:\n"
        "  ring hops: 11 tp collective-permute(s), 20.0 KiB/device "
        "overlapped; 6 parked in loop carries (double-buffered)\n"
        "  residual synchronous tp collectives: 0 all-gather(s), "
        "0 reduce-scatter(s), 14 all-reduce(s)\n"
        "  overlap evidence: PROVEN (hops feed only data movement into "
        "the next partial matmul)\n"
    )

    FUSED_LINE = (
        "  fused-kernel dispatch decisions: bias_gelu/pallas 2  "
        "qkv/pallas 2\n"
    )

    def test_single_dump_golden(self):
        mod = _load_script("telemetry_report")
        out = io.StringIO()
        mod.render(self._report(), out=out)
        text = out.getvalue()
        assert self.GOLDEN in text
        assert self.FUSED_LINE in text

    def test_dir_mode_aggregate_renders_section(self, tmp_path):
        mod = _load_script("telemetry_report")
        for rank in (0, 1):
            rep = self._report(with_counters=False)
            rep["meta"]["rank"] = rank
            with open(tmp_path / f"telemetry.json.rank{rank}", "w") as f:
                json.dump(rep, f)
        reports = mod.load_rank_dumps(str(tmp_path))
        assert sorted(reports) == [0, 1]
        out = io.StringIO()
        mod.render_cross_rank(reports, out=out)
        # Gauges max across ranks: the aggregate section equals one
        # rank's.
        assert self.GOLDEN in out.getvalue()

    def test_absent_gauges_omit_section(self):
        mod = _load_script("telemetry_report")
        out = io.StringIO()
        mod.render({"meta": {}, "metrics": {}}, out=out)
        assert "-- tp overlap --" not in out.getvalue()
