"""Memory-budgeted recompute planner tests (``recompute`` knob,
``parallel/remat_plan.py``, the ZB/interleaved stash executors).

Covers: the acceptance gate — the X-ray remat census of the zero-bubble
program at (pp=2, mb=8, v=2, ``recompute: stash_weight``) reads <= 0.35
FLOP-weighted recompute (vs the 0.79 committed golden for ``full``) with
losses/grads allclose to the ``full`` run and the pp=1 baseline; the
extended ring plan's machine-check (stash slots == planner prediction,
``auto`` never exceeds its budget, per-chunk degradation); stash-lifetime
validation through ``tests/schedule_checker.py`` across the existing
12-config sweep; the committed ``zero_bubble_stash_weight_pp2_mb4``
golden; knob plumbing (config/env aliases, step-key and exec-cache
canonicalization, checkpoint-policy mapping for non-pipeline paths); and
the telemetry-report surface.
"""

import importlib.util
import io
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax

import smdistributed_modelparallel_tpu as smp
from smdistributed_modelparallel_tpu.models.transformer_lm import TransformerLM
from smdistributed_modelparallel_tpu.parallel import pipeline_1f1b, remat_plan
from smdistributed_modelparallel_tpu.parallel.memory import (
    recompute_ring_plan,
)
from smdistributed_modelparallel_tpu.parallel.pipeline_1f1b import (
    build_interleaved_1f1b_schedule,
    build_zero_bubble_schedule,
)
from smdistributed_modelparallel_tpu.utils import hlo_audit
from smdistributed_modelparallel_tpu.utils.exceptions import ConfigError
from tests.models import softmax_xent
from tests.schedule_checker import check_schedule, check_stash_lifetimes
from tests.test_pipeline_zero_bubble import SWEEP

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SCRIPTS = os.path.join(_REPO, "scripts")


def _load_script(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(_SCRIPTS, f"{name}.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _train(cfg, steps=2, n_layers=4, step_fn=None):
    smp.reset()
    smp.init(cfg)
    module = TransformerLM(
        vocab_size=32, max_len=12, d_model=16, n_layers=n_layers, n_heads=2,
    )
    model = smp.DistributedModel(module)
    optimizer = smp.DistributedOptimizer(optax.sgd(0.1), model)
    ids = jax.random.randint(jax.random.key(0), (8, 12), 0, 32)

    if step_fn is None:
        @smp.step
        def train_step(model, batch):
            logits = model(batch)
            loss = jnp.mean(softmax_xent(logits[:, :-1], batch[:, 1:]))
            model.backward(loss)
            return loss
    else:
        train_step = step_fn

    losses, grads = [], None
    for i in range(steps):
        out = train_step(model, ids)
        if i == 0:
            grads = jax.device_get(model.grads)
        losses.append(float(out.reduce_mean()))
        optimizer.step()
    return losses, grads, train_step


def _assert_parity(got, want, gg, wg):
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-3, atol=1e-5),
        gg, wg,
    )


# ----------------------------------------------------------------------
# Extended ring plan + stash-lifetime checker (satellite; pure python)
# ----------------------------------------------------------------------


class TestRecomputeRingPlan:
    @pytest.mark.parametrize("S,M,W,V", SWEEP)
    def test_zb_stash_lifetimes_across_sweep(self, S, M, W, V):
        """The planner's ring sizes are exactly the slot counts under
        which the ``m % slots`` stash indexing is sound — validated by
        the schedule checker's lifetime rules for every stash lifetime
        the executors use (B->W, F->W, F->B)."""
        sched = build_zero_bubble_schedule(S, M, W, V)
        fk, fm, bk, bm, wk, wm = sched
        ticks = check_schedule(S, M, fm, bm, fwd_chunk=fk, bwd_chunk=bk,
                               wgt_mb=wm, wgt_chunk=wk, virtual=V, window=W)
        rings = recompute_ring_plan(fk, fm, bk, bm, wk, wm,
                                    num_stages=S, virtual=V)
        assert rings["b_to_w"] >= 1
        assert rings["f_to_w"] >= rings["b_to_w"]
        check_stash_lifetimes(ticks, "B", "W", rings["b_to_w"], S, M, V)
        check_stash_lifetimes(ticks, "F", "W", rings["f_to_w"], S, M, V)
        check_stash_lifetimes(ticks, "F", "B", rings["f_to_b"], S, M, V)

    @pytest.mark.parametrize("S,M,W,V", SWEEP)
    def test_interleaved_stash_lifetimes_across_sweep(self, S, M, W, V):
        fk, fm, bk, bm = build_interleaved_1f1b_schedule(S, M, W, V)
        ticks = check_schedule(S, M, fm, bm, fwd_chunk=fk, bwd_chunk=bk,
                               virtual=V, window=W)
        rings = recompute_ring_plan(fk, fm, bk, bm,
                                    num_stages=S, virtual=V)
        assert rings["b_to_w"] == 0 and rings["f_to_w"] == 0
        assert rings["f_to_b"] >= 1
        check_stash_lifetimes(ticks, "F", "B", rings["f_to_b"], S, M, V)

    def test_plan_is_tight_and_checker_catches_undersized_ring(self):
        """The ring sizes are minimal: one slot fewer must violate the
        no-reuse-before-consuming-tick rule somewhere (else the sweep
        above proves nothing)."""
        S, M, W, V = 2, 8, 4, 2
        sched = build_zero_bubble_schedule(S, M, W, V)
        fk, fm, bk, bm, wk, wm = sched
        ticks = check_schedule(S, M, fm, bm, fwd_chunk=fk, bwd_chunk=bk,
                               wgt_mb=wm, wgt_chunk=wk, virtual=V, window=W)
        rings = recompute_ring_plan(fk, fm, bk, bm, wk, wm,
                                    num_stages=S, virtual=V)
        assert rings["f_to_w"] > 1
        with pytest.raises(AssertionError, match="overwrites slot"):
            check_stash_lifetimes(ticks, "F", "W", rings["f_to_w"] - 1,
                                  S, M, V)
        # Read-before-write is caught too.
        bad = {**ticks, "W": {k: -1 for k in ticks["W"]}}
        with pytest.raises(AssertionError, match="before"):
            check_stash_lifetimes(bad, "B", "W", rings["b_to_w"], S, M, V)

    def test_b_to_w_matches_w_queue_convention(self):
        """At the gate config the B->W stash depth equals the W-queue
        peak the original ring plan reports — the stash rings cost what
        the deferral already cost."""
        from smdistributed_modelparallel_tpu.parallel.memory import (
            zero_bubble_ring_plan,
        )

        sched = build_zero_bubble_schedule(2, 8, 4, 2)
        plan = zero_bubble_ring_plan(*sched, num_stages=2, virtual=2,
                                     window=4)
        rings = recompute_ring_plan(*sched, num_stages=2, virtual=2)
        assert rings["b_to_w"] == plan["w_queue_peak"]


class TestPlannerBudget:
    def _plan(self, mode, budget_mb=None, res_bytes=1000, cot_bytes=100,
              V=4):
        p = remat_plan.RecomputePlan(
            "zb", mode, 2, V, res_ring_slots=2, cot_ring_slots=2,
            res_slot_bytes=res_bytes, cot_slot_bytes=cot_bytes,
            budget=None if budget_mb is None else budget_mb * (1 << 20),
        )
        return p

    def test_explicit_modes_ignore_budget(self):
        p = self._plan("stash_weight")
        assert p.stash_chunks == [0, 1, 2, 3]
        assert p.degraded_chunks == []
        assert p.effective == "stash_weight"

    def test_auto_degrades_per_chunk_highest_first(self):
        # chunk_bytes = 2*1000 + 2*100 = 2200; budget fits 2 chunks.
        p = remat_plan.RecomputePlan(
            "zb", "auto", 2, 4, res_ring_slots=2, cot_ring_slots=2,
            res_slot_bytes=1000, cot_slot_bytes=100, budget=4500,
        )
        assert p.stash_chunks == [0, 1]
        assert p.degraded_chunks == [2, 3]
        assert p.stash_bytes <= 4500
        assert p.effective == "stash_weight"
        grid = p.grid()
        assert grid[0] == ["stash", "stash", "recompute", "recompute"]

    def test_auto_degrades_to_full_under_zero_budget(self):
        p = remat_plan.RecomputePlan(
            "zb", "auto", 2, 2, res_ring_slots=2, cot_ring_slots=2,
            res_slot_bytes=1000, cot_slot_bytes=100, budget=0,
        )
        assert p.stash_chunks == []
        assert p.effective == "full"
        assert p.stash_bytes == 0

    def test_auto_never_exceeds_budget(self):
        for budget in (0, 1, 2200, 2199, 4400, 8800, 10 ** 9):
            p = remat_plan.RecomputePlan(
                "zb", "auto", 2, 4, res_ring_slots=2, cot_ring_slots=2,
                res_slot_bytes=1000, cot_slot_bytes=100, budget=budget,
            )
            assert p.stash_bytes <= budget

    def test_predicted_fraction_model(self):
        assert remat_plan.predicted_fraction("zb", "full") == 0.5
        assert remat_plan.predicted_fraction("zb", "stash_weight") == 0.25
        assert remat_plan.predicted_fraction("zb", "stash_all") == 0.0
        assert remat_plan.predicted_fraction("1f1b", "full") == 0.25
        assert remat_plan.predicted_fraction("1f1b", "stash_all") == 0.0
        assert remat_plan.predicted_fraction("1f1b", "stash_weight") is None

    def test_budget_bytes_sources(self, monkeypatch):
        class Cfg:
            recompute_budget_mb = 3

        assert remat_plan.budget_bytes(Cfg()) == 3 * (1 << 20)
        monkeypatch.setenv(remat_plan.BUDGET_ENV, "5")

        class NoCfg:
            recompute_budget_mb = None

        assert remat_plan.budget_bytes(NoCfg()) == 5 * (1 << 20)
        monkeypatch.setenv(remat_plan.BUDGET_ENV, "junk")
        # Unparsable env falls through (last-audit default or None).
        assert remat_plan.budget_bytes(NoCfg()) in (
            None,
            *[a.memory.get("temp_bytes") for a in hlo_audit.audits.values()
              if (a.memory or {}).get("temp_bytes")],
        )


# ----------------------------------------------------------------------
# Config / knob plumbing
# ----------------------------------------------------------------------


class TestKnobPlumbing:
    def test_config_accepts_modes(self):
        for mode in ("full", "stash_weight", "stash_all", "auto"):
            cfg = smp.ModelParallelConfig({"recompute": mode})
            assert cfg.recompute == mode
        with pytest.raises(ConfigError):
            smp.ModelParallelConfig({"recompute": "sometimes"})

    def test_env_alias(self, monkeypatch):
        monkeypatch.setenv("SMP_RECOMPUTE", "stash_weight")
        monkeypatch.setenv("SMP_RECOMPUTE_BUDGET_MB", "9")
        cfg = smp.ModelParallelConfig({})
        assert cfg.recompute == "stash_weight"
        assert cfg.recompute_budget_mb == 9
        # Explicit config wins over the env.
        cfg = smp.ModelParallelConfig({"recompute": "full"})
        assert cfg.recompute == "full"
        monkeypatch.setenv("SMP_RECOMPUTE", "junk")
        with pytest.raises(ConfigError):
            smp.ModelParallelConfig({})

    def test_resolve_and_active_for(self):
        class Cfg:
            recompute = "stash_weight"
            pipeline_parallel_degree = 1

        assert remat_plan.resolve(Cfg()) == "stash_weight"
        blk = remat_plan.active_for(Cfg())
        assert blk == {"mode": "stash_weight",
                       "effective": "checkpoint_policy"}

        class Full:
            recompute = "full"

        assert remat_plan.active_for(Full()) is None

    @pytest.mark.parametrize("cfg,recomputed", [
        ({"recompute": "full"}, {"exp", "dot_general"}),
        ({"recompute": "stash_weight"}, {"exp"}),
        ({"recompute": "auto"}, {"exp"}),
        ({"recompute": "stash_all"}, set()),
        ({"offload_activations": True}, {"exp", "dot_general"}),
        (None, {"exp", "dot_general"}),
    ], ids=["full", "stash_weight", "auto", "stash_all", "offload",
            "uninitialized"])
    def test_remat_policy_mapping(self, cfg, recomputed):
        """What each mode keeps across a checkpointed function, by
        behaviour: every mode keeps the two values the flash forward
        names; the stash modes the weight products besides, ``stash_all``
        everything; an elementwise value is made again by all but that
        one. A primitive counts as recomputed where the gradient's jaxpr
        holds it once more than that of the function left unwrapped."""
        from smdistributed_modelparallel_tpu.parallel.memory import (
            FLASH_LSE_NAME,
            FLASH_OUT_NAME,
            remat_policy,
        )
        from jax.ad_checkpoint import checkpoint_name

        def probe(x, w):
            out = checkpoint_name(jnp.sin(x), FLASH_OUT_NAME)
            lse = checkpoint_name(jnp.log1p(x * x), FLASH_LSE_NAME)
            # a product of the four, so the backward pass wants each
            return jnp.sum(out * lse * (x @ w) * jnp.exp(x))

        smp.reset()
        if cfg is not None:
            smp.init(cfg)
        policy = remat_policy()
        smp.reset()
        x, w = jnp.ones((4, 4)), jnp.ones((4, 4))

        def count(fn):
            text = str(jax.make_jaxpr(jax.grad(fn, (0, 1)))(x, w))
            return {p: text.count(f" {p}") for p in (
                "sin", "log1p", "dot_general", "exp")}

        plain = count(probe)
        kept = count(jax.checkpoint(probe, policy=policy))
        assert {p for p in plain if kept[p] == plain[p] + 1} == recomputed
        assert all(kept[p] - plain[p] in (0, 1) for p in plain)
        # and with nothing kept, all four are made again: the count tells
        everything = count(jax.checkpoint(probe, policy=None))
        assert all(everything[p] == plain[p] + 1 for p in plain)

    def test_no_flash_call_is_rematerialized_as_before(self):
        """A function that carries neither name is, under
        ``smp.checkpoint``, the program it is under full remat: the same
        gradient jaxpr but for the policy's own repr."""
        import re

        def fn(x, w):
            h = jnp.tanh(x @ w)
            return jnp.sum(jnp.exp(h) * h)

        smp.reset()
        smp.init({"recompute": "full"})
        x, w = jnp.ones((4, 8)), jnp.ones((8, 8))

        def text(wrapped):
            jaxpr = jax.make_jaxpr(jax.grad(wrapped, (0, 1)))(x, w)
            return re.sub(r"policy=.*", "policy=_", str(jaxpr))

        assert text(smp.checkpoint(fn)) == text(
            jax.checkpoint(fn, policy=None))
        assert "remat" in text(smp.checkpoint(fn))
        smp.reset()

    def test_step_key_canonicalization(self):
        """Default knob contributes NOTHING to the step key (stray env
        budget included); a stash mode inserts a keyed element; the
        budget is keyed only under auto."""
        from smdistributed_modelparallel_tpu.utils import exec_cache

        def key_for(rmode, budget):
            recompute_key = (
                () if rmode == "full"
                else ((rmode,
                       (-1 if budget is None else budget)
                       if rmode == "auto" else 0),)
            )
            return exec_cache.stable_key_hash(
                (("pipe",), ("zero",)) + recompute_key + ("shapes",)
            )

        assert key_for("full", 0) == key_for("full", 512)
        assert key_for("stash_weight", 0) == key_for("stash_weight", 512)
        assert key_for("auto", 256) != key_for("auto", 512)
        # Unset budget (planner fallback) vs explicit 0 (degrade all)
        # build different programs — different keys.
        assert key_for("auto", None) != key_for("auto", 0)
        assert key_for("full", 0) != key_for("stash_weight", 0)

    def test_exec_cache_knob_facts(self, monkeypatch):
        from smdistributed_modelparallel_tpu.utils.exec_cache import (
            _recompute_knob_facts,
        )

        class Cfg:
            recompute = "full"
            recompute_budget_mb = 77

        assert _recompute_knob_facts(Cfg()) == {}
        Cfg.recompute = "stash_weight"
        assert _recompute_knob_facts(Cfg()) == {"recompute": "stash_weight"}
        Cfg.recompute = "auto"
        assert _recompute_knob_facts(Cfg()) == {
            "recompute": "auto", "recompute_budget_mb": 77,
        }
        Cfg.recompute_budget_mb = None
        assert _recompute_knob_facts(Cfg()) == {
            "recompute": "auto", "recompute_budget_mb": -1,
        }

    def test_exec_cache_stored_meta_flip_rejected(self, tmp_path,
                                                  monkeypatch):
        """Satellite: a disk entry whose stored recompute knob differs
        from the live one is a verified miss (reject_version), and
        pre-knob entries (no recompute fact) keep verifying at the
        default."""
        from smdistributed_modelparallel_tpu.utils import exec_cache

        smp.reset()
        smp.init({"recompute": "stash_weight"})
        monkeypatch.setenv(exec_cache.ENV, "on")
        monkeypatch.setenv(exec_cache.DIR_ENV, str(tmp_path / "cache"))
        f = jax.jit(lambda x: x * 2.0)
        x = jnp.ones((4,), jnp.float32)
        lowered = f.lower(x)
        sha = exec_cache.module_hash(lowered)
        path = exec_cache.store("step", "r" * 16, lowered.compile(),
                                module_sha=sha)
        assert path
        loaded, _ = exec_cache.load("step", "r" * 16, module_sha=sha)
        assert loaded is not None
        meta_path = os.path.join(path, "meta.json")
        with open(meta_path) as fh:
            meta = json.load(fh)
        assert meta["knobs"]["recompute"] == "stash_weight"
        meta["knobs"]["recompute"] = "stash_all"
        with open(meta_path, "w") as fh:
            json.dump(meta, fh)
        loaded, _ = exec_cache.load("step", "r" * 16, module_sha=sha)
        assert loaded is None
        assert os.path.exists(path)
        # Default knob: a pre-knob entry (no recompute fact at all)
        # still verifies — idle values never invalidate caches.
        smp.reset()
        smp.init({"recompute": "full"})
        meta["knobs"].pop("recompute", None)
        with open(meta_path, "w") as fh:
            json.dump(meta, fh)
        loaded, _ = exec_cache.load("step", "r" * 16, module_sha=sha)
        assert loaded is not None

    def test_fingerprint_diff_flags_recompute_block(self):
        a = {"recompute": {"mode": "stash_weight", "stash_chunks": [0, 1]}}
        b = {"recompute": {"mode": "stash_weight", "stash_chunks": [0]}}
        changes = hlo_audit.diff(a, b, fields=hlo_audit.SEMANTIC_FIELDS)
        assert any(c["field"] == "recompute.stash_chunks" for c in changes)
        assert hlo_audit.diff(a, dict(a),
                              fields=hlo_audit.SEMANTIC_FIELDS) == []


# ----------------------------------------------------------------------
# Compiled executors (heavier cases tiered slow in conftest)
# ----------------------------------------------------------------------


class TestCensusGate:
    def test_gate_pp2_mb8_v2_stash_weight(self):
        """THE acceptance gate: at (pp=2, mb=8, v=2, zero_bubble,
        stash_weight) the compiled program holds at most half the dot
        FLOPs of the `full` program at the same config, and the same
        unique ones — what went is recompute — with losses/grads allclose
        to the `full` run and to the pp=1 baseline at the existing
        tolerances. The stash plan's rings must match the planner
        prediction (machine-checked memory bound).

        The census's FRACTION is held relatively, not to an absolute
        bound: jax 0.9.0's compiled CPU HLO keeps a source location on few
        dots (3 of 34 here), so same-shape dots of different layers
        collide and the fraction is an upper bound (0.50 here where
        0.4.37 read 0.35, and 0.83 for `full`). Totals and unique FLOPs
        do not depend on the key."""
        stash, stash_grads, step_fn = _train({
            "pipeline_parallel_degree": 2, "microbatches": 8, "ddp": True,
            "pipeline": "zero_bubble", "virtual_pipeline_degree": 2,
            "recompute": "stash_weight",
        })
        audit = hlo_audit.of_step_function(step_fn)
        if audit is None:
            pytest.skip("AOT step executable unavailable on this backend")
        # The fingerprint carries the plan; the plan matches the
        # machine-checked ring sizes.
        blk = audit.fingerprint.get("recompute")
        assert blk is not None
        assert blk["mode"] == "stash_weight"
        assert blk["stash_chunks"] == [0, 1] and blk["degraded_chunks"] == []
        sched = build_zero_bubble_schedule(2, 8, 4, 2)
        rings = recompute_ring_plan(*sched, num_stages=2, virtual=2)
        assert blk["res_ring_slots"] == rings["b_to_w"]
        assert blk["cot_ring_slots"] == rings["b_to_w"]
        plan = remat_plan.plans["zb"]
        assert plan.res_ring_slots == rings["b_to_w"]
        assert plan.stash_bytes == blk["stash_bytes"]
        full, full_grads, full_fn = _train({
            "pipeline_parallel_degree": 2, "microbatches": 8, "ddp": True,
            "pipeline": "zero_bubble", "virtual_pipeline_degree": 2,
        })
        # vs the `full` program at the same config.
        stash_r = audit.remat
        full_r = hlo_audit.of_step_function(full_fn).remat
        assert stash_r["flops"] <= 0.5 * full_r["flops"], (stash_r, full_r)
        assert (
            stash_r["flops"] - stash_r["recomputed_flops"]
            == full_r["flops"] - full_r["recomputed_flops"]
        ), (stash_r, full_r)
        assert full_r["fraction"] >= 1.5 * stash_r["fraction"]
        base, base_grads, _ = _train({"microbatches": 8})
        _assert_parity(stash, full, stash_grads, full_grads)
        _assert_parity(stash, base, stash_grads, base_grads)

    def test_golden_fingerprint_stash_weight_pp2_mb4(self):
        """Committed golden for zb_h1 + stash_weight at pp2-mb4: the
        program must recompile to a clean semantic diff (census, remat
        fraction, recompute plan block)."""
        _, _, step_fn = _train({
            "pipeline_parallel_degree": 2, "microbatches": 4, "ddp": True,
            "pipeline": "zero_bubble", "recompute": "stash_weight",
        }, steps=1)
        audit = hlo_audit.of_step_function(step_fn)
        if audit is None:
            pytest.skip("AOT step executable unavailable on this backend")
        from tests.conftest import assert_matches_hlo_golden

        assert_matches_hlo_golden(audit, "zero_bubble_stash_weight_pp2_mb4")
        assert audit.findings == []


class TestStashParity:
    """Loss/grad parity of every stash mode against the pp=1 baseline
    (heavy multi-compile cases; tiered slow)."""

    def test_zb_stash_all_parity(self):
        base, base_grads, _ = _train({"microbatches": 4})
        za, za_grads, step_fn = _train({
            "pipeline_parallel_degree": 2, "microbatches": 4, "ddp": True,
            "pipeline": "zero_bubble", "recompute": "stash_all",
        })
        _assert_parity(za, base, za_grads, base_grads)
        audit = hlo_audit.of_step_function(step_fn)
        if audit is not None:
            # stash_all removes B's forward too: census below the
            # stash_weight golden's.
            assert audit.remat["fraction"] <= 0.30, audit.remat

    def test_interleaved_stash_all_parity(self):
        base, base_grads, _ = _train({"microbatches": 4})
        iv, iv_grads, _ = _train({
            "pipeline_parallel_degree": 2, "microbatches": 4, "ddp": True,
            "virtual_pipeline_degree": 2, "recompute": "stash_all",
        })
        _assert_parity(iv, base, iv_grads, base_grads)
        v1, v1_grads, _ = _train({
            "pipeline_parallel_degree": 2, "microbatches": 4, "ddp": True,
            "recompute": "stash_all",
        })
        _assert_parity(v1, base, v1_grads, base_grads)

    def test_zb_uneven_layers_stash_weight(self):
        base, base_grads, _ = _train({"microbatches": 4}, n_layers=6)
        zb, zb_grads, _ = _train({
            "pipeline_parallel_degree": 2, "microbatches": 4, "ddp": True,
            "pipeline": "zero_bubble", "virtual_pipeline_degree": 2,
            "recompute": "stash_weight",
        }, n_layers=6)
        _assert_parity(zb, base, zb_grads, base_grads)


class TestAutoDegradation:
    def test_auto_zero_budget_routes_to_full_executor(self, monkeypatch):
        """auto with no headroom degrades every chunk and the build
        falls back to the untouched recompute executor — parity holds
        and the plan says so. The fall-back is the dispatch's, on the
        set-up the stash executor planned from: the microbatch queue is
        embedded once for that build."""
        base, base_grads, _ = _train({"microbatches": 4})
        regions = []
        real_region = pipeline_1f1b.named_region
        monkeypatch.setattr(
            pipeline_1f1b, "named_region",
            lambda name: regions.append(name) or real_region(name),
        )
        ab, ab_grads, _ = _train({
            "pipeline_parallel_degree": 2, "microbatches": 4, "ddp": True,
            "pipeline": "zero_bubble", "recompute": "auto",
            "recompute_budget_mb": 0,
        })
        _assert_parity(ab, base, ab_grads, base_grads)
        plan = remat_plan.plans["zb"]
        assert plan.effective == "full"
        assert plan.degraded_chunks and not plan.stash_chunks
        assert regions.count("smp/pipeline/embed") == 1

    def test_auto_mixed_plan_dual_path_parity(self, monkeypatch):
        """A budget that fits exactly ONE of two chunks: the executor
        compiles both W paths (residual for the stashed chunk, recompute
        for the degraded one) and stays numerically exact."""
        base, base_grads, _ = _train({"microbatches": 4})
        real_plan = remat_plan.plan_pipeline

        def pinned_budget_plan(schedule, mode, S, V, **kw):
            p = remat_plan.RecomputePlan(
                schedule, mode, S, V,
                res_ring_slots=kw["res_ring_slots"],
                cot_ring_slots=kw["cot_ring_slots"],
                res_slot_bytes=kw["res_slot_bytes"],
                cot_slot_bytes=kw["cot_slot_bytes"],
                # One chunk's bytes exactly: the second degrades.
                budget=(kw["res_ring_slots"] * kw["res_slot_bytes"]
                        + kw["cot_ring_slots"] * kw["cot_slot_bytes"]),
            )
            remat_plan.publish(p)
            remat_plan.plans[schedule] = p
            return p

        monkeypatch.setattr(remat_plan, "plan_pipeline", pinned_budget_plan)
        am, am_grads, _ = _train({
            "pipeline_parallel_degree": 2, "microbatches": 4, "ddp": True,
            "pipeline": "zero_bubble", "virtual_pipeline_degree": 2,
            "recompute": "auto",
        })
        monkeypatch.setattr(remat_plan, "plan_pipeline", real_plan)
        _assert_parity(am, base, am_grads, base_grads)
        plan = remat_plan.plans["zb"]
        assert plan.stash_chunks == [0] and plan.degraded_chunks == [1]
        assert plan.stash_bytes <= plan.budget_bytes


# ----------------------------------------------------------------------
# telemetry_report "-- recompute --" section (golden)
# ----------------------------------------------------------------------


def _gauge_family(series):
    return {"kind": "gauge", "help": "", "series": series}


class TestRecomputeReportSection:
    def _report(self):
        lab = {"schedule": "zb"}
        metrics = {
            "smp_recompute_mode_info": [
                ({**lab, "mode": "auto", "effective": "stash_weight"}, 1),
            ],
            "smp_recompute_stash_bytes": [({**lab}, 180676)],
            "smp_recompute_budget_bytes": [({**lab}, 262144)],
            "smp_recompute_chunks": [
                ({**lab, "decision": "stash"}, 2),
                ({**lab, "decision": "recompute"}, 0),
            ],
            "smp_recompute_ring_slots": [
                ({**lab, "ring": "residual"}, 2),
                ({**lab, "ring": "cotangent"}, 2),
            ],
            "smp_recompute_predicted_fraction": [
                ({**lab, "when": "full"}, 0.5),
                ({**lab, "when": "planned"}, 0.25),
            ],
        }
        return {
            "meta": {"pid": 1, "phase": "run/step"},
            "metrics": {
                name: _gauge_family([
                    {"labels": labels, "value": value}
                    for labels, value in series
                ])
                for name, series in metrics.items()
            },
        }

    GOLDEN = (
        "\n-- recompute --\n"
        "zb: mode auto -> stash_weight   chunks: 2 stashed\n"
        "  stash: 176.4 KiB/device vs budget 256.0 KiB"
        "  [rings: residual x2, cotangent x2]\n"
        "  recompute census (planner model): 50% full -> 25% planned "
        "(measured program census in -- hlo audit --)\n"
    )

    def test_single_dump_golden(self):
        mod = _load_script("telemetry_report")
        out = io.StringIO()
        mod.render(self._report(), out=out)
        assert self.GOLDEN in out.getvalue()

    def test_dir_mode_aggregate_renders_section(self, tmp_path):
        mod = _load_script("telemetry_report")
        for rank in (0, 1):
            rep = self._report()
            rep["meta"]["rank"] = rank
            with open(tmp_path / f"telemetry.json.rank{rank}", "w") as f:
                json.dump(rep, f)
        reports = mod.load_rank_dumps(str(tmp_path))
        out = io.StringIO()
        mod.render_cross_rank(reports, out=out)
        assert self.GOLDEN in out.getvalue()
