"""ZeRO-3 fully-sharded parameters (``sharded_params: "zero3"``).

Coverage map:
- config surface: knob constraints, SMP_ZERO3 / SMP_ZERO3_BUCKET_MB env
  aliases, mutual exclusion with the legacy zero2d knob;
- spec machinery: largest-divisible-dim rdp placement, idempotence on
  specs already carrying rdp, the gathered-layout strip helpers, and
  ``describe_state_layout``'s param-sharding mode;
- the end-to-end gate (acceptance): parity vs the unsharded baseline at
  rdp=2 (losses/grads/updated params), the X-ray census showing
  per-layer rdp all-gathers + the bucketed reduce-scatter, ZERO
  replication findings, per-device param bytes == 1/rdp, the overlap /
  double-buffered-register evidence, and the committed golden
  fingerprint;
- composition (slow tier): pp2 x zero3 parity, the GSPMD fallback path
  with prefetch off, and the elastic round trips across world shapes
  (zero3 -> plain dp and back, bitwise);
- satellites: exec-cache knob facts (flip -> verified miss) and the
  telemetry_report "-- zero --" section golden.
"""

import importlib.util
import io
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import smdistributed_modelparallel_tpu as smp
from smdistributed_modelparallel_tpu.backend.config import ModelParallelConfig
from smdistributed_modelparallel_tpu.backend.topology import RDP_AXIS
from smdistributed_modelparallel_tpu.models.transformer_lm import TransformerLM
from smdistributed_modelparallel_tpu.parallel import zero
from smdistributed_modelparallel_tpu.utils import hlo_audit
from smdistributed_modelparallel_tpu.utils.exceptions import ConfigError

from tests.models import softmax_xent

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SCRIPTS = os.path.join(_REPO, "scripts")

# The canonical zero3 model/config: identical to the golden generator's
# (tests/goldens/generate_hlo_fingerprints.py "zero3_rdp2").
CANON_MODEL = dict(vocab_size=32, max_len=12, d_model=16, n_layers=4,
                   n_heads=2)
Z3 = {"sharded_params": "zero3", "sdp_param_persistence_threshold": 1}


def _load_script(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(_SCRIPTS, f"{name}.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _train(cfg, steps=3, lr=0.1, model_kwargs=None):
    smp.shutdown()
    smp.init(cfg)
    kwargs = dict(CANON_MODEL)
    kwargs.update(model_kwargs or {})
    model = smp.DistributedModel(TransformerLM(**kwargs))
    opt = smp.DistributedOptimizer(optax.sgd(lr), model)

    @smp.step
    def train_step(model, ids):
        logits = model(ids)
        loss = jnp.mean(softmax_xent(logits[:, :-1], ids[:, 1:]))
        model.backward(loss)
        return loss

    ids = jax.random.randint(jax.random.key(0), (8, 12), 0, 32)
    losses = []
    for _ in range(steps):
        out = train_step(model, ids)
        losses.append(float(out.reduce_mean()))
        opt.step()
    return losses, model, opt, train_step


def _np_tree(tree):
    return {
        str(path): np.asarray(leaf)
        for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]
    }


def _assert_trees_close(a, b, atol):
    assert a.keys() == b.keys()
    for k in a:
        np.testing.assert_allclose(a[k], b[k], atol=atol, err_msg=k)


def _rdp_sharded_leaves(params):
    n = 0
    for leaf in jax.tree_util.tree_leaves(params):
        spec = getattr(leaf.sharding, "spec", None) or ()
        if any(
            RDP_AXIS in (a if isinstance(a, tuple) else (a,))
            for a in spec if a is not None
        ):
            n += 1
    return n


def _param_device_bytes(params):
    per_device = total = 0
    for leaf in jax.tree_util.tree_leaves(params):
        shard = leaf.sharding.shard_shape(leaf.shape)
        n = 1
        for d in shard:
            n *= int(d)
        per_device += n * leaf.dtype.itemsize
        total += int(leaf.size) * leaf.dtype.itemsize
    return per_device, total


# ----------------------------------------------------------------------
# Config surface
# ----------------------------------------------------------------------


class TestConfig:
    def test_zero3_requires_ddp(self):
        with pytest.raises(ConfigError):
            ModelParallelConfig({"sharded_params": "zero3"})

    def test_zero3_excludes_zero2d_degree(self):
        with pytest.raises(ConfigError):
            ModelParallelConfig({
                "sharded_params": "zero3", "ddp": True,
                "sharded_data_parallel_degree": 4,
            })

    def test_zero3_excludes_sdp_json(self):
        with pytest.raises(ConfigError):
            ModelParallelConfig({
                "sharded_params": "zero3", "ddp": True,
                "_sharded_data_parallelism_config": {
                    "zero_optimization": {"stage": 3},
                },
            })

    def test_enabled_property_and_default(self):
        cfg = ModelParallelConfig({"sharded_params": "zero3", "ddp": True})
        assert cfg.zero3_enabled and not cfg.zero2d_enabled
        assert ModelParallelConfig({}).sharded_params == "none"
        assert not ModelParallelConfig({}).zero3_enabled

    def test_env_alias(self, monkeypatch):
        monkeypatch.setenv("SMP_ZERO3", "1")
        assert ModelParallelConfig({"ddp": True}).zero3_enabled
        # Explicit config wins over the env alias.
        assert not ModelParallelConfig(
            {"ddp": True, "sharded_params": "none"}
        ).zero3_enabled
        monkeypatch.setenv("SMP_ZERO3", "garbage")
        with pytest.raises(ConfigError):
            ModelParallelConfig({"ddp": True})

    def test_bucket_env_alias(self, monkeypatch):
        monkeypatch.setenv("SMP_ZERO3_BUCKET_MB", "7")
        cfg = ModelParallelConfig({"ddp": True, "sharded_params": "zero3"})
        assert cfg.zero3_bucket_mb == 7
        monkeypatch.setenv("SMP_ZERO3_BUCKET_MB", "nope")
        with pytest.raises(ConfigError):
            ModelParallelConfig({"ddp": True})


# ----------------------------------------------------------------------
# Spec machinery
# ----------------------------------------------------------------------


class TestSpecs:
    def test_add_rdp_axis_prefers_largest_dim(self):
        # Scanned stack [L=4, in=32, out=64]: "first" grabs the layer
        # axis, "largest" the out dim — keeping the per-layer dynamic
        # slice local under zero3.
        assert zero.add_rdp_axis(None, (4, 32, 64), 2) == [RDP_AXIS, None, None]
        assert zero.add_rdp_axis(None, (4, 32, 64), 2, prefer="largest") == [
            None, None, RDP_AXIS,
        ]

    def test_add_rdp_axis_idempotent_on_rdp_specs(self):
        # A spec already carrying rdp (zero2d/zero3 param mirrored into
        # its optimizer moment) must come back unchanged — one mesh axis
        # cannot name two dims.
        spec = [RDP_AXIS, None]
        assert zero.add_rdp_axis(spec, (32, 64), 2) == [RDP_AXIS, None]

    def test_add_rdp_axis_threshold_and_indivisible(self):
        assert zero.add_rdp_axis(None, (3, 5), 2, prefer="largest") is None
        assert zero.add_rdp_axis(None, (8,), 2, persistence_threshold=100) is None

    def test_strip_rdp(self):
        from jax.sharding import PartitionSpec as P

        assert zero.strip_rdp(P(RDP_AXIS, None)) == P(None, None)
        assert zero.strip_rdp(P(("pp", RDP_AXIS), "tp")) == P(("pp",), "tp")

    def test_slice_batch_nonzero_axis(self):
        """input_split_axes can put the batch on a later dim: the slice
        split must land on THAT dim and still present the rdp slices as
        the leading vmap axis."""
        smp.shutdown()
        smp.init({"microbatches": 2, "ddp": True,
                  "_device_count_override": 2})
        leaf = jnp.arange(2 * 8 * 3, dtype=jnp.float32).reshape(2, 8, 3)
        out = jax.jit(lambda l: zero.zero3_slice_batch(l, 1, 2))(leaf)
        assert out.shape == (2, 2, 4, 3)
        np.testing.assert_array_equal(
            np.asarray(out[1]), np.asarray(leaf[:, 4:, :])
        )

    def test_outputs_mergeable_probe(self):
        S = jax.ShapeDtypeStruct
        f32 = jnp.float32
        # Leading batch dim scaling by rdp, scalars, and flattened
        # leading dims all merge exactly.
        assert zero.zero3_outputs_mergeable(
            {"loss": S((), f32), "logits": S((8, 12, 32), f32),
             "flat": S((96,), f32)},
            {"loss": S((), f32), "logits": S((4, 12, 32), f32),
             "flat": S((48,), f32)},
            2,
        )
        # Batch on a later axis does not scale dim 0 -> not mergeable.
        assert not zero.zero3_outputs_mergeable(
            {"stats": S((3, 8), f32)}, {"stats": S((3, 4), f32)}, 2
        )
        # A shape that coincidentally equals the sliced shape (no batch
        # dependence) must NOT be treated as mergeable either.
        assert not zero.zero3_outputs_mergeable(
            {"w": S((2, 2), f32)}, {"w": S((2, 2), f32)}, 2
        )

    def test_describe_state_layout_modes(self):
        d = zero.describe_state_layout({"sharded_params": "zero3"})
        assert d["zero3"] and d["sharded_params"] == "zero3"
        assert not d["zero2d"]
        d = zero.describe_state_layout({"sharded_data_parallel_degree": 4})
        assert d["zero2d"] and not d["zero3"]
        assert d["sharded_params"] == "none"


# ----------------------------------------------------------------------
# End-to-end acceptance gate (fast tier): parity + the X-ray evidence
# ----------------------------------------------------------------------


class TestZero3Gate:
    def test_parity_and_xray_gate(self):
        """THE acceptance test: at rdp=2, zero3 must (a) match the
        unsharded baseline bit-for-tolerance on losses/grads/updated
        params, (b) compile a program whose census shows per-layer
        rdp-attributed all-gathers and a bucketed rdp reduce-scatter,
        (c) report ZERO replicated params, (d) realize per-device param
        bytes at exactly 1/rdp of the logical total, and (e) match the
        committed golden fingerprint."""
        base_cfg = {"microbatches": 2, "ddp": True,
                    "_device_count_override": 2}
        base_l, base_model, _, base_step = _train(base_cfg)
        base_grads = _np_tree(base_model.grads)
        base_params = _np_tree(base_model.params)
        base_audit = hlo_audit.of_step_function(base_step)

        z3_l, model, _, train_step = _train(dict(base_cfg, **Z3))
        np.testing.assert_allclose(base_l, z3_l, atol=2e-5)
        _assert_trees_close(base_grads, _np_tree(model.grads), atol=2e-5)
        _assert_trees_close(base_params, _np_tree(model.params), atol=2e-5)

        # (b) collective census: per-layer gathers + bucketed scatter,
        # all attributed to the rdp axis.
        audit = hlo_audit.of_step_function(train_step)
        n_layers = CANON_MODEL["n_layers"]
        assert audit.collective_count("all-gather", RDP_AXIS) >= n_layers
        assert audit.collective_count("reduce-scatter", RDP_AXIS) >= 1
        assert audit.zero is not None
        assert audit.zero["gather_ops"] >= n_layers
        assert audit.zero["scatter_ops"] >= 1
        # Overlap evidence: every gather/scatter byte is issued inside a
        # loop body, and the double-buffered transfer registers are
        # structurally present (an all-gather parked in the scan carry,
        # untouched by the same iteration's dots).
        assert audit.zero["loop_gather_ops"] == audit.zero["gather_ops"]
        assert audit.zero["overlap_fraction"] == pytest.approx(1.0)
        assert audit.zero["prefetch_registers"] > 0

        # (c) replication detector: nothing replicated that should not be.
        assert audit.findings == []
        assert _rdp_sharded_leaves(model.params) == len(
            jax.tree_util.tree_leaves(model.params)
        )

        # (d) per-device param memory is exactly the 1/rdp shard; the
        # compiled program's argument bytes drop below the baseline's
        # (same batch, params halved).
        per_device, total = _param_device_bytes(model.params)
        assert per_device * 2 == total
        if audit.memory.get("argument_bytes") and base_audit is not None \
                and base_audit.memory.get("argument_bytes"):
            assert (audit.memory["argument_bytes"]
                    < base_audit.memory["argument_bytes"])

        # (e) committed golden (SEMANTIC_FIELDS diff, zero block included).
        from tests.conftest import assert_matches_hlo_golden

        assert_matches_hlo_golden(audit, "zero3_rdp2")

    def test_optimizer_moments_mirror_param_shards(self):
        smp.shutdown()
        smp.init(dict({"microbatches": 2, "ddp": True,
                       "_device_count_override": 2}, **Z3))
        model = smp.DistributedModel(TransformerLM(**CANON_MODEL))
        opt = smp.DistributedOptimizer(optax.adamw(1e-3), model)

        @smp.step
        def train_step(model, ids):
            logits = model(ids)
            loss = jnp.mean(softmax_xent(logits[:, :-1], ids[:, 1:]))
            model.backward(loss)
            return loss

        ids = jax.random.randint(jax.random.key(0), (8, 12), 0, 32)
        train_step(model, ids)
        opt.step()
        moment_leaves = [
            leaf for leaf in jax.tree_util.tree_leaves(opt.opt_state)
            if isinstance(leaf, jax.Array) and leaf.ndim >= 1
        ]
        assert moment_leaves
        sharded = sum(
            1 for leaf in moment_leaves
            if any(
                RDP_AXIS in (a if isinstance(a, tuple) else (a,))
                for a in (getattr(leaf.sharding, "spec", None) or ())
                if a is not None
            )
        )
        assert sharded > 0, "no optimizer moment sharded over rdp"


# ----------------------------------------------------------------------
# Composition (slow tier: extra multi-program compiles)
# ----------------------------------------------------------------------


class TestZero3Composition:
    def test_pp2_composition_parity(self):
        """pp2 x zero3: parity vs the unsharded pp=1 baseline, rdp
        gathers INSIDE the tick loop (per-stage gather scoping), pp
        permutes intact, zero findings."""
        base_cfg = {"microbatches": 4, "ddp": True,
                    "_device_count_override": 4}
        base_l, base_model, _, _ = _train(base_cfg)
        base_params = _np_tree(base_model.params)

        z3_l, model, _, train_step = _train(dict(
            base_cfg, pipeline_parallel_degree=2, **Z3
        ))
        np.testing.assert_allclose(base_l, z3_l, atol=1e-4)
        _assert_trees_close(base_params, _np_tree(model.params), atol=1e-4)
        audit = hlo_audit.of_step_function(train_step)
        assert audit.collective_count("all-gather", RDP_AXIS) > 0
        assert audit.collective_count("collective-permute", "pp") > 0
        assert audit.findings == []
        assert audit.zero is not None
        # Per-stage scoping: the rdp gathers live inside the tick loop.
        assert audit.zero["loop_gather_ops"] == audit.zero["gather_ops"] > 0

    def test_unmergeable_outputs_fall_back_exact(self):
        """A step fn returning an output whose batch is NOT on the
        leading dim must trip the output-shape probe into the GSPMD
        gradient path — outputs byte-exact vs the baseline, params still
        sharded."""
        def run(extra):
            smp.shutdown()
            cfg = {"microbatches": 2, "ddp": True,
                   "_device_count_override": 2}
            cfg.update(extra)
            smp.init(cfg)
            model = smp.DistributedModel(TransformerLM(**CANON_MODEL))

            @smp.step
            def train_step(model, ids):
                logits = model(ids)
                loss = jnp.mean(softmax_xent(logits[:, :-1], ids[:, 1:]))
                model.backward(loss)
                # [T, B] — batch on the trailing dim: not slice-mergeable.
                return loss, jnp.swapaxes(logits.sum(-1), 0, 1)

            ids = jax.random.randint(jax.random.key(0), (8, 12), 0, 32)
            out = train_step(model, ids)
            loss, swapped = out.outputs[0]
            return np.asarray(loss), np.asarray(swapped), model, train_step

        b_loss, b_swapped, _, _ = run({})
        z_loss, z_swapped, model, train_step = run(Z3)
        np.testing.assert_allclose(b_loss, z_loss, atol=2e-5)
        assert z_swapped.shape == b_swapped.shape
        np.testing.assert_allclose(b_swapped, z_swapped, atol=2e-4)
        # Fallback kept params sharded (the storage story is unaffected).
        assert _rdp_sharded_leaves(model.params) > 0
        audit = hlo_audit.of_step_function(train_step)
        # GSPMD grads: no manual reduce-scatter buckets on this program.
        assert audit.collective_count("reduce-scatter", RDP_AXIS) == 0
        assert audit.collective_count("all-gather", RDP_AXIS) > 0

    def test_prefetch_off_gspmd_path(self, monkeypatch):
        """SMP_ZERO3_PREFETCH=0: the lifted scan stays in place and GSPMD
        places the per-layer gathers; parity and the reduce-scatter grad
        path are unaffected."""
        base_cfg = {"microbatches": 2, "ddp": True,
                    "_device_count_override": 2}
        base_l, base_model, _, _ = _train(base_cfg)
        base_grads = _np_tree(base_model.grads)
        monkeypatch.setenv("SMP_ZERO3_PREFETCH", "0")
        z3_l, model, _, train_step = _train(dict(base_cfg, **Z3))
        np.testing.assert_allclose(base_l, z3_l, atol=2e-5)
        _assert_trees_close(base_grads, _np_tree(model.grads), atol=2e-5)
        audit = hlo_audit.of_step_function(train_step)
        assert audit.collective_count("all-gather", RDP_AXIS) > 0
        assert audit.collective_count("reduce-scatter", RDP_AXIS) >= 1
        # No transfer registers on this path — the gathers feed compute.
        assert audit.zero["prefetch_registers"] == 0


# ----------------------------------------------------------------------
# Elastic round trips across world shapes (slow tier)
# ----------------------------------------------------------------------


class TestZero3Elastic:
    def _ids(self):
        return jax.random.randint(jax.random.key(0), (8, 12), 0, 32)

    def _build(self, cfg):
        smp.shutdown()
        smp.init(cfg)
        model = smp.DistributedModel(TransformerLM(**CANON_MODEL))
        opt = smp.DistributedOptimizer(optax.sgd(0.1), model)

        @smp.step
        def train_step(model, ids):
            logits = model(ids)
            loss = jnp.mean(softmax_xent(logits[:, :-1], ids[:, 1:]))
            model.backward(loss)
            return loss

        train_step(model, self._ids())
        opt.step()
        return model, opt

    @pytest.mark.parametrize("direction", ["zero3_to_dp", "dp_to_zero3"])
    def test_round_trip_world_shape_change(self, tmp_path, direction):
        """Save under one layout, resume under the other: shard catalogs
        key by logical path + global bounds, so a zero3 checkpoint's
        1/rdp param pieces reassemble bitwise under plain dp — and a
        plain-dp checkpoint shards cleanly INTO zero3 (the supervisor's
        shrink-to-survivors recovery crosses exactly this boundary)."""
        dp_cfg = {"microbatches": 2, "ddp": True,
                  "_device_count_override": 2}
        z3_cfg = dict(dp_cfg, **Z3)
        src_cfg, dst_cfg = (
            (z3_cfg, dp_cfg) if direction == "zero3_to_dp"
            else (dp_cfg, z3_cfg)
        )
        model, opt = self._build(src_cfg)
        saved = _np_tree(model.params)
        smp.save_checkpoint(str(tmp_path), tag="t", model=model,
                            optimizer=opt, blocking=True)

        model2, _ = self._build(dst_cfg)
        # model2 is initialized, so the (elastic) resume applies
        # immediately: each leaf reassembles from logical bounds and
        # re-slices under the destination layout's shardings.
        smp.resume_from_checkpoint(str(tmp_path), tag="t")
        resumed = _np_tree(model2.params)
        assert saved.keys() == resumed.keys()
        for k in saved:
            np.testing.assert_array_equal(saved[k], resumed[k], err_msg=k)


# ----------------------------------------------------------------------
# Exec-cache knob facts: a knob flip can never warm-hit
# ----------------------------------------------------------------------


class TestCacheKnobs:
    def test_knob_facts_present(self):
        from smdistributed_modelparallel_tpu.utils import exec_cache

        smp.shutdown()
        smp.init(dict({"microbatches": 2, "ddp": True,
                       "_device_count_override": 2}, **Z3))
        knobs = exec_cache._knob_facts()
        assert knobs["sharded_params"] == "zero3"
        assert knobs["zero3_bucket_mb"] == 25
        assert knobs["sdp_param_persistence_threshold"] == 1
        assert knobs["zero3_prefetch"] == "on"

    def test_idle_knobs_canonicalized_when_off(self, monkeypatch):
        """With zero3 off, bucket/threshold/prefetch cannot affect the
        program — a stray SMP_ZERO3_PREFETCH (or a different bucket
        default) must NOT invalidate caches of byte-identical programs."""
        from smdistributed_modelparallel_tpu.utils import exec_cache

        smp.shutdown()
        monkeypatch.setenv("SMP_ZERO3_PREFETCH", "0")
        smp.init({"microbatches": 2, "ddp": True,
                  "_device_count_override": 2,
                  "zero3_bucket_mb": 13,
                  "sdp_param_persistence_threshold": 7})
        knobs = exec_cache._knob_facts()
        assert knobs["sharded_params"] == "none"
        assert knobs["zero3_bucket_mb"] == 0
        assert knobs["sdp_param_persistence_threshold"] == 0
        assert knobs["zero3_prefetch"] == "-"

    def test_prefetch_knob_normalized(self, monkeypatch):
        monkeypatch.setenv("SMP_ZERO3_PREFETCH", "0")
        assert zero.prefetch_knob() == "off"
        monkeypatch.setenv("SMP_ZERO3_PREFETCH", "off")
        assert zero.prefetch_knob() == "off"
        monkeypatch.delenv("SMP_ZERO3_PREFETCH")
        assert zero.prefetch_knob() == "on"

    def test_knob_flip_is_a_verified_miss(self, tmp_path, monkeypatch):
        """A disk entry stored under different zero3 knobs must be
        rejected at load (reject_version), exactly like a jaxlib skew —
        the belt-and-braces guard behind the step key's zero tuple."""
        from smdistributed_modelparallel_tpu.utils import exec_cache

        smp.shutdown()
        monkeypatch.setenv(exec_cache.ENV, "on")
        monkeypatch.setenv(exec_cache.DIR_ENV, str(tmp_path / "cache"))
        f = jax.jit(lambda x: x * 2.0)
        x = jnp.ones((4,), jnp.float32)
        lowered = f.lower(x)
        sha = exec_cache.module_hash(lowered)
        path = exec_cache.store("step", "k" * 16, lowered.compile(),
                                module_sha=sha)
        assert path
        # Same knobs -> verified hit.
        loaded, _ = exec_cache.load("step", "k" * 16, module_sha=sha)
        assert loaded is not None
        # Flip one zero3 knob in the stored facts -> rejected, entry kept
        # (it belongs to the other knob setting, not corrupt).
        meta_path = os.path.join(path, "meta.json")
        with open(meta_path) as fh:
            meta = json.load(fh)
        meta["knobs"]["sharded_params"] = "zero3"
        meta["knobs"]["zero3_bucket_mb"] = 13
        with open(meta_path, "w") as fh:
            json.dump(meta, fh)
        loaded, _ = exec_cache.load("step", "k" * 16, module_sha=sha)
        assert loaded is None
        assert os.path.exists(path)

    def test_step_key_carries_zero_tuple(self):
        """The in-memory step cache key embeds (mode, bucket, threshold):
        flipping any of them changes the disk key hash too."""
        from smdistributed_modelparallel_tpu.utils import exec_cache

        base = (("none", 25, 1000000, 1), "shapes...")
        flipped = (("zero3", 25, 1000000, 1), "shapes...")
        assert (exec_cache.stable_key_hash(base)
                != exec_cache.stable_key_hash(flipped))


# ----------------------------------------------------------------------
# telemetry_report "-- zero --" section (golden)
# ----------------------------------------------------------------------


def _gauge_family(series):
    return {"kind": "gauge", "help": "", "series": series}


class TestZeroReportSection:
    def _report(self):
        lab = {"step": "step"}
        metrics = {
            "smp_zero3_gather_ops": [({**lab}, 30)],
            "smp_zero3_gather_bytes": [({**lab}, 31296)],
            "smp_zero3_scatter_ops": [({**lab}, 1)],
            "smp_zero3_scatter_bytes": [({**lab}, 27712)],
            "smp_zero3_buckets": [({**lab}, 1)],
            "smp_zero3_bucket_bytes": [({**lab}, 55424)],
            "smp_zero3_sharded_params": [({**lab}, 16)],
            "smp_zero3_persistent_params": [({**lab}, 0)],
            "smp_zero3_overlap_fraction": [({**lab}, 1.0)],
            "smp_zero3_prefetch_registers": [({**lab}, 12)],
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
        "\n-- zero --\n"
        "step:\n"
        "  param gathers: 30 op(s), 30.6 KiB/device   grad scatters: "
        "1 op(s), 27.1 KiB/device\n"
        "  reduce-scatter buckets: 1 (54.1 KiB grads/microbatch)\n"
        "  params: 16 rdp-sharded, 0 persistent (replicated)\n"
        "  overlap: 100.0% of gather/scatter bytes issued inside loop "
        "bodies; 12 double-buffered register gather(s)\n"
    )

    def test_single_dump_golden(self):
        mod = _load_script("telemetry_report")
        out = io.StringIO()
        mod.render(self._report(), out=out)
        text = out.getvalue()
        assert self.GOLDEN in text

    def test_dir_mode_aggregate_renders_section(self, tmp_path):
        mod = _load_script("telemetry_report")
        for rank in (0, 1):
            rep = self._report()
            rep["meta"]["rank"] = rank
            with open(tmp_path / f"telemetry.json.rank{rank}", "w") as f:
                json.dump(rep, f)
        reports = mod.load_rank_dumps(str(tmp_path))
        assert sorted(reports) == [0, 1]
        out = io.StringIO()
        mod.render_cross_rank(reports, out=out)
        # Gauges max across ranks: the aggregate section equals one rank's.
        assert self.GOLDEN in out.getvalue()

    def test_absent_gauges_omit_section(self):
        mod = _load_script("telemetry_report")
        out = io.StringIO()
        mod.render({"meta": {}, "metrics": {}}, out=out)
        assert "-- zero --" not in out.getvalue()


# ----------------------------------------------------------------------
# resilience_probe: saved param-sharding mode surfaces
# ----------------------------------------------------------------------


class TestResilienceProbeLayout:
    def test_state_layout_reported(self, tmp_path):
        import pickle

        mod = _load_script("resilience_probe")
        d = tmp_path / "t_partial"
        d.mkdir()
        (d / ".committed").write_text("")
        with open(d / "smp_config.pt", "wb") as fh:
            pickle.dump({
                "pipeline_parallel_degree": 1, "tensor_parallel_degree": 1,
                "sharded_data_parallel_degree": 1,
                "sharded_params": "zero3", "shard_optimizer_state": False,
                "microbatches": 2, "num_processes": 1,
            }, fh)
        info = mod.inspect_partial_dir(str(d))
        assert info["topology"]["sharded_params"] == "zero3"
        assert info["state_layout"]["zero3"] is True
        assert info["state_layout"]["zero2d"] is False
