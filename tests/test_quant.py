"""Low-precision frontier (``smp.quant``): fp8 delayed-scaling training
matmuls + int8 paged-KV / weight-only-int8 serving.

Coverage map:
- config surface: the SMP_MATMUL_PRECISION env alias, schema rejects,
  and the canonicalization rules (bf16 under pp > 1 / zero3; the
  SMP_KV_QUANT / SMP_DECODE_WEIGHTS env readers and their rejects);
- THE training acceptance gate: bf16-vs-fp8 loss-trajectory parity over
  10 steps at the canonical TINY config, the X-ray ``quant`` census
  (e4m3 forward + e5m2 gradient casts, zero findings), the
  ``smp_quant_*`` gauges/counters, and the committed ``quant_fp8``
  golden fingerprint;
- the silently-upcast-matmul detector e2e: an fp8-requested program
  none of whose seams engaged must carry a ``quant_upcast`` finding;
- default-knob hygiene: bf16 programs carry NO quant block and no
  config fact (byte-identical contract);
- QuantState checkpointing (slow tier): amax/scale round-trip through
  save/resume at the exact coordinate AND through the elastic glob
  fallback;
- serving: int8 paged-KV pool bytes <= 0.55x bf16 (gauge-asserted via
  ``smp_serve_kv_bytes``) with greedy-exact token parity; weight-only
  int8 engine vs ``smp.generate`` parity incl. both knobs together
  (slow tier);
- satellites: step-cache/exec-cache quant knob facts (defaults omitted,
  stored-meta flip -> reject_version) and the telemetry_report
  "-- quant --" section goldens (single dump + cross-rank dir mode).
"""

import glob
import importlib.util
import io
import json
import os
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import smdistributed_modelparallel_tpu as smp
from smdistributed_modelparallel_tpu import quant
from smdistributed_modelparallel_tpu.backend.config import ModelParallelConfig
from smdistributed_modelparallel_tpu.backend.state import state
from smdistributed_modelparallel_tpu.models.transformer_lm import (
    TransformerLM,
)
from smdistributed_modelparallel_tpu.nn.cross_entropy import (
    vocab_parallel_cross_entropy,
)
from smdistributed_modelparallel_tpu.nn.transformer import (
    DistributedTransformerLMHead,
)
from smdistributed_modelparallel_tpu.serving import (
    ServeRequest,
    ServingEngine,
)
from smdistributed_modelparallel_tpu.utils import hlo_audit
from smdistributed_modelparallel_tpu.utils import telemetry as tel
from smdistributed_modelparallel_tpu.utils.exceptions import ConfigError

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SCRIPTS = os.path.join(_REPO, "scripts")

# The canonical model/config: identical to the golden generator's
# (tests/goldens/generate_hlo_fingerprints.py "quant_fp8").
TINY = dict(
    num_layers=2, num_attention_heads=4, attention_head_size=8,
    hidden_size=32, intermediate_size=64, vocab_size=96, num_positions=32,
    causal_mask_size=32, pre_layernorm=True, post_layernorm=False,
    final_layernorm=True, attention_dropout_prob=0.0,
    hidden_dropout_prob=0.0, embedding_dropout_prob=0.0,
)
BASE = {"microbatches": 2, "ddp": True}


def _load_script(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(_SCRIPTS, f"{name}.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _train(cfg, steps=2):
    smp.shutdown()
    smp.init(cfg)
    model = smp.DistributedModel(DistributedTransformerLMHead(**TINY))
    opt = smp.DistributedOptimizer(optax.sgd(0.1), model)

    @smp.step
    def train_step(model, ids):
        logits = model(ids)
        loss = jnp.mean(
            vocab_parallel_cross_entropy(logits[:, :-1], ids[:, 1:])
        )
        model.backward(loss)
        return loss

    ids = jax.random.randint(jax.random.key(0), (4, 16), 0, 96)
    losses = []
    for _ in range(steps):
        out = train_step(model, ids)
        losses.append(float(out.reduce_mean()))
        opt.step()
    return losses, model, train_step


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
# Config surface + canonical modes
# ----------------------------------------------------------------------


class TestConfig:
    def test_defaults(self):
        cfg = ModelParallelConfig({})
        assert cfg.matmul_precision == "bf16"

    def test_schema_rejects_unknown_mode(self):
        with pytest.raises(ConfigError):
            ModelParallelConfig({"matmul_precision": "int4"})

    def test_env_alias(self, monkeypatch):
        monkeypatch.setenv("SMP_MATMUL_PRECISION", "fp8")
        assert ModelParallelConfig({}).matmul_precision == "fp8"
        # Explicit config wins over the env alias.
        assert ModelParallelConfig(
            {"matmul_precision": "bf16"}
        ).matmul_precision == "bf16"
        monkeypatch.setenv("SMP_MATMUL_PRECISION", "off")
        assert ModelParallelConfig({}).matmul_precision == "bf16"
        monkeypatch.setenv("SMP_MATMUL_PRECISION", "garbage")
        with pytest.raises(ConfigError):
            ModelParallelConfig({})

    def test_mode_canonicalization(self):
        # Plain data parallel: fp8 engages.
        cfg = ModelParallelConfig(dict(BASE, matmul_precision="fp8"))
        assert quant.matmul_precision_mode(cfg) == "fp8"
        # pp > 1: the pipelined executors own their grad plumbing ->
        # bf16 (warned once; an idle knob never moves a cache key).
        cfg = ModelParallelConfig({
            "matmul_precision": "fp8", "pipeline_parallel_degree": 2,
            "microbatches": 4, "ddp": True,
        })
        assert quant.matmul_precision_mode(cfg) == "bf16"
        # zero3: the manual-gradient path -> bf16.
        cfg = ModelParallelConfig(dict(
            BASE, matmul_precision="fp8", sharded_params="zero3",
        ))
        assert quant.matmul_precision_mode(cfg) == "bf16"
        assert quant.matmul_precision_mode(None) == "bf16"

    def test_kv_quant_env(self, monkeypatch):
        for v in ("", "0", "none", "off", "bf16"):
            monkeypatch.setenv("SMP_KV_QUANT", v)
            assert quant.kv_quant_mode() == "none"
        monkeypatch.delenv("SMP_KV_QUANT", raising=False)
        assert quant.kv_quant_mode() == "none"
        monkeypatch.setenv("SMP_KV_QUANT", "int8")
        assert quant.kv_quant_mode() == "int8"
        monkeypatch.setenv("SMP_KV_QUANT", "fp4")
        with pytest.raises(ValueError):
            quant.kv_quant_mode()

    def test_decode_weights_env(self, monkeypatch):
        monkeypatch.delenv("SMP_DECODE_WEIGHTS", raising=False)
        assert quant.decode_weights_mode() == "none"
        monkeypatch.setenv("SMP_DECODE_WEIGHTS", "int8")
        assert quant.decode_weights_mode() == "int8"
        monkeypatch.setenv("SMP_DECODE_WEIGHTS", "int2")
        with pytest.raises(ValueError):
            quant.decode_weights_mode()

    def test_serving_key_suffix(self, monkeypatch):
        monkeypatch.delenv("SMP_KV_QUANT", raising=False)
        monkeypatch.delenv("SMP_DECODE_WEIGHTS", raising=False)
        # Defaults contribute NOTHING — pre-knob key tuples.
        assert quant.serving_key_suffix() == ()
        monkeypatch.setenv("SMP_KV_QUANT", "int8")
        assert quant.serving_key_suffix() == ((("kv_quant", "int8"),))
        monkeypatch.setenv("SMP_DECODE_WEIGHTS", "int8")
        assert quant.serving_key_suffix() == (
            ("kv_quant", "int8"), ("decode_weights", "int8"),
        )


# ----------------------------------------------------------------------
# THE training acceptance gate: parity + the X-ray census + the golden
# ----------------------------------------------------------------------


class TestFp8Gate:
    def test_parity_census_gauges_and_golden(self):
        """THE acceptance test: at the canonical TINY config,
        ``matmul_precision: fp8`` must (a) track the bf16 loss
        trajectory over 10 steps, (b) compile a program whose X-ray
        ``quant`` census shows e4m3 forward AND e5m2 gradient casts
        with zero findings, (c) publish the ``smp_quant_*`` gauges and
        dispatch counters with a live delayed-scaling state, and
        (d) match the committed ``quant_fp8`` golden fingerprint."""
        base_l, _, _ = _train(BASE, steps=10)
        fp8_l, _, train_step = _train(
            dict(BASE, matmul_precision="fp8"), steps=10
        )
        # (a) the quantization error stays a small relative
        # perturbation of the trajectory (CPU smoke measures ~1e-4).
        np.testing.assert_allclose(base_l, fp8_l, rtol=2e-2)

        # (b) the census: e4m3 forward operands, e5m2 cotangents; the
        # detector stayed silent (the program IS quantized).
        audit = hlo_audit.of_step_function(train_step)
        assert audit.quant is not None
        assert audit.quant["f8_casts"]["e4m3"] > 0
        assert audit.quant["f8_casts"]["e5m2"] > 0
        assert audit.findings == []
        assert audit.config.get("matmul_precision") == "fp8"

        # (c) delayed scaling is LIVE: amax observations landed, scales
        # moved off the fresh-start 1.0, and the gauges mirror them.
        qs = state.quant_state
        assert qs is not None
        assert qs.amax_history[:, 0].any()
        assert (qs.scale != 1.0).any()
        assert _gauge("smp_quant_amax", site="qkv.x") > 0
        assert _gauge("smp_quant_scale", site="qkv.x") is not None
        disp = _metric_series("smp_quant_dispatch_total")
        assert any(
            s["labels"].get("path") == "fp8" and s["value"] > 0
            for s in disp
        )

        # (d) committed golden (SEMANTIC_FIELDS diff, quant block
        # included — evidence presence per bucket, not exact counts).
        from tests.conftest import assert_matches_hlo_golden

        assert_matches_hlo_golden(audit, "quant_fp8")

    def test_default_bf16_is_additive(self):
        """The byte-identical contract's fingerprint face: a default
        program carries NO quant block and no config fact."""
        _, _, train_step = _train(BASE, steps=1)
        audit = hlo_audit.of_step_function(train_step)
        assert audit.quant is None
        assert "matmul_precision" not in audit.config

    def test_upcast_detector_fires_when_no_seam_engages(self, monkeypatch):
        """Detector e2e: neuter every seam's dispatch while the config
        still claims fp8 — the program compiles with zero f8 evidence
        and the X-ray must flag ``quant_upcast`` instead of letting the
        low-precision claim stand."""
        monkeypatch.setattr(quant, "fp8_trace_active", lambda: False)
        _, _, train_step = _train(
            dict(BASE, matmul_precision="fp8"), steps=1
        )
        audit = hlo_audit.of_step_function(train_step)
        assert audit.quant is not None
        assert audit.quant["native_f8_dots"] == 0
        assert audit.quant["fp8_origin_dots"] == 0
        assert not any(audit.quant["f8_casts"].values())
        kinds = {f.get("kind") for f in audit.findings}
        assert "quant_upcast" in kinds


# ----------------------------------------------------------------------
# QuantState checkpointing: exact coordinate + elastic glob fallback
# ----------------------------------------------------------------------


class TestQuantCheckpoint:
    def test_amax_scale_roundtrip_and_elastic_resume(self, tmp_path):
        root = str(tmp_path / "ckpt")
        losses, model, step_fn = _train(
            dict(BASE, matmul_precision="fp8"), steps=4
        )
        want = state.quant_state.state_dict()
        assert want["amax_history"].any()
        smp.save_checkpoint(root, tag="q", model=model)
        files = glob.glob(
            os.path.join(root, "q_partial", "quant_states*.pt")
        )
        assert files, "quant_states file missing from the checkpoint"

        # Exact-coordinate resume: a fresh fp8 build starts zeroed and
        # restores the saved history/scales bit-for-bit.
        _, model2, step2 = _train(
            dict(BASE, matmul_precision="fp8"), steps=0
        )
        assert not state.quant_state.state_dict()["amax_history"].any()
        smp.resume_from_checkpoint(root, tag="q")
        got = state.quant_state.state_dict()
        np.testing.assert_array_equal(
            got["amax_history"], want["amax_history"]
        )
        np.testing.assert_array_equal(got["scale"], want["scale"])
        # Training continues under the restored scales.
        ids = jax.random.randint(jax.random.key(0), (4, 16), 0, 96)
        step2(model2, ids)

        # Elastic fallback: rename the coordinate file to one no live
        # rank owns — the glob fallback still restores the state.
        src = glob.glob(
            os.path.join(root, "q_partial", "quant_states*.pt")
        )[0]
        shutil.move(
            src,
            os.path.join(os.path.dirname(src), "quant_states_7_0_0.pt"),
        )
        _, model3, step3 = _train(
            dict(BASE, matmul_precision="fp8"), steps=0
        )
        smp.resume_from_checkpoint(root, tag="q")
        got3 = state.quant_state.state_dict()
        np.testing.assert_array_equal(
            got3["amax_history"], want["amax_history"]
        )
        np.testing.assert_array_equal(got3["scale"], want["scale"])
        step3(model3, ids)


# ----------------------------------------------------------------------
# Serving: int8 paged-KV pool + weight-only int8 decode
# ----------------------------------------------------------------------


def _zoo(**kw):
    kw.setdefault("vocab_size", 97)
    kw.setdefault("max_len", 64)
    kw.setdefault("d_model", 32)
    kw.setdefault("n_layers", 2)
    kw.setdefault("n_heads", 4)
    kw.setdefault("pos_type", "rotary")
    return TransformerLM(**kw)


def _prompt(seed, length, vocab=97):
    return list(map(int, np.asarray(
        jax.random.randint(jax.random.key(seed), (length,), 0, vocab)
    )))


def _generate_ref(mod, params, prompt, max_new, **kw):
    out = np.asarray(smp.generate(
        mod, jnp.asarray(prompt, jnp.int32)[None, :], max_new,
        params=params, **kw,
    ))
    return list(out[0, len(prompt):])


def _engine(mod, params):
    return ServingEngine(
        mod, params=params, max_slots=3, num_blocks=13,
        block_tokens_override=4, prefill_chunk=4,
    )


SPECS = [
    ("q0", 40, 7, 6),
    ("q1", 41, 11, 4),
    ("q2", 42, 3, 8),
]


def _run(engine):
    return engine.run(
        [ServeRequest(rid, _prompt(seed, n), m)
         for rid, seed, n, m in SPECS],
        timeout_s=300,
    )


class TestServingInt8KV:
    def test_pool_bytes_halved_gauge_asserted_with_token_parity(
        self, monkeypatch
    ):
        """THE serving acceptance: the int8 pool's bytes/block (scale
        sidecars included) land at <= 0.55x the bf16 pool's — asserted
        off the ``smp_serve_kv_bytes`` gauge, not dtype names — while
        greedy decode stays token-for-token exact."""
        monkeypatch.delenv("SMP_KV_QUANT", raising=False)
        smp.init({})
        mod = _zoo()
        probe = jnp.zeros((1, 4), jnp.int32)
        params = mod.init(jax.random.key(0), probe)["params"]

        eng_b = _engine(mod, params)
        res_b = _run(eng_b)
        bytes_b = eng_b.kv_block_bytes
        assert bytes_b > 0
        total_b = _gauge("smp_serve_kv_bytes", state="total")
        assert total_b == eng_b.alloc.num_blocks * bytes_b

        monkeypatch.setenv("SMP_KV_QUANT", "int8")
        eng_q = _engine(mod, params)
        res_q = _run(eng_q)
        bytes_q = eng_q.kv_block_bytes
        assert bytes_q <= 0.55 * bytes_b
        # The gauge reflects the quantized pool now.
        total_q = _gauge("smp_serve_kv_bytes", state="total")
        assert total_q == eng_q.alloc.num_blocks * bytes_q
        assert total_q <= 0.55 * total_b
        # Greedy token parity, int8 pool vs bf16 pool.
        for rid, _, _, _ in SPECS:
            assert list(res_q[rid]) == list(res_b[rid]), rid
        # The dispatch decision was counted.
        assert _gauge is not None
        disp = [
            s for s in _metric_series("smp_quant_dispatch_total")
            if s["labels"].get("site") == "kv_cache"
            and s["labels"].get("path") == "int8"
        ]
        assert disp and disp[0]["value"] >= 1

    def test_serving_key_moves_with_the_knob(self, monkeypatch):
        """A knob flip must recompile, never reuse the other layout's
        programs — the key suffix is the mechanism."""
        monkeypatch.delenv("SMP_KV_QUANT", raising=False)
        base = quant.serving_key_suffix()
        monkeypatch.setenv("SMP_KV_QUANT", "int8")
        assert quant.serving_key_suffix() != base


class TestDecodeWeightsInt8:
    def test_engine_matches_generate_fake_quant(self, monkeypatch):
        """Weight-only int8: the engine's store-int8+dequant programs
        and ``smp.generate``'s fake-quant path are numerics-identical,
        so the parity oracle holds under the knob — alone and combined
        with the int8 KV pool."""
        monkeypatch.delenv("SMP_KV_QUANT", raising=False)
        monkeypatch.setenv("SMP_DECODE_WEIGHTS", "int8")
        smp.init({})
        mod = _zoo()
        probe = jnp.zeros((1, 4), jnp.int32)
        params = mod.init(jax.random.key(0), probe)["params"]

        eng = _engine(mod, params)
        res = _run(eng)
        for rid, seed, n, m in SPECS:
            ref = _generate_ref(mod, params, _prompt(seed, n), m)
            assert list(res[rid]) == ref, rid
        disp = [
            s for s in _metric_series("smp_quant_dispatch_total")
            if s["labels"].get("site") == "decode_weights"
        ]
        assert disp and disp[0]["value"] >= 1

        # Both serving knobs together keep the same parity.
        monkeypatch.setenv("SMP_KV_QUANT", "int8")
        eng2 = _engine(mod, params)
        res2 = _run(eng2)
        for rid, seed, n, m in SPECS:
            ref = _generate_ref(mod, params, _prompt(seed, n), m)
            assert list(res2[rid]) == ref, rid


# ----------------------------------------------------------------------
# Step-cache / exec-cache knob facts
# ----------------------------------------------------------------------


class TestKnobFacts:
    def test_defaults_omit_all_quant_facts(self, monkeypatch):
        from smdistributed_modelparallel_tpu.utils import exec_cache

        monkeypatch.delenv("SMP_KV_QUANT", raising=False)
        monkeypatch.delenv("SMP_DECODE_WEIGHTS", raising=False)
        smp.shutdown()
        smp.init(dict(BASE))
        facts = exec_cache._knob_facts()
        assert "matmul_precision" not in facts
        assert "kv_quant" not in facts
        assert "decode_weights" not in facts

    def test_engaged_knobs_append_facts(self, monkeypatch):
        from smdistributed_modelparallel_tpu.utils import exec_cache

        smp.shutdown()
        smp.init(dict(BASE, matmul_precision="fp8"))
        assert exec_cache._knob_facts().get("matmul_precision") == "fp8"
        monkeypatch.setenv("SMP_KV_QUANT", "int8")
        monkeypatch.setenv("SMP_DECODE_WEIGHTS", "int8")
        facts = exec_cache._knob_facts()
        assert facts.get("kv_quant") == "int8"
        assert facts.get("decode_weights") == "int8"
        # Canonicalization keys the FACT, not the raw knob: fp8 under
        # pp > 1 resolves bf16, so the fact disappears.
        smp.shutdown()
        smp.init({
            "matmul_precision": "fp8", "pipeline_parallel_degree": 2,
            "microbatches": 4, "ddp": True,
        })
        assert "matmul_precision" not in exec_cache._knob_facts()

    def test_knob_flip_is_a_verified_miss(self, tmp_path, monkeypatch):
        """A disk entry stored at the defaults (no quant facts at all)
        must reject (version skew) once a live quant knob engages, and
        verify again when the knob drops back — the PR-12/13 contract."""
        from smdistributed_modelparallel_tpu.utils import exec_cache

        monkeypatch.delenv("SMP_KV_QUANT", raising=False)
        smp.shutdown()
        smp.init(dict(BASE))
        monkeypatch.setenv(exec_cache.ENV, "on")
        monkeypatch.setenv(exec_cache.DIR_ENV, str(tmp_path / "cache"))
        f = jax.jit(lambda x: x * 2.0)
        x = jnp.ones((4,), jnp.float32)
        lowered = f.lower(x)
        sha = exec_cache.module_hash(lowered)
        path = exec_cache.store(
            "step", "k" * 16, lowered.compile(), module_sha=sha
        )
        assert path
        loaded, _ = exec_cache.load("step", "k" * 16, module_sha=sha)
        assert loaded is not None
        with open(os.path.join(path, "meta.json")) as fh:
            meta = json.load(fh)
        # Stored pre-knob: defaults omit every quant fact.
        assert "matmul_precision" not in meta["knobs"]
        assert "kv_quant" not in meta["knobs"]
        # Flip a LIVE knob on: the pre-knob entry belongs to the other
        # program -> rejected, entry kept on disk for its own env.
        monkeypatch.setenv("SMP_KV_QUANT", "int8")
        loaded, _ = exec_cache.load("step", "k" * 16, module_sha=sha)
        assert loaded is None
        assert os.path.exists(path)
        # Back at the default the same entry verifies again.
        monkeypatch.delenv("SMP_KV_QUANT")
        loaded, _ = exec_cache.load("step", "k" * 16, module_sha=sha)
        assert loaded is not None


# ----------------------------------------------------------------------
# telemetry_report "-- quant --" section (golden)
# ----------------------------------------------------------------------


class TestQuantReportSection:
    def _report(self, with_counters=True):
        metrics = {
            "smp_quant_amax": {
                "kind": "gauge", "help": "", "series": [
                    {"labels": {"site": "qkv.x"}, "value": 2.0},
                    {"labels": {"site": "qkv.w"}, "value": 0.0},
                ],
            },
            "smp_quant_scale": {
                "kind": "gauge", "help": "", "series": [
                    {"labels": {"site": "qkv.x"}, "value": 0.5},
                    {"labels": {"site": "qkv.w"}, "value": 1.0},
                ],
            },
            "smp_serve_kv_bytes": {
                "kind": "gauge", "help": "", "series": [
                    {"labels": {"state": "used"}, "value": 4224},
                    {"labels": {"state": "total"}, "value": 27456},
                ],
            },
        }
        if with_counters:
            metrics["smp_quant_dispatch_total"] = {
                "kind": "counter", "help": "", "series": [
                    {"labels": {"site": "qkv", "path": "fp8"},
                     "value": 2},
                    {"labels": {"site": "kv_cache", "path": "int8"},
                     "value": 1},
                ],
            }
        return {
            "meta": {"pid": 1, "phase": "run/step"},
            "metrics": metrics,
        }

    GOLDEN = (
        "\n-- quant --\n"
        "  dispatch decisions: kv_cache/int8 x1  qkv/fp8 x2\n"
        "  site                    amax       scale\n"
        "  qkv.x                      2         0.5\n"
        "  (1 slot(s) never observed — scale held at 1.0)\n"
        "  kv pool bytes: 4.1 KiB used / 26.8 KiB total\n"
    )

    def test_single_dump_golden(self):
        mod = _load_script("telemetry_report")
        out = io.StringIO()
        mod.render(self._report(), out=out)
        assert self.GOLDEN in out.getvalue()

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
        text = out.getvalue()
        # Gauges max across ranks (exact for the replicated SPMD quant
        # state): the aggregate table equals one rank's.
        assert "-- quant --" in text
        assert "  qkv.x                      2         0.5\n" in text
        assert "  kv pool bytes: 4.1 KiB used / 26.8 KiB total\n" in text

    def test_absent_gauges_omit_section(self):
        mod = _load_script("telemetry_report")
        out = io.StringIO()
        mod.render({"meta": {}, "metrics": {}}, out=out)
        assert "-- quant --" not in out.getvalue()
