"""Training-health monitor tests (utils/health.py).

Covers the ISSUE 3 acceptance criteria: a NaN injected at a known layer in
a 2-stage pipeline is attributed to that layer + microbatch + rank in the
health dump AND the flight-recorder ring; cheap mode's health word is
fetched asynchronously (one step behind, no sync on the dispatched step);
``SMP_HEALTH_CHECK=off`` compiles to byte-identical HLO (the tag is
identity and the step program contains no finiteness ops); a simulated
RESOURCE_EXHAUSTED produces a post-mortem dump with the XLA memory
breakdown; loss-scale overflows emit flight-recorder events; and the
odd-length ring-attention padding keeps the flash path exact.
"""

import json
import math
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax

import smdistributed_modelparallel_tpu as smp
from smdistributed_modelparallel_tpu.backend.state import state
from smdistributed_modelparallel_tpu.utils import health, hlo_audit
from smdistributed_modelparallel_tpu.utils import telemetry as tel
from smdistributed_modelparallel_tpu.utils.flight_recorder import flight_recorder


def _metric_series(name):
    return tel.telemetry.report()["metrics"].get(name, {"series": []})["series"]


def _gauge(name, **labels):
    for s in _metric_series(name):
        if all(s["labels"].get(k) == v for k, v in labels.items()):
            return s["value"]
    return None


def _tiny_setup(num_mb=2):
    import flax.linen as nn

    smp.reset()
    smp.init({"microbatches": num_mb})

    class Net(nn.Module):
        @nn.compact
        def __call__(self, x):
            return nn.Dense(8, name="dense")(x)

    model = smp.DistributedModel(Net())
    opt = smp.DistributedOptimizer(optax.sgd(0.1), model)

    @smp.step
    def train(model, x, y):
        out = model(x)
        loss = jnp.mean((out - y) ** 2)
        model.backward(loss)
        return loss

    x = jax.random.normal(jax.random.key(0), (4, 8))
    y = jax.random.normal(jax.random.key(1), (4, 8))
    return model, opt, train, x, y


def _runner(step_fn):
    (runner,) = step_fn._cache.values()
    return runner


def _compiled_hlo(step_fn):
    c = _runner(step_fn).holder.get("compiled")
    if c is None:
        pytest.skip("AOT step executable unavailable on this backend")
    return c.as_text()


class TestModeAndNoOp:
    def test_mode_parsing(self, monkeypatch):
        for raw, want in [("", "off"), ("off", "off"), ("0", "off"),
                          ("cheap", "cheap"), ("1", "cheap"), ("on", "cheap"),
                          ("full", "full"), ("bogus", "off")]:
            monkeypatch.setenv("SMP_HEALTH_CHECK", raw)
            assert health.mode() == want, raw

    def test_tag_is_identity_and_compiles_away(self, monkeypatch):
        """Off mode: a tagged function lowers to byte-identical HLO."""
        monkeypatch.delenv("SMP_HEALTH_CHECK", raising=False)

        def make(tagged):
            def fn(x):
                y = health.tag("probe", x) if tagged else x
                return y * 2.0 + 1.0

            return fn

        x = jnp.ones((4, 4))
        plain = jax.jit(make(False)).lower(x).compile().as_text()
        tagged = jax.jit(make(True)).lower(x).compile().as_text()

        strip = hlo_audit.strip_source_metadata
        assert strip(tagged) == strip(plain)
        assert "line=" not in strip(plain)

    def test_off_mode_step_has_no_sentinel(self, monkeypatch):
        monkeypatch.delenv("SMP_HEALTH_CHECK", raising=False)
        model, opt, train, x, y = _tiny_setup()
        train(model, x, y)
        assert list(_runner(train).health_schema) == []
        assert health.monitor.pending_step is None
        assert health.monitor.checked_steps == []
        assert "is-finite" not in _compiled_hlo(train)


class TestCheapMode:
    def test_async_word_one_step_behind(self, monkeypatch):
        """Cheap mode: step N's word is decoded at step N+1's dispatch —
        never a host read of the step just dispatched."""
        monkeypatch.setenv("SMP_HEALTH_CHECK", "cheap")
        model, opt, train, x, y = _tiny_setup()
        train(model, x, y)
        assert health.monitor.pending_step == 0
        assert health.monitor.checked_steps == []   # no fetch yet
        opt.step()
        train(model, x, y)
        assert health.monitor.pending_step == 1
        assert health.monitor.checked_steps == [0]
        tags = health.monitor.last_check["tags"]
        assert {"loss", "outputs", "grads"} <= set(tags)
        assert all(d["bad"] == 0 for d in tags.values())
        # The sentinel IS in the compiled program in cheap mode.
        assert "is-finite" in _compiled_hlo(train)
        # ... and the checks counter fed telemetry.
        assert _gauge("smp_health_bad_count", tag="loss") == 0

    def test_full_mode_checks_synchronously(self, monkeypatch):
        monkeypatch.setenv("SMP_HEALTH_CHECK", "full")
        model, opt, train, x, y = _tiny_setup()
        train(model, x, y)
        assert health.monitor.checked_steps == [0]
        assert "params" in health.monitor.last_check["tags"]

    def test_input_nan_attributed_to_microbatch(self, monkeypatch, tmp_path):
        monkeypatch.setenv("SMP_HEALTH_CHECK", "cheap")
        monkeypatch.setenv("SMP_HEALTH_PATH", str(tmp_path / "h.json"))
        model, opt, train, x, y = _tiny_setup()
        train(model, x, y)
        opt.step()
        # Rows 2-3 are microbatch 1 of 2.
        x_bad = x.at[2:].set(jnp.nan)
        train(model, x_bad, y)
        health.monitor.flush()
        assert len(health.monitor.trips) == 1
        trip = health.monitor.trips[0]
        att = trip["attribution"]
        assert att["layer"].startswith("input")
        assert att["microbatch"] == 1
        assert trip["tags"]["loss"]["microbatch"] == 1


class TestBisectionParams:
    def test_bisection_uses_dispatch_time_params(self, monkeypatch, tmp_path):
        """A poisoned optimizer update can land before the async word is
        decoded; bisection must re-run with the params the faulting step
        was DISPATCHED with, not the now-poisoned live tree."""
        import flax.linen as nn

        monkeypatch.setenv("SMP_HEALTH_CHECK", "cheap")
        monkeypatch.setenv("SMP_HEALTH_PATH", str(tmp_path / "h.json"))
        smp.reset()
        smp.init({"microbatches": 2})

        class Net(nn.Module):
            @nn.compact
            def __call__(self, x):
                h = nn.relu(nn.Dense(8, name="first")(x))
                return nn.Dense(8, name="second")(h)

        model = smp.DistributedModel(Net())
        opt = smp.DistributedOptimizer(optax.sgd(0.1), model)

        @smp.step
        def train(model, x, y):
            out = model(x)
            loss = jnp.mean((out - y) ** 2)
            model.backward(loss)
            return loss

        x = jax.random.normal(jax.random.key(0), (4, 8))
        y = jax.random.normal(jax.random.key(1), (4, 8))
        train(model, x, y)
        opt.step()
        params = model.params
        params["second"]["kernel"] = jnp.full_like(
            params["second"]["kernel"], jnp.nan
        )
        model.params = params
        train(model, x, y)
        # Simulate the poisoned update landing before decode: every live
        # param goes NaN. Dispatch-time params still say "second".
        model.params = jax.tree_util.tree_map(
            lambda p: jnp.full_like(p, jnp.nan), model.params
        )
        health.monitor.flush()
        att = health.monitor.trips[-1]["attribution"]
        assert att["params_source"] == "dispatch"
        assert att["layer"].startswith("second"), att
        assert att["microbatch"] == 0


class TestPipelineAttribution:
    def test_nan_at_known_layer_attributed(self, monkeypatch, tmp_path):
        """ISSUE 3 acceptance: NaN injected at layer 2 of a 2-stage
        pipeline -> attribution (layer name + microbatch + rank) in the
        health dump and the flight-recorder ring; the sentinel's stage
        entry points at stage 1 (layers 2-3) and not stage 0."""
        from smdistributed_modelparallel_tpu.models.transformer_lm import (
            TransformerLM,
        )
        from tests.models import softmax_xent

        monkeypatch.setenv("SMP_HEALTH_CHECK", "cheap")
        dump_path = str(tmp_path / "health.json")
        monkeypatch.setenv("SMP_HEALTH_PATH", dump_path)
        smp.reset()
        smp.init({"pipeline_parallel_degree": 2, "microbatches": 2,
                  "ddp": True})
        module = TransformerLM(
            vocab_size=32, max_len=12, d_model=16, n_layers=4, n_heads=2
        )
        model = smp.DistributedModel(module)
        opt = smp.DistributedOptimizer(optax.sgd(0.1), model)
        ids = jax.random.randint(jax.random.key(0), (8, 12), 0, 32)

        @smp.step
        def train_step(model, batch):
            logits = model(batch)
            loss = jnp.mean(softmax_xent(logits[:, :-1], batch[:, 1:]))
            model.backward(loss)
            return loss

        train_step(model, ids)
        opt.step()
        params = model.params
        kern = params["layers"]["block"]["attn"]["qkv"]["kernel"]
        params["layers"]["block"]["attn"]["qkv"]["kernel"] = (
            kern.at[2].set(jnp.nan)
        )
        model.params = params
        train_step(model, ids)
        health.monitor.flush()

        assert len(health.monitor.trips) == 1
        trip = health.monitor.trips[0]
        att = trip["attribution"]
        assert att["layer"] == "layers/block#2"
        assert att["microbatch"] == 0
        assert att["rank"] == 0
        # Stage sentinel: stage 1 (layers 2-3) tripped, stage 0 clean.
        assert "pp/1f1b/stage1" in trip["tags"]
        assert "pp/1f1b/stage0" not in trip["tags"]
        # Dump on disk carries the same attribution.
        dumped = json.load(open(dump_path))
        assert dumped["kind"] == "health"
        assert dumped["trips"][-1]["attribution"]["layer"] == "layers/block#2"
        # ... and the ring holds both the trip and the fault events.
        events = [e for e in flight_recorder.snapshot()
                  if e["kind"] == "health"]
        assert any(e["event"] == "trip" for e in events)
        faults = [e for e in events if e["event"] == "fault"]
        assert faults and faults[-1]["tag"] == "layers/block#2"
        assert faults[-1]["microbatch"] == 0
        # Fault attribution counter carries the labels for the report CLI.
        series = _metric_series("smp_health_fault_total")
        assert series and series[0]["labels"]["layer"] == "layers/block#2"
        assert series[0]["labels"]["microbatch"] == "0"


class TestLossScaleEvents:
    def test_overflow_and_growth_recorded(self):
        from smdistributed_modelparallel_tpu.fp16.loss_scaler import (
            DynamicLossScaler,
        )

        tel.telemetry.reset()
        flight_recorder.clear()
        s = DynamicLossScaler(init_scale=2.0 ** 16, scale_window=2)
        s.update(True)                      # overflow: halve
        s.update(False)
        s.update(False)                     # window hit: grow
        events = [e for e in flight_recorder.snapshot()
                  if e["kind"] == "health" and e["event"] == "loss_scale"]
        assert [e["tag"] for e in events] == ["overflow", "growth"]
        assert events[0]["value"] == 2.0 ** 15
        assert _gauge("smp_loss_scale") == s.loss_scale
        counts = {
            s_["labels"]["event"]: s_["value"]
            for s_ in _metric_series("smp_loss_scale_events_total")
        }
        assert counts == {"overflow": 1, "growth": 1}

    def test_static_scaler_overflow_recorded(self):
        from smdistributed_modelparallel_tpu.fp16.loss_scaler import LossScaler

        flight_recorder.clear()
        LossScaler(scale=128.0).update(True)
        events = [e for e in flight_recorder.snapshot()
                  if e["kind"] == "health"]
        assert events and events[0]["tag"] == "static_overflow"


class TestOOMPostmortem:
    def test_classification(self):
        assert health.is_resource_exhausted(
            RuntimeError("RESOURCE_EXHAUSTED: Out of memory allocating 1GB")
        )
        assert health.is_resource_exhausted(ValueError("Out of memory"))
        assert not health.is_resource_exhausted(ValueError("bad shape"))

    def test_postmortem_dump_contents(self, monkeypatch, tmp_path):
        path = str(tmp_path / "oom.json")
        monkeypatch.setenv("SMP_HEALTH_PATH", path)
        smp.reset()
        smp.init({"microbatches": 2})
        compiled = jax.jit(lambda x: x @ x).lower(jnp.ones((8, 8))).compile()
        out = health.oom_postmortem(
            "step", compiled,
            RuntimeError("RESOURCE_EXHAUSTED: Out of memory while trying "
                         "to allocate 2.5GiB"),
        )
        assert out == path
        d = json.load(open(path))
        assert d["kind"] == "oom_postmortem"
        ma = d["memory_analysis"]
        assert {"argument_size_in_bytes", "output_size_in_bytes",
                "temp_size_in_bytes"} <= set(ma)
        assert d["live_buffers"]["total_bytes"] >= 0
        assert d["memory_config"]["microbatches"] == 2
        assert "offload_activations" in d["memory_config"]
        events = [e for e in flight_recorder.snapshot()
                  if e["kind"] == "health" and e["event"] == "oom"]
        assert events

    def test_step_engine_guard_dumps_and_reraises(self, monkeypatch, tmp_path):
        path = str(tmp_path / "oom_step.json")
        monkeypatch.setenv("SMP_HEALTH_PATH", path)
        model, opt, train, x, y = _tiny_setup()
        train(model, x, y)
        runner = _runner(train)

        def boom(*args, **kwargs):
            raise RuntimeError(
                "RESOURCE_EXHAUSTED: Out of memory while trying to "
                "allocate 12.0GiB"
            )

        runner.holder["compiled"] = boom
        with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
            train(model, x, y)
        assert os.path.exists(path)
        assert json.load(open(path))["kind"] == "oom_postmortem"


class TestUpdateStatsGauges:
    def test_grad_and_update_ratio_gauges(self, monkeypatch):
        monkeypatch.setenv("SMP_HEALTH_CHECK", "cheap")
        model, opt, train, x, y = _tiny_setup()
        train(model, x, y)
        opt.step()
        gn = _gauge("smp_grad_norm")
        pn = _gauge("smp_param_norm")
        assert gn is not None and math.isfinite(gn) and gn > 0
        assert pn is not None and pn > 0
        # Default fused path retains the pre-update tree -> ratio present.
        ur = _gauge("smp_update_ratio")
        assert ur is not None and 0 < ur < 1

    def test_disabled_without_health_mode(self, monkeypatch):
        monkeypatch.delenv("SMP_HEALTH_CHECK", raising=False)
        model, opt, train, x, y = _tiny_setup()
        train(model, x, y)
        opt.step()
        assert _gauge("smp_grad_norm") is None


class TestReportCLI:
    def _write_dump(self, path):
        tel.telemetry.reset()
        tel.record_health_check(3, {
            "loss": {"bad": 2.0, "absmax": 11.5, "microbatch": 1},
            "grads": {"bad": 0.0, "absmax": 0.25, "microbatch": -1},
        })
        tel.record_health_trip("loss", 3, 2.0, 11.5, 1)
        tel.record_health_fault("layers/block#2", 0, "loss", 3)
        tel.record_loss_scale("overflow", 32768.0)
        tel.record_update_stats(0.5, 10.0, 0.01)
        tel.record_oom("step_pipeline")
        return tel.telemetry.dump(path)

    @staticmethod
    def _run_cli(path):
        import subprocess
        import sys

        script = os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "scripts", "telemetry_report.py",
        )
        r = subprocess.run(
            [sys.executable, script, path],
            capture_output=True, text=True, timeout=120,
        )
        assert r.returncode == 0, r.stderr[-2000:]
        return r.stdout

    def test_single_dump_health_section(self, tmp_path):
        path = self._write_dump(str(tmp_path / "t.json"))
        out = self._run_cli(path)
        assert "-- health --" in out
        assert "1 trip(s)" in out
        assert "loss" in out and "first_mb=1" in out
        assert "fault: layer=layers/block#2 microbatch=0" in out
        assert "loss scale: 32768" in out
        assert "update ratio: 0.001" in out
        assert "OOM post-mortem dumped for step_pipeline" in out

    def test_directory_mode_health_section(self, tmp_path):
        d = tmp_path / "dumps"
        d.mkdir()
        self._write_dump(str(d / "t.json.rank0"))
        self._write_dump(str(d / "t.json.rank1"))
        out = self._run_cli(str(d))
        assert "-- health --" in out
        # Counters sum across ranks: 2 trips, 2 checks.
        assert "2 trip(s)" in out
        assert "fault: layer=layers/block#2" in out


class TestRingPadding:
    """ADVICE satellite: odd/prime per-shard lengths pad to the next
    chunkable multiple instead of falling back to the O(T^2) body."""

    def test_pad_plan_minimal_padding(self):
        from smdistributed_modelparallel_tpu.ops.context_parallel import (
            _pad_plan, _ring_chunks,
        )

        # Prime just past 2x the kernel envelope: no exact divisor...
        assert _ring_chunks(16411, 8192, 128) is None
        # ... but one padded row away from a 4-way split.
        tl_pad, n_sub = _pad_plan(16411, 8192, 128)
        assert tl_pad - 16411 <= 128
        assert tl_pad % n_sub == 0
        assert 128 <= tl_pad // n_sub <= 8192
        # Already-chunkable lengths plan zero padding.
        assert _pad_plan(8192, 8192, 128) == (8192, 1)
        assert _pad_plan(16384, 8192, 128) == (16384, 2)
        # Impossible floors give up (fallback keeps working).
        assert _pad_plan(7, 8, 16) is None

    @pytest.mark.parametrize("causal", [True, False])
    def test_padded_ring_matches_full_attention(self, causal, monkeypatch):
        from smdistributed_modelparallel_tpu.ops import (
            context_parallel as cp,
            pallas_attention as pk,
        )

        smp.shutdown()
        smp.init({"context_parallel_degree": 2, "ddp": True,
                  "context_parallel_impl": "ring"})
        # Shrink the envelope so Tl=37 (prime) has no exact divisor and
        # the padded flash path must engage (48 = 3 x 16 per shard).
        monkeypatch.setattr(pk, "FORCE_INTERPRET", True)
        monkeypatch.setattr(cp, "_RING_CHUNK", 16)
        monkeypatch.setattr(cp, "_RING_MIN_LEN_INTERPRET", 16)
        assert cp._ring_chunks(37, 16, 16) is None
        assert cp._pad_plan(37, 16, 16) == (48, 3)

        B, T, H, hd = 1, 74, 2, 8
        ks = jax.random.split(jax.random.key(0), 3)
        q = jax.random.normal(ks[0], (B, T, H, hd))
        k = jax.random.normal(ks[1], (B, T, H, hd))
        v = jax.random.normal(ks[2], (B, T, H, hd))
        with jax.set_mesh(state.mesh):
            out = cp.cp_attention(
                q, k, v, scale=1.0 / np.sqrt(hd), causal=causal, impl="ring"
            )
        assert out.shape == (B, T, H, hd)
        s = jnp.einsum("bthd,bshd->bhts", q, k).astype(jnp.float32)
        s = s / np.sqrt(hd)
        if causal:
            mask = jnp.tril(jnp.ones((T, T), bool))
            s = jnp.where(mask[None, None], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        ref = jnp.einsum("bhts,bshd->bthd", p, v.astype(jnp.float32))
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref.astype(q.dtype)), atol=3e-5
        )
