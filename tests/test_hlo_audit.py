"""Compiled-program X-ray (``smp.xray`` / utils/hlo_audit.py) tests.

Covers: the HLO text parsers (collective shapes/bytes, literal and iota
replica groups, permute pairs, mesh-axis attribution incl. world/self/
unattributed), the remat census, fingerprint diff (and its parity with
the stdlib mirror in ``scripts/hlo_report.py``), the ``SMP_HLO_AUDIT=off``
hard no-op, the end-to-end census of a real pp=2 pipeline compile
(gauges, persistence, flight-recorder fingerprint, report CLIs), and the
replication DETECTOR itself: a pp=2/v=2 program compiled with the
stage-axis sharding pins deliberately neutered must be flagged as the
PR-5 replicated-tick-loop failure, with tensor name and wasted bytes.
"""

import importlib.util
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax

import smdistributed_modelparallel_tpu as smp
from smdistributed_modelparallel_tpu.utils import hlo_audit
from smdistributed_modelparallel_tpu.utils.flight_recorder import (
    flight_recorder,
)
from smdistributed_modelparallel_tpu.utils.telemetry import telemetry
from smdistributed_modelparallel_tpu.models.transformer_lm import TransformerLM
from tests.models import softmax_xent

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _mesh22():
    from jax.sharding import Mesh

    return Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("pp", "dp"))


# ----------------------------------------------------------------------
# Parsers + attribution (no compile)
# ----------------------------------------------------------------------


class TestCensusParser:
    def test_literal_groups_attributed_to_axis(self):
        text = (
            "%ar = f32[16,16]{1,0} all-reduce(f32[16,16]{1,0} %x), "
            "channel_id=1, replica_groups={{0,2},{1,3}}, "
            "use_global_device_ids=true, to_apply=%sum\n"
        )
        census = hlo_audit.collective_census(text, mesh=_mesh22())
        assert census["all-reduce"]["count"] == 1
        assert census["all-reduce"]["bytes"] == 16 * 16 * 4
        assert census["all-reduce"]["axes"] == {
            "pp": {"count": 1, "bytes": 1024}
        }

    def test_iota_groups_with_transpose(self):
        # [2,2]<=[2,2]T(1,0): arange(4).reshape(2,2).T -> rows {0,2},{1,3}
        # == the pp-axis groups of the (pp=2, dp=2) mesh.
        text = (
            "%ar = bf16[8]{0} all-reduce(bf16[8]{0} %x), channel_id=1, "
            "replica_groups=[2,2]<=[2,2]T(1,0), "
            "use_global_device_ids=true, to_apply=%sum\n"
        )
        census = hlo_audit.collective_census(text, mesh=_mesh22())
        assert census["all-reduce"]["axes"] == {
            "pp": {"count": 1, "bytes": 16}
        }

    def test_iota_groups_flat(self):
        # [2,2]<=[4]: rows {0,1},{2,3} == dp-axis groups.
        text = (
            "%ag = f32[4,4]{1,0} all-gather(f32[2,4]{1,0} %x), "
            "channel_id=2, replica_groups=[2,2]<=[4], dimensions={0}, "
            "use_global_device_ids=true\n"
        )
        census = hlo_audit.collective_census(text, mesh=_mesh22())
        assert census["all-gather"]["axes"] == {
            "dp": {"count": 1, "bytes": 64}
        }

    def test_permute_pairs_attributed_to_axis(self):
        text = (
            "%cp = f32[4,8]{1,0} collective-permute(f32[4,8]{1,0} %x), "
            "channel_id=3, source_target_pairs={{0,1},{2,3},{1,0},{3,2}}\n"
        )
        census = hlo_audit.collective_census(text, mesh=_mesh22())
        assert census["collective-permute"]["axes"] == {
            "dp": {"count": 1, "bytes": 128}
        }

    def test_world_self_and_unattributed(self):
        text = (
            "%a = f32[4]{0} all-reduce(f32[4]{0} %x), "
            "replica_groups={{0,1,2,3}}, use_global_device_ids=true, "
            "to_apply=%s\n"
            "%b = f32[4]{0} all-reduce(f32[4]{0} %y), "
            "replica_groups={{0},{1},{2},{3}}, "
            "use_global_device_ids=true, to_apply=%s\n"
            "%c = f32[4]{0} all-reduce(f32[4]{0} %z), "
            "replica_groups={{0,3},{1,2}}, use_global_device_ids=true, "
            "to_apply=%s\n"
        )
        census = hlo_audit.collective_census(text, mesh=_mesh22())
        assert set(census["all-reduce"]["axes"]) == {
            "world", "self", "unattributed"
        }

    def test_start_counted_once_done_skipped(self):
        text = (
            "%s = f32[8]{0} all-reduce-start(f32[8]{0} %x), "
            "replica_groups={{0,2},{1,3}}, use_global_device_ids=true, "
            "to_apply=%sum\n"
            "%d = f32[8]{0} all-reduce-done(f32[8]{0} %s)\n"
        )
        census = hlo_audit.collective_census(text, mesh=_mesh22())
        assert census["all-reduce"]["count"] == 1

    def test_tuple_shape_bytes(self):
        assert hlo_audit._shape_bytes(
            "(f32[2,2]{1,0}, bf16[8]{0}, pred[])"
        ) == 16 + 16 + 1

    def test_empty_replica_groups_is_world(self):
        text = (
            "%a = f32[4]{0} all-reduce(f32[4]{0} %x), replica_groups={}, "
            "to_apply=%s\n"
        )
        census = hlo_audit.collective_census(text, mesh=_mesh22())
        assert census["all-reduce"]["axes"] == {
            "world": {"count": 1, "bytes": 16}
        }

    def test_no_mesh_is_unattributed(self):
        text = (
            "%a = f32[4]{0} all-reduce(f32[4]{0} %x), "
            "replica_groups={{0,1}}, to_apply=%s\n"
        )
        census = hlo_audit.collective_census(text, mesh=None)
        assert census["all-reduce"]["axes"] == {
            "unattributed": {"count": 1, "bytes": 16}
        }


class TestRematCensus:
    _DOT = (
        "%dot.{i} = f32[4,16]{{1,0}} dot(f32[4,8]{{1,0}} %a, "
        "f32[8,16]{{1,0}} %b), lhs_contracting_dims={{1}}, "
        "rhs_contracting_dims={{0}}, metadata={{op_name=\"jit(f)/dot\" "
        "stack_frame_id={line}}}\n"
    )

    def test_duplicates_counted_as_recompute(self):
        # The same structural dot three times (a double-forward re-run
        # compiles the body again) + one distinct dot.
        text = (
            self._DOT.format(i=1, line=10)
            + self._DOT.format(i=2, line=10)
            + self._DOT.format(i=3, line=10)
            + self._DOT.format(i=4, line=99)
        )
        remat = hlo_audit.remat_census(text)
        assert remat["dots"] == 4
        assert remat["recomputed_dots"] == 2
        # flops per dot: 2 * (4*16) * 8 = 1024; 2 of 4 are re-runs.
        assert remat["flops"] == 4 * 1024.0
        assert remat["recomputed_flops"] == 2 * 1024.0
        assert remat["fraction"] == 0.5

    def test_no_dots_is_zero(self):
        remat = hlo_audit.remat_census("%add = f32[4]{0} add(%a, %b)\n")
        assert remat["fraction"] == 0.0 and remat["dots"] == 0


class TestWhileCarries:
    def test_carry_bytes_and_op_name(self):
        text = (
            '%while.9 = (s32[], f32[2,4,8]{2,1,0}, f32[16]{0}) '
            'while((s32[], f32[2,4,8]{2,1,0}, f32[16]{0}) %tuple.1), '
            'condition=%cond, body=%body, metadata={op_name='
            '"jit(step)/smp/pipeline/steady/while" source_file="p.py" '
            'source_line=5}\n'
        )
        carries = hlo_audit.while_carries(text)
        assert len(carries) == 1
        assert carries[0]["bytes"] == 4 + 2 * 4 * 8 * 4 + 16 * 4
        assert "smp/pipeline" in carries[0]["op_name"]


# ----------------------------------------------------------------------
# Fingerprint diff (+ parity with the stdlib CLI mirror)
# ----------------------------------------------------------------------


def _mk_fp(permutes=10, remat=0.2, replicated=()):
    return {
        "name": "step_pipeline_1f1b",
        "config": {"pipeline": "interleaved", "pp": 2, "tp": 1, "v": 1,
                   "mb": 4},
        "collectives": {
            "collective-permute": {
                "count": permutes, "bytes": permutes * 100,
                "axes": {"pp": {"count": permutes,
                                "bytes": permutes * 100}},
            },
        },
        "replicated": list(replicated),
        "replicated_bytes": sum(
            f.get("bytes_wasted", 0) for f in replicated
        ),
        "remat": {"fraction": remat, "dots": 10, "recomputed_dots": 2,
                  "flops": 100.0, "recomputed_flops": 20.0},
        "memory": {"temp_bytes": 1000},
        "flops": 12345.0,
        "hlo_sha256": "aa" * 32,
    }


class TestDiff:
    def test_identical_is_clean(self):
        assert hlo_audit.diff(_mk_fp(), _mk_fp()) == []

    def test_detects_permute_count_and_axis_delta(self):
        changes = hlo_audit.diff(_mk_fp(permutes=10), _mk_fp(permutes=0))
        fields = {c["field"] for c in changes}
        assert "collectives.collective-permute.pp.count" in fields
        assert "collectives.collective-permute.pp.bytes" in fields

    def test_remat_tolerance(self):
        assert hlo_audit.diff(_mk_fp(remat=0.20), _mk_fp(remat=0.21)) == []
        changes = hlo_audit.diff(_mk_fp(remat=0.20), _mk_fp(remat=0.30))
        assert any(c["field"] == "remat.fraction" for c in changes)

    def test_replicated_findings_delta(self):
        bad = _mk_fp(replicated=[{
            "kind": "replicated_loop_carry", "tensor": "while.1",
            "bytes": 100, "bytes_wasted": 50, "detail": "d",
        }])
        fields = {c["field"] for c in hlo_audit.diff(_mk_fp(), bad)}
        assert {"replicated_bytes", "replicated_findings"} <= fields

    def test_semantic_fields_skip_memory_and_hashes(self):
        a, b = _mk_fp(), _mk_fp()
        b["memory"] = {"temp_bytes": 999999}
        b["hlo_sha256"] = "bb" * 32
        b["flops"] = 1.0
        assert hlo_audit.diff(a, b, fields=hlo_audit.SEMANTIC_FIELDS) == []
        assert hlo_audit.diff(a, b) != []

    def test_cli_mirror_agrees(self):
        """scripts/hlo_report.py vendors the diff for stdlib-only use;
        this pins the two implementations together."""
        spec = importlib.util.spec_from_file_location(
            "hlo_report", os.path.join(_REPO, "scripts", "hlo_report.py")
        )
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        for a, b in (
            (_mk_fp(), _mk_fp()),
            (_mk_fp(permutes=10), _mk_fp(permutes=0)),
            (_mk_fp(remat=0.2), _mk_fp(remat=0.5)),
        ):
            for fields in (None, hlo_audit.SEMANTIC_FIELDS):
                assert (
                    mod.diff_fingerprints(a, b, fields=fields)
                    == hlo_audit.diff(a, b, fields=fields)
                )


# ----------------------------------------------------------------------
# SMP_HLO_AUDIT=off: a hard no-op
# ----------------------------------------------------------------------


class _UntouchableExecutable:
    """Any attribute access (as_text, cost_analysis, ...) fails the test:
    the off path must return before touching the executable."""

    def __getattr__(self, name):
        raise AssertionError(
            f"SMP_HLO_AUDIT=off touched the executable ({name})"
        )


class TestAuditOff:
    def test_off_is_hard_noop(self, monkeypatch):
        monkeypatch.setenv("SMP_HLO_AUDIT", "off")
        before_audits = dict(hlo_audit.audits)
        fam = telemetry._families.get("smp_hlo_audits_total")
        before = fam.value if fam is not None else 0
        assert hlo_audit.maybe_audit(
            "step", _UntouchableExecutable()
        ) is None
        fam = telemetry._families.get("smp_hlo_audits_total")
        after = fam.value if fam is not None else 0
        assert after == before
        assert dict(hlo_audit.audits) == before_audits

    def test_zero_also_disables(self, monkeypatch):
        monkeypatch.setenv("SMP_HLO_AUDIT", "0")
        assert not hlo_audit.enabled()
        monkeypatch.setenv("SMP_HLO_AUDIT", "on")
        assert hlo_audit.enabled()
        monkeypatch.delenv("SMP_HLO_AUDIT")
        assert hlo_audit.enabled()


# ----------------------------------------------------------------------
# End-to-end: real pipeline compiles
# ----------------------------------------------------------------------


def _train_pp(cfg, step_fn=None):
    smp.reset()
    smp.init(cfg)
    model = smp.DistributedModel(TransformerLM(
        vocab_size=32, max_len=12, d_model=16, n_layers=4, n_heads=2,
    ))
    optimizer = smp.DistributedOptimizer(optax.sgd(0.1), model)
    ids = jax.random.randint(jax.random.key(0), (8, 12), 0, 32)

    if step_fn is None:
        @smp.step
        def step_fn(model, batch):
            logits = model(batch)
            loss = jnp.mean(softmax_xent(logits[:, :-1], batch[:, 1:]))
            model.backward(loss)
            return loss

    step_fn(model, ids)
    optimizer.step()
    return step_fn


class TestEndToEnd:
    def test_census_persistence_and_reports(self, tmp_path, monkeypatch):
        """One pp=2 compile exercises the whole surface: stored audit,
        per-axis census, gauges, SMP_HLO_AUDIT_PATH persistence, the
        flight-recorder fingerprint, and both report CLIs."""
        dump = tmp_path / "xray.json"
        monkeypatch.setenv("SMP_HLO_AUDIT_PATH", str(dump))
        step_fn = _train_pp({
            "pipeline_parallel_degree": 2, "microbatches": 4, "ddp": True,
        })
        runner = list(step_fn._cache.values())[0]
        if runner.holder.get("compiled") is None:
            pytest.skip("AOT step executable unavailable on this backend")
        audit = runner.hlo_audit
        assert audit is not None, "post-compile audit did not run"
        # The PR-5 guard, structured: pp-axis permutes present, detector
        # clean.
        assert audit.collective_count("collective-permute", axis="pp") > 0
        assert audit.collective_count("collective-permute") >= \
            audit.collective_count("collective-permute", axis="pp")
        assert audit.findings == []
        assert audit.replicated_bytes == 0
        assert 0.0 <= audit.remat["fraction"] < 1.0
        assert audit.memory.get("temp_bytes", 0) > 0
        assert audit.key, "audit not keyed by the step-cache key"
        # Telemetry gauges.
        rep = telemetry.report()
        series = rep["metrics"]["smp_hlo_collective_ops"]["series"]
        labels = [s["labels"] for s in series]
        assert any(
            l["op"] == "collective-permute" and l["axis"] == "pp"
            for l in labels
        )
        # Persistence, keyed by name@cache-key.
        data = json.loads(dump.read_text())
        (key_id,) = [
            k for k in data["programs"] if k.endswith(audit.key)
        ]
        assert key_id.startswith(audit.name + "@")
        assert data["programs"][key_id]["fingerprint"] == \
            audit.fingerprint_hash
        # Flight-recorder compile event carries the fingerprint.
        events = [
            e for e in flight_recorder.snapshot()
            if e.get("kind") == "compile" and e.get("event") == "hlo_audit"
        ]
        assert events and events[-1]["fingerprint"] == audit.fingerprint_hash
        # telemetry_report.py renders the section (stdlib subprocess).
        tm = tmp_path / "tm.json"
        telemetry.dump(str(tm))
        out = subprocess.run(
            [sys.executable, os.path.join(_REPO, "scripts",
                                          "telemetry_report.py"), str(tm)],
            capture_output=True, text=True, timeout=120,
        )
        assert out.returncode == 0, out.stderr[-2000:]
        assert "-- hlo audit --" in out.stdout
        assert "collective-permute" in out.stdout
        # hlo_report.py show + diff (clean against itself; dirty + rc=1
        # once the census moves).
        show = subprocess.run(
            [sys.executable, os.path.join(_REPO, "scripts",
                                          "hlo_report.py"),
             "show", str(dump)],
            capture_output=True, text=True, timeout=120,
        )
        assert show.returncode == 0, show.stderr[-2000:]
        assert "collective-permute" in show.stdout
        mutated = json.loads(dump.read_text())
        fp = mutated["programs"][key_id]
        fp["collectives"]["collective-permute"]["axes"]["pp"]["count"] = 0
        (tmp_path / "mutated.json").write_text(json.dumps(mutated))
        diff_clean = subprocess.run(
            [sys.executable, os.path.join(_REPO, "scripts",
                                          "hlo_report.py"),
             "diff", str(dump), str(dump), "--check"],
            capture_output=True, text=True, timeout=120,
        )
        assert diff_clean.returncode == 0, diff_clean.stdout
        assert "clean" in diff_clean.stdout
        diff_dirty = subprocess.run(
            [sys.executable, os.path.join(_REPO, "scripts",
                                          "hlo_report.py"),
             "diff", str(dump), str(tmp_path / "mutated.json"), "--check"],
            capture_output=True, text=True, timeout=120,
        )
        assert diff_dirty.returncode == 1, diff_dirty.stdout
        assert "collectives.collective-permute.pp.count" in diff_dirty.stdout

    def test_detector_flags_replicated_tick_loop(self, monkeypatch):
        """The acceptance gate for the detector: compile the pp=2/v=2
        program with the stage-axis sharding pins neutered (the exact
        PR-5 failure — GSPMD replicates the whole tick loop, zero
        pp-axis permutes) and the audit must flag the replicated loop
        carry with a tensor name and a wasted-byte estimate."""
        monkeypatch.setattr(
            jax.lax, "with_sharding_constraint", lambda x, *_a, **_k: x
        )
        step_fn = _train_pp({
            "pipeline_parallel_degree": 2, "microbatches": 4, "ddp": True,
            "virtual_pipeline_degree": 2,
        })
        audit = hlo_audit.of_step_function(step_fn)
        if audit is None:
            pytest.skip("AOT step executable unavailable on this backend")
        assert audit.collective_count("collective-permute", axis="pp") == 0
        kinds = {f["kind"] for f in audit.findings}
        assert "replicated_loop_carry" in kinds
        (finding,) = [
            f for f in audit.findings
            if f["kind"] == "replicated_loop_carry"
        ]
        # The tick loop is a while op; its op_name names the culprit.
        assert "while" in finding["tensor"]
        assert finding["bytes"] > 0
        # pp=2: half the carry bytes are pure waste.
        assert finding["bytes_wasted"] == finding["bytes"] // 2
        assert audit.replicated_bytes > 0
        assert "0 pp-axis collective-permutes" in finding["detail"]
