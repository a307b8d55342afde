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
# Op index: a record per instruction, the census as a sum over them
# ----------------------------------------------------------------------

_J = "jit(full_impl)/"
# sha256 of tests/goldens/hlo_fingerprints.json as PR 25's tree has it
# (PR 23's, with `1f1b_pp2_mb4` regenerated for the conditional sub-steps).
_GOLDENS_SHA256 = (
    "1141a9a2924aaa36b0217d1df780c29acd86d3e20ca39c53ceaa6f107f339090")
# op_name (as jax 0.9.0 and this library write them on the two benchmark
# cells' compiled steps), instruction name -> phase, nearest smp scope.
_PHASE_CASES = [
    (_J + "smp/optimizer/update/mul", "fusion.1", "optimizer",
     "smp/optimizer/update"),
    # First match wins: whatever else an op under the update's scope says.
    (_J + "smp/optimizer/update/transpose(jvp())/add", "add.2", "optimizer",
     "smp/optimizer/update"),
    (_J + "transpose(jvp(jvp()))/checkpoint/rematted_computation/tanh",
     "tanh.3", "recompute", None),
    (_J + "while/body/closed_call/transpose(jvp(TransformerLM))/"
     "layers/block/proj/dot_general", "fusion.415.remat2", "recompute", None),
    (_J + "while/body/jvp(TransformerLM)/layers/block/fc/dot_general",
     "gte.remat.13", "recompute", None),
    (_J + "smp/pipeline/steady/while/body/closed_call/smp/pipeline/tick_bwd"
     "/vmap(jvp())/while/body/DistributedTransformerLayer/output/dot_general",
     "fusion.711", "recompute", "smp/pipeline/tick_bwd"),
    (_J + "smp/pipeline/steady/while/body/closed_call/smp/pipeline/tick_bwd"
     "/vmap(transpose(jvp()))/attention/shard_map/psum", "all-reduce.96",
     "backward", "smp/pipeline/tick_bwd"),
    (_J + "smp/pipeline/cooldown/smp/pipeline/tick_bwd_weight/dot_general",
     "fusion.5", "backward", "smp/pipeline/tick_bwd_weight"),
    (_J + "smp/pipeline/cooldown_weight/while/body/add", "add.6",
     "backward", "smp/pipeline/cooldown_weight"),
    (_J + "while/body/closed_call/transpose(jvp(TransformerLM))/"
     "layers/block/attn/qkv/dot_general", "multiply_reduce_fusion.14",
     "backward", None),
    (_J + "while/body/closed_call/smp/step/accumulate/add",
     "select_add_fusion.46", "backward", "smp/step/accumulate"),
    (_J + "while/body/closed_call/jvp(TransformerLM)/layers/block/fc/"
     "dot_general", "fusion.7", "forward", None),
    (_J + "smp/pipeline/steady/while/body/closed_call/smp/pipeline/tick_fwd"
     "/vmap()/while/body/DistributedTransformerLayer/output/dot_general",
     "all-reduce.93", "forward", "smp/pipeline/tick_fwd"),
    (_J + "smp/pipeline/embed/jit(_take)/gather", "fusion.8", "forward",
     "smp/pipeline/embed"),
    (_J + "smp/pipeline/steady/smp/pipeline/head/jvp()/reduce_sum",
     "reduce.9", "forward", "smp/pipeline/head"),
    (_J + "smp/step/cast_params/convert_element_type",
     "convert_element_type.127", "forward", "smp/step/cast_params"),
    (_J + "jit(_threefry_split)/threefry2x32", "fusion.10", "other", None),
    (_J + "smp/pipeline/steady/while/body/dynamic_slice", "fusion.11",
     "other", "smp/pipeline/steady"),
    ("", "copy.601", "other", None),
]


def _instr(name, op_name, rhs="f32[8]{0} fusion(%p), kind=kLoop"):
    meta = f', metadata={{op_name="{op_name}" stack_frame_id=3}}' \
        if op_name else ""
    return f"  %{name} = {rhs}{meta}\n"


class TestOpIndex:
    @pytest.mark.parametrize(
        "op_name,instr,phase,scope", _PHASE_CASES,
        ids=[f"{c[2]}:{c[1]}" for c in _PHASE_CASES])
    def test_phase_and_scope_from_the_markers(self, op_name, instr, phase,
                                              scope):
        assert hlo_audit.phase_of(op_name, instr) == phase
        (rec,) = hlo_audit.op_records(_instr(instr, op_name)).values()
        # Nested scopes are all listed too, outermost first (PR 27).
        nested = rec.pop("scopes", None)
        assert rec == {"phase": phase, "scope": scope}
        assert nested is None or (len(nested) > 1 and nested[-1] == scope
                                  and nested == hlo_audit.scopes_of(op_name))
        assert phase in hlo_audit.PHASES

    def test_of_a_joined_op_name_the_first_part_decides(self):
        fwd = _J + "jvp(Net)/dot_general"
        bwd = _J + "transpose(jvp(Net))/dot_general"
        upd = _J + "smp/optimizer/update/sub"
        assert hlo_audit.phase_of(f"{fwd};{bwd};{upd}") == "forward"
        assert hlo_audit.phase_of(f"{upd};{fwd}") == "optimizer"
        assert hlo_audit.scope_of(f"{fwd};{upd}") is None
        assert hlo_audit.scope_of(f"{upd};{fwd}") == "smp/optimizer/update"
        index = hlo_audit.op_records(_instr("fusion.3", f"{bwd};{fwd}"))
        assert index == {"fusion.3": {"phase": "backward", "scope": None}}

    def test_keys_are_the_names_a_trace_prints(self):
        text = (
            "HloModule jit_step, is_scheduled=true\n\n"
            "%fused_computation.1 (p: f32[8]) -> f32[8] {\n"
            "  %p = f32[8]{0} parameter(0)\n"
            + _instr("neg.1", _J + "jvp(Net)/neg", "f32[8]{0} negate(%p)")
            .replace("  %neg", "  ROOT %neg") +
            "}\n\n"
            "ENTRY %main.9 (a: f32[8]) -> f32[8] {\n"
            "  %a = f32[8]{0} parameter(0)\n"
            "  ROOT %fusion.3 = f32[8]{0} fusion(%a), kind=kLoop, "
            "calls=%fused_computation.1\n"
            "}\n"
        )
        index = hlo_audit.op_records(text)
        assert list(index) == ["p", "neg.1", "a", "fusion.3"]
        # A fusion the compiler left anonymous takes its body's op_name.
        assert index["fusion.3"]["phase"] == "forward"
        assert index["a"] == {"phase": "other", "scope": None}

    def test_anonymous_fusion_takes_its_roots_op_name_else_the_first(self):
        bwd = _J + "transpose(jvp(Net))/mul"
        text = (
            "%fused_computation.2 (p: f32[8]) -> f32[8] {\n"
            + _instr("mul.1", _J + "smp/optimizer/update/mul",
                     "f32[8]{0} multiply(%p, %p)")
            + _instr("mul.2", bwd, "f32[8]{0} multiply(%mul.1, %p)")
            .replace("  %mul.2", "  ROOT %mul.2") +
            "}\n"
            "%fused_computation.3 (p: f32[8]) -> f32[8] {\n"
            + _instr("mul.3", _J + "smp/optimizer/update/mul",
                     "f32[8]{0} multiply(%p, %p)")
            + "  ROOT %b = f32[8]{0} bitcast(%mul.3)\n"
            "}\n"
            "  %fusion.4 = f32[8]{0} fusion(%a), kind=kLoop, "
            "calls=%fused_computation.2\n"
            "  %fusion.5 = f32[8]{0} fusion(%a), kind=kLoop, "
            "calls=%fused_computation.3\n"
        )
        index = hlo_audit.op_records(text)
        assert index["fusion.4"]["phase"] == "backward"
        assert index["fusion.5"] == {
            "phase": "optimizer", "scope": "smp/optimizer/update"}

    def test_an_unmarked_op_takes_the_phase_of_its_first_marked_operand(
            self):
        """The gradient accumulation and the compiler's copies carry no
        marker (and none of the scopes a later build adds, where a compile
        cache hands back an older build's executable)."""
        bwd = _J + "while/body/closed_call/transpose(jvp(Net))/while"
        text = (
            _instr("while.269", bwd, "bf16[8]{0} get-tuple-element(%while.2)"
                   ", index=13")
            + "  %gte.5 = f32[8]{0} get-tuple-element(%arg_tuple.0), index=1\n"
            + _instr("select_add_fusion.46",
                     _J + "while/body/closed_call/add",
                     "f32[8]{0} fusion(%gte.5, %while.269, %compare.7), "
                     "kind=kLoop")
            + "  %gte.6 = f32[8]{0} get-tuple-element(%select_add_fusion.46)"
            ", index=0\n"
            "  %copy.601 = f32[8]{0} copy(%gte.6)\n"
            + _instr("convert.1", _J + "convert_element_type",
                     "bf16[8]{0} convert(%params.1)")
        )
        index = hlo_audit.op_records(text)
        assert index["gte.5"]["phase"] == "other"
        assert index["select_add_fusion.46"]["phase"] == "backward"
        assert index["copy.601"]["phase"] == "backward"      # a chain
        assert index["convert.1"]["phase"] == "other"
        assert {r["scope"] for r in index.values()} == {None}

    @pytest.mark.parametrize("line,record", [
        ("%ar = f32[16,16]{1,0} all-reduce(f32[16,16]{1,0} %x), "
         "channel_id=1, replica_groups={{0,2},{1,3}}, "
         "use_global_device_ids=true, to_apply=%sum",
         {"op": "all-reduce", "axis": "pp", "bytes": 1024}),
        ("%ag = f32[4,4]{1,0} all-gather(f32[2,4]{1,0} %x), channel_id=2, "
         "replica_groups=[2,2]<=[4], dimensions={0}, "
         "use_global_device_ids=true",
         {"op": "all-gather", "axis": "dp", "bytes": 64}),
        ("%ar2 = bf16[8]{0} all-reduce(bf16[8]{0} %x), channel_id=1, "
         "replica_groups=[2,2]<=[2,2]T(1,0), use_global_device_ids=true, "
         "to_apply=%sum",
         {"op": "all-reduce", "axis": "pp", "bytes": 16}),
        ("%cp = f32[4,8]{1,0} collective-permute(f32[4,8]{1,0} %x), "
         "channel_id=3, source_target_pairs={{0,1},{2,3},{1,0},{3,2}}",
         {"op": "collective-permute", "axis": "dp", "bytes": 128}),
    ], ids=["literal", "iota", "iota-transposed", "permute"])
    def test_each_collective_carries_its_axis(self, line, record):
        (rec,) = hlo_audit.op_records(line + "\n", mesh=_mesh22()).values()
        assert rec == {"phase": "other", "scope": None, **record}

    _PROGRAM = (
        "  %ars = f32[8]{0} all-reduce-start(f32[8]{0} %x), "
        "replica_groups={{0,2},{1,3}}, use_global_device_ids=true, "
        "to_apply=%sum, metadata={op_name=\"" + _J
        + "transpose(jvp(Net))/psum\"}\n"
        "  %ard = f32[8]{0} all-reduce-done(f32[8]{0} %ars)\n"
        "  %ar.2 = (f32[4]{0}, f32[4]{0}) all-reduce(%a, %b), "
        "replica_groups={{0,1},{2,3}}, use_global_device_ids=true, "
        "to_apply=%sum\n"
        "  %ag = f32[4,4]{1,0} all-gather(f32[2,4]{1,0} %x), "
        "replica_groups=[2,2]<=[4], dimensions={0}, "
        "use_global_device_ids=true\n"
        "  %cps = (f32[4]{0}, f32[4]{0}) collective-permute-start(%y), "
        "source_target_pairs={{0,2},{1,3}}\n"
        "  %cpd = f32[4]{0} collective-permute-done(%cps)\n"
        "  %f = f32[4]{0} fusion(%cpd), kind=kLoop\n"
    )

    def test_done_half_takes_axis_and_phase_from_its_start(self):
        index = hlo_audit.op_records(self._PROGRAM, mesh=_mesh22())
        assert index["ard"] == {
            "phase": "backward", "scope": None, "op": "all-reduce",
            "axis": "pp", "bytes": 0, "done": True}
        assert index["cpd"]["axis"] == index["cps"]["axis"] == "pp"
        assert index["ar.2"]["axis"] == "dp"

    def test_census_is_the_sum_over_the_index(self):
        index = hlo_audit.op_records(self._PROGRAM, mesh=_mesh22())
        census = hlo_audit.collective_census(self._PROGRAM, mesh=_mesh22())
        assert census == hlo_audit.census_of(index)
        assert census == {
            "all-reduce": {"count": 2, "bytes": 64, "axes": {
                "pp": {"count": 1, "bytes": 32},
                "dp": {"count": 1, "bytes": 32}}},
            "all-gather": {"count": 1, "bytes": 64, "axes": {
                "dp": {"count": 1, "bytes": 64}}},
            "collective-permute": {"count": 1, "bytes": 32, "axes": {
                "pp": {"count": 1, "bytes": 32}}},
        }
        for op, ent in census.items():
            mine = [r for r in index.values()
                    if r.get("op") == op and not r.get("done")]
            assert ent["count"] == len(mine)
            assert ent["bytes"] == sum(r["bytes"] for r in mine)

    def test_compiled_step_has_every_phase(self):
        """``value_and_grad`` of checkpointed layers plus an update under
        the optimizer's scope, as the installed JAX names them."""
        def layer(x, w):
            return jnp.tanh(x @ w)

        def loss(params, x):
            for w in params:
                x = jax.checkpoint(layer)(x, w)
            return jnp.sum(x * x)

        def step(params, x):
            value, grads = jax.value_and_grad(loss)(params, x)
            with jax.named_scope("smp/optimizer/update"):
                params = [w - 0.1 * g for w, g in zip(params, grads)]
            return value, params

        params = [jnp.ones((16, 16))] * 3
        compiled = jax.jit(step).lower(params, jnp.ones((4, 16))).compile()
        index = hlo_audit.op_records(compiled.as_text())
        phases = {rec["phase"] for rec in index.values()}
        assert phases == set(hlo_audit.PHASES)
        scopes = {rec["scope"] for rec in index.values()}
        assert scopes == {None, "smp/optimizer/update"}
        assert hlo_audit.census_of(index) == {}

    def test_index_is_held_but_never_persisted_or_hashed(self):
        text_fp = {}
        for with_index in (False, True):
            audit = hlo_audit.ProgramAudit(
                "step", "k", {}, {"fraction": 0.0}, {}, [], 1.0, 2.0, "sha",
                {"pp": 1},
                op_index={"fusion.1": {"phase": "forward", "scope": None}}
                if with_index else None,
            )
            text_fp[with_index] = json.dumps(audit.as_dict(), sort_keys=True)
            assert "op_index" not in audit.as_dict()
            assert "op_index" not in audit.fingerprint
        assert text_fp[False] == text_fp[True]
        assert audit.op_index == {
            "fusion.1": {"phase": "forward", "scope": None}}

    def test_committed_goldens_are_as_committed(self):
        """The goldens hash nothing the index adds: the file's bytes are
        the ones the last regeneration committed."""
        import hashlib

        path = os.path.join(_REPO, "tests", "goldens",
                            "hlo_fingerprints.json")
        with open(path, "rb") as f:
            assert hashlib.sha256(f.read()).hexdigest() == _GOLDENS_SHA256

    def test_off_builds_no_index(self, monkeypatch):
        monkeypatch.setenv("SMP_HLO_AUDIT", "off")
        monkeypatch.setattr(hlo_audit, "audits", {})
        assert hlo_audit.maybe_audit(
            "step", _UntouchableExecutable()) is None
        assert hlo_audit.op_index("step") == {}

    def test_accessor_returns_the_latest_audit_of_a_compiled_step(self):
        """A real ``@smp.step`` compile: the index of program ``step``
        names the optimizer update, the accumulation and both passes."""
        smp.reset()
        smp.init({"microbatches": 2, "bf16": True})
        model = smp.DistributedModel(TransformerLM(
            vocab_size=32, max_len=12, d_model=16, n_layers=2, n_heads=2))
        optimizer = smp.DistributedOptimizer(optax.sgd(0.1), model)

        @smp.step
        def step_fn(model, batch):
            logits = model(batch)
            loss = jnp.mean(softmax_xent(logits[:, :-1], batch[:, 1:]))
            model.backward(loss)
            return loss

        step_fn(model, jax.random.randint(jax.random.key(0), (4, 12), 0, 32))
        optimizer.step()
        index = hlo_audit.op_index("step")
        assert index is hlo_audit.audits["step"].op_index and index
        assert index is hlo_audit.of_step_function(step_fn).op_index
        by_scope = {}
        for rec in index.values():
            by_scope.setdefault(rec["scope"], set()).add(rec["phase"])
        assert by_scope["smp/optimizer/update"] == {"optimizer"}
        assert by_scope["smp/step/accumulate"] == {"backward"}
        assert by_scope["smp/step/cast_params"] == {"forward"}
        assert {"forward", "backward"} <= by_scope[None]
        assert hlo_audit.census_of(index) == \
            hlo_audit.audits["step"].census


# ----------------------------------------------------------------------
# Kernels the compiler names itself, and the join of a trace to the tree
# ----------------------------------------------------------------------

_W = _J + "while/body/smp/step/user/layer/smp/layer/full/output/while/body/"
_KERNEL = ('custom_call_target="tpu_custom_call", frontend_attributes={'
           'mosaic_fusion_entry_point="true",ragged_dot_tiling="512,256,256"}'
           ', metadata={op_name="ragged-dot-none"}')


def _chunk_body(product_operands, product_user_scope,
                product=_KERNEL, rows_scope="smp/moe/experts"):
    """A chunk loop's body as the TPU compiler leaves it: the group
    metadata kernel, its elements, the rows made ready under
    ``rows_scope``, the grouped product, its user."""
    return (
        "%body (p: (f32[8], f32[8])) -> f32[8] {\n"
        "  %sizes = s32[4]{0} parameter(0)\n"
        "  %meta.1 = (s32[5]{0}, s32[1]{0}) custom-call(%sizes), "
        'custom_call_target="tpu_custom_call", '
        'metadata={op_name="ragged-dot-metadata"}\n'
        "  %gte.1 = s32[5]{0} get-tuple-element(%meta.1), index=0\n"
        "  %weights = bf16[4,8,8]{2,1,0} get-tuple-element(%p), index=1\n"
        "  %buffer = bf16[16,8]{1,0} custom-call(), "
        'custom_call_target="AllocateBuffer"\n'
        + _instr("rows.1", _W + rows_scope + "/select_n",
                 "bf16[16,8]{1,0} fusion(%x), kind=kLoop")
        + f"  %ragged-dot-none.7 = bf16[16,8]{{1,0}} custom-call(%gte.1, "
        f"{product_operands}), {product}\n"
        + _instr("user.1", _W + product_user_scope + "/add",
                 "f32[16,8]{1,0} fusion(%ragged-dot-none.7), kind=kLoop")
        + "}\n")


class TestCompilerKernels:
    """B of PR 39: a kernel the compiler made of one instruction keeps no
    path in its ``op_name``; the index marks it and gives it the scopes
    of the code that made it."""

    def test_takes_the_scopes_of_the_rows_it_is_given_not_of_its_user(self):
        """The second product of the expert FFN: its rows are made under
        ``smp/moe/experts``, its result feeds ``smp/moe/combine``."""
        index = hlo_audit.op_records(
            _chunk_body("%rows.1, %weights", "smp/moe/combine"))
        assert index["ragged-dot-none.7"] == {
            "phase": "other", "kernel": "ragged_dot", "inherited": True,
            "scope": "smp/moe/experts",
            "scopes": ("smp/step/user", "smp/layer/full",
                       "smp/moe/experts")}
        # the rows and the user keep what their own op_name says
        assert index["rows.1"]["scope"] == "smp/moe/experts"
        assert index["user.1"]["scope"] == "smp/moe/combine"
        assert "inherited" not in index["rows.1"]

    def test_rows_from_a_buffer_of_the_compilers_are_found_from_the_user(
            self):
        index = hlo_audit.op_records(
            _chunk_body("%buffer, %weights", "smp/moe/experts",
                        rows_scope="smp/moe/dispatch"))
        rec = index["ragged-dot-none.7"]
        assert (rec["scope"], rec["inherited"]) == ("smp/moe/experts", True)
        assert rec["scopes"][-2:] == ("smp/layer/full", "smp/moe/experts")

    def test_the_metadata_kernel_is_found_through_what_it_feeds(self):
        """Its operand is a parameter and its users are tuple elements:
        looked through to the product, itself still unmarked, and on to
        the product's user."""
        index = hlo_audit.op_records(
            _chunk_body("%rows.1, %weights", "smp/moe/experts"))
        assert index["meta.1"]["kernel"] == "ragged_dot_metadata"
        assert index["meta.1"]["scope"] == "smp/moe/experts"
        assert index["meta.1"]["inherited"] is True
        assert index["gte.1"]["scope"] is None      # only kernels inherit

    def test_with_no_marked_neighbour_it_stays_unscoped(self):
        text = (
            "  %a = bf16[16,8]{1,0} parameter(0)\n"
            f"  %ragged-dot-none.2 = bf16[16,8]{{1,0}} custom-call(%a), "
            f"{_KERNEL}\n"
            "  %b = bf16[16,8]{1,0} copy(%ragged-dot-none.2)\n")
        index = hlo_audit.op_records(text)
        assert index["ragged-dot-none.2"] == {
            "phase": "other", "scope": None, "kernel": "ragged_dot"}
        assert set(index["b"]) == {"phase", "scope"}

    def test_neighbours_that_disagree_leave_what_they_share(self):
        """Rows from one layer's scope and a user... both operands marked,
        under different expert-layer scopes: the layer is what is left."""
        text = _chunk_body("%rows.1, %rows.2", "smp/moe/combine").replace(
            "  %ragged-dot-none.7", _instr(
                "rows.2", _W + "smp/moe/dispatch/gather",
                "bf16[16,8]{1,0} fusion(%x), kind=kLoop")
            + "  %ragged-dot-none.7")
        rec = hlo_audit.op_records(text)["ragged-dot-none.7"]
        assert rec["scopes"] == ("smp/step/user", "smp/layer/full")
        assert rec["scope"] == "smp/layer/full"

    def test_user_code_alone_on_one_side_defers_to_the_other(self):
        text = (
            _instr("rows.1", _J + "smp/step/user/mul",
                   "bf16[16,8]{1,0} fusion(%x), kind=kLoop")
            + f"  %ragged-dot-none.3 = bf16[16,8]{{1,0}} custom-call("
            f"%rows.1), {_KERNEL}\n"
            + _instr("user.1", _J + "smp/step/user/smp/moe/experts/add",
                     "f32[16,8]{1,0} fusion(%ragged-dot-none.3), kind=kLoop"))
        rec = hlo_audit.op_records(text)["ragged-dot-none.3"]
        assert rec["scopes"] == ("smp/step/user", "smp/moe/experts")

    def test_a_compilers_copy_stays_unscoped_and_is_told_what_it_is_near(
            self):
        """Only kernels inherit: a layout copy beside the products, the
        halves of an asynchronous copy and a fusion the compiler made with
        no name inside keep ``scope: None`` (no reader of scopes counts
        them anew) and carry ``near`` for the account of the unscoped."""
        text = _chunk_body("%copy.5, %weights", "smp/moe/experts").replace(
            "  %ragged-dot-none.7",
            "  %copy.5 = bf16[16,8]{0,1} copy(%rows.1)\n"
            "  %copy-start.6 = (bf16[16,8]{1,0}, u32[]) copy-start(%copy.5)\n"
            "  %copy-done.6 = bf16[16,8]{1,0} copy-done(%copy-start.6)\n"
            "  %fusion.9 = f32[128]{0} fusion(%copy-done.6), kind=kCustom, "
            "calls=%nameless\n"
            "  %ragged-dot-none.7")
        index = hlo_audit.op_records(text)
        path = ("smp/step/user", "smp/layer/full", "smp/moe/experts")
        for name in ("copy.5", "copy-done.6", "fusion.9"):
            assert index[name]["scope"] is None, name
            assert "scopes" not in index[name], name
            assert index[name]["near"] == path, name
        # the kernel still finds its rows through the copy
        assert index["ragged-dot-none.7"]["scopes"] == path
        assert "near" not in index["ragged-dot-none.7"]
        # free instructions and the marked are told nothing
        assert "near" not in index["weights"] and "near" not in index["sizes"]
        assert "near" not in index["rows.1"]
        joined = hlo_audit.seconds_by_scope(
            {"copy.5": 2.0, "fusion.9": 1.0, "weights": 0.5, "gone.1": 0.25,
             "ragged-dot-none.7": 4.0}, index)
        assert joined["unscoped"]["seconds"] == 3.75
        assert joined["unscoped"]["near"] == {path: 3.0, (): 0.75}
        assert joined["tree"] == {path: 4.0}

    def test_a_kernel_whose_op_name_is_a_path_is_the_programs_own(self):
        """A Pallas kernel is a ``tpu_custom_call`` too; its ``op_name``
        holds the program's path and scopes, and nothing is inherited."""
        text = _instr(
            "smp_flash_fwd.3",
            _J + "smp/step/user/smp/attn/full/smp/attn/core/smp_flash_fwd",
            'bf16[8]{0} custom-call(%q), '
            'custom_call_target="tpu_custom_call"')
        (rec,) = hlo_audit.op_records(text).values()
        assert "kernel" not in rec and "inherited" not in rec
        assert rec["scope"] == "smp/attn/core"


_FLASH = _J + ("while/body/smp/step/user/{}/smp/layer/full/smp/attn/full/"
                "smp/attn/core/{}")
_PALLAS = 'bf16[8]{0} custom-call(%q), custom_call_target="tpu_custom_call"'


def _flash_step(recomputed):
    """The flash kernels of a checkpointed layer as the TPU compiler
    leaves them: the forward under a plain ``jvp(`` path, the two
    backward kernels transposed and, where the layer's backward pass runs
    the forward again, a second forward kernel under
    ``rematted_computation``; a grouped product of the compiler's beside
    them."""
    text = _instr("smp_flash_fwd.3", _FLASH.format(
        "jvp(layer)", "smp_flash_fwd"), _PALLAS)
    if recomputed:
        text += _instr("smp_flash_fwd.4", _FLASH.format(
            "transpose(jvp(layer))/checkpoint/rematted_computation",
            "smp_flash_fwd"), _PALLAS)
    for name in ("smp_flash_bwd_dq", "smp_flash_bwd_dkv"):
        text += _instr(name + ".5", _FLASH.format(
            "transpose(jvp(layer))", name), _PALLAS)
    return text + _chunk_body("%rows.1, %weights", "smp/moe/experts")


class TestKernelCensus:
    """``smp_kernel_calls{kernel, phase}``: how often a Mosaic kernel stands
    in the compiled step, by phase, so the program says itself whether a
    checkpointed layer's backward pass runs the flash forward again."""

    @pytest.mark.parametrize("recomputed", [False, True])
    def test_a_kernel_is_counted_under_its_phase(self, recomputed):
        index = hlo_audit.op_records(_flash_step(recomputed))
        assert index["smp_flash_fwd.3"]["pallas"] == "smp_flash_fwd"
        assert "kernel" not in index["smp_flash_fwd.3"]
        census = hlo_audit.kernel_census(index)
        assert census["smp_flash_fwd"] == {
            "forward": 1, "recompute": int(recomputed), "backward": 0}
        for name in ("smp_flash_bwd_dq", "smp_flash_bwd_dkv"):
            assert census[name] == {
                "forward": 0, "recompute": 0, "backward": 1}
        # the compiler's own kernels are Mosaic kernels too
        assert sum(census["ragged_dot"].values()) == 1
        assert sum(census["ragged_dot_metadata"].values()) == 1
        assert set(census) == {
            "smp_flash_fwd", "smp_flash_bwd_dq", "smp_flash_bwd_dkv",
            "ragged_dot", "ragged_dot_metadata"}

    def test_a_kernel_with_no_name_of_the_librarys_is_unnamed(self):
        index = hlo_audit.op_records(_instr(
            "custom-call.9", _J + "jvp(layer)/pallas_call", _PALLAS))
        assert hlo_audit.kernel_census(index) == {
            "unnamed": {"forward": 1, "recompute": 0, "backward": 0}}

    @pytest.mark.parametrize("recomputed", [False, True])
    def test_the_gauge_reads_zero_where_nothing_is_recomputed(
            self, recomputed):
        telemetry.reset()
        audit = hlo_audit.ProgramAudit(
            "step", "k", {}, {"fraction": 0.0}, {}, [], 1.0, 2.0, "sha",
            {"pp": 1},
            op_index=hlo_audit.op_records(_flash_step(recomputed)))
        hlo_audit._publish(audit)
        series = telemetry.report()["metrics"]["smp_kernel_calls"]["series"]
        read = {(s["labels"]["kernel"], s["labels"]["phase"]): s["value"]
                for s in series if s["labels"]["step"] == "step"}
        assert read[("smp_flash_fwd", "forward")] == 1
        assert read[("smp_flash_fwd", "recompute")] == int(recomputed)
        assert read[("smp_flash_bwd_dkv", "backward")] == 1
        assert read[("smp_flash_bwd_dkv", "recompute")] == 0
        telemetry.reset()


class TestCacheKeyedOnNames:
    """A compile cache keyed without metadata hands a build the names of
    whichever build filled it (PR 24 met this on the chip); the step is
    compiled under a key that holds them."""

    @pytest.fixture
    def cache_dir(self, tmp_path):
        from jax.experimental.compilation_cache import (
            compilation_cache as cc,
        )

        names = ("jax_compilation_cache_dir",
                 "jax_persistent_cache_min_compile_time_secs",
                 "jax_persistent_cache_min_entry_size_bytes")
        before = {n: getattr(jax.config, n) for n in names}
        jax.config.update(names[0], str(tmp_path))
        jax.config.update(names[1], 0.0)
        jax.config.update(names[2], -1)
        cc.reset_cache()
        try:
            yield tmp_path
        finally:
            for n, v in before.items():
                jax.config.update(n, v)
            cc.reset_cache()

    @staticmethod
    def _lowered(scope):
        def full_impl(x):
            with jax.named_scope(scope):
                return jnp.tanh(x @ x).sum()

        return jax.jit(full_impl).lower(jnp.ones((64, 64)))

    def test_a_second_build_gets_its_own_names_and_a_rerun_still_hits(
            self, cache_dir):
        import importlib

        step_mod = importlib.import_module(
            "smdistributed_modelparallel_tpu.step")
        entries = lambda: len([  # noqa: E731
            f for f in os.listdir(cache_dir)
            if f.startswith("jit_full_impl") and f.endswith("-cache")])
        # The fault, with JAX's own key: the second build's program is the
        # first's but for a scope's name, and it is handed the first's.
        self._lowered("smp/head/loss").compile()
        stale = self._lowered("smp/head/logits").compile().as_text()
        assert "smp/head/loss" in stale and "smp/head/logits" not in stale
        assert entries() == 1
        # The step's compile: each build its own entry, and a second run
        # of one build (the same lines of the same files: the key holds
        # them too, so all three are lowered from one line) finds its own.
        texts = [step_mod._compile_keyed_on_names(
            self._lowered(scope)).as_text() for scope in (
                "smp/head/loss", "smp/head/logits", "smp/head/logits")]
        assert "smp/head/loss" in texts[0]
        for text in texts[1:]:
            assert "smp/head/logits" in text and "smp/head/loss" not in text
        assert entries() == 1 + 2
        assert jax.config.jax_compilation_cache_include_metadata_in_key \
            is False

    def test_without_the_op_index_the_key_is_jaxs_own(self, cache_dir,
                                                      monkeypatch):
        import importlib

        step_mod = importlib.import_module(
            "smdistributed_modelparallel_tpu.step")
        monkeypatch.setenv("SMP_HLO_AUDIT", "off")
        step_mod._compile_keyed_on_names(self._lowered("smp/head/loss"))
        other = step_mod._compile_keyed_on_names(
            self._lowered("smp/head/logits"))
        assert "smp/head/loss" in other.as_text()    # nobody reads them


class TestSecondsByScope:
    """C of PR 39: one join from ``{instruction: self seconds}`` to the
    tree, whose parts sum to the busy time."""

    _INDEX = {
        "fusion.1": {"phase": "forward", "scope": "smp/attn/core",
                     "scopes": ("smp/step/user", "smp/layer/block",
                                "smp/attn/full", "smp/attn/core")},
        "fusion.2": {"phase": "backward", "scope": "smp/mlp/dense",
                     "scopes": ("smp/step/user", "smp/layer/block",
                                "smp/mlp/dense")},
        "fusion.3": {"phase": "forward", "scope": "smp/step/user"},
        "fusion.4": {"phase": "backward", "scope": "smp/step/user",
                     "scopes": ("smp/pipeline/steady", "smp/pipeline/head",
                                "smp/step/user")},
        "fusion.5": {"phase": "backward", "scope": "smp/step/accumulate"},
        "copy.6": {"phase": "other", "scope": None},
        "ragged-dot-none.7": {"phase": "recompute", "kernel": "ragged_dot",
                              "inherited": True, "scope": "smp/moe/experts",
                              "scopes": ("smp/step/user", "smp/layer/full",
                                         "smp/moe/experts")},
        "ragged-dot-none.8": {"phase": "other", "kernel": "ragged_dot",
                              "scope": None},
        "all-reduce.9": {"phase": "backward", "scope": "smp/pipeline/steady",
                         "op": "all-reduce", "axis": "tp", "bytes": 64},
    }
    _SECONDS = {"fusion.1": 1.0, "fusion.2": 2.0, "fusion.3": 0.25,
                "fusion.4": 0.5, "fusion.5": 0.125, "copy.6": 0.0625,
                "ragged-dot-none.7": 4.0, "ragged-dot-none.8": 0.5,
                "all-reduce.9": 1.5, "copy.99": 0.03125}

    def _joined(self):
        return hlo_audit.seconds_by_scope(self._SECONDS, self._INDEX)

    def test_the_parts_sum_to_busy(self):
        rec = self._joined()
        assert rec["busy_s"] == sum(self._SECONDS.values())
        assert sum(rec["tree"].values()) + rec["unscoped"]["seconds"] \
            == rec["busy_s"]
        assert sum(rec["by_phase"].values()) == rec["busy_s"]

    def test_a_name_the_index_lacks_is_unscoped_and_other(self):
        rec = self._joined()
        assert rec["unscoped"]["seconds"] == 0.0625 + 0.5 + 0.03125
        assert rec["unscoped"]["top"] == [
            ["ragged-dot-none.8", 0.5, "other"],
            ["copy.6", 0.0625, "other"],
            ["copy.99", 0.03125, "other"]]
        assert rec["by_phase"]["other"] == 0.0625 + 0.5 + 0.03125

    def test_a_prefix_or_one_scope_sums_its_subtree(self):
        tree = self._joined()["tree"]
        assert tree[("smp/step/user", "smp/layer/block", "smp/attn/full",
                     "smp/attn/core")] == 1.0
        assert hlo_audit.seconds_under(tree, "smp/layer/block") == 3.0
        assert hlo_audit.seconds_under(tree, "smp/attn/") == 1.0
        assert hlo_audit.seconds_under(tree, "smp/moe/", "smp/mlp/") == 6.0
        assert hlo_audit.seconds_under(tree, "smp/step/user") == 7.75
        assert hlo_audit.seconds_under(tree, "smp/head/") == 0

    def test_user_only_is_the_innermost_scope_under_a_pipeline_too(self):
        rec = self._joined()
        assert rec["user_only_s"] == 0.25 + 0.5
        assert rec["tree"][("smp/step/user",)] == 0.25

    def test_kernels_axes_and_the_ten_largest(self):
        rec = self._joined()
        assert rec["kernels"] == {"ragged_dot": 4.5}
        assert rec["by_axis"] == {"tp": 1.5}
        many = {f"copy.{i}": float(i) for i in range(1, 15)}
        top = hlo_audit.seconds_by_scope(many, self._INDEX)["unscoped"]["top"]
        assert [row[0] for row in top] == [
            f"copy.{i}" for i in range(14, 4, -1)]

    def test_nothing_without_an_index(self, monkeypatch):
        monkeypatch.setattr(hlo_audit, "audits", {})
        assert hlo_audit.step_program() is None
        assert hlo_audit.seconds_by_scope(self._SECONDS) is None
        assert hlo_audit.seconds_by_scope(self._SECONDS, "step") is None
        assert hlo_audit.seconds_by_scope(self._SECONDS, {}) is None

    def test_the_program_defaults_to_the_step_audited_last(self, monkeypatch):
        audit = lambda name: hlo_audit.ProgramAudit(  # noqa: E731
            name, "k", {}, {"fraction": 0.0}, {}, [], 1.0, 2.0, "sha",
            {"pp": 1}, op_index=self._INDEX)
        monkeypatch.setattr(hlo_audit, "audits", {
            "step": audit("step"), "serve_decode": audit("serve_decode")})
        assert hlo_audit.step_program() == "step"
        assert hlo_audit.seconds_by_scope(self._SECONDS) == self._joined()


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
    @pytest.fixture(scope="class")
    def pp2(self, tmp_path_factory):
        """The class's one pp=2 compile: its audit and where it was
        persisted. The first test to ask finds the telemetry and the
        flight recorder as the compile left them."""
        dump = tmp_path_factory.mktemp("xray") / "xray.json"
        with pytest.MonkeyPatch.context() as mp:
            mp.setenv("SMP_HLO_AUDIT_PATH", str(dump))
            step_fn = _train_pp({
                "pipeline_parallel_degree": 2, "microbatches": 4,
                "ddp": True,
            })
        runner = list(step_fn._cache.values())[0]
        if runner.holder.get("compiled") is None:
            pytest.skip("AOT step executable unavailable on this backend")
        audit = runner.hlo_audit
        assert audit is not None, "post-compile audit did not run"
        return audit, dump

    def test_census_persistence_and_reports(self, tmp_path, pp2):
        """One pp=2 compile exercises the whole surface: stored audit,
        per-axis census, gauges, SMP_HLO_AUDIT_PATH persistence, the
        flight-recorder fingerprint, and both report CLIs."""
        audit, dump = pp2
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

    # What a reader of the audit may rely on without a schema check of
    # its own: a hash to compare, and a census it can sum.

    def test_the_fingerprint_hash_is_a_non_empty_string(self, pp2):
        audit, _ = pp2
        assert isinstance(audit.fingerprint_hash, str)
        assert audit.fingerprint_hash
        assert audit.as_dict()["fingerprint"] == audit.fingerprint_hash

    def test_every_census_entry_has_a_count_and_bytes(self, pp2):
        audit, _ = pp2
        assert audit.census
        for op, ent in audit.census.items():
            rows = [ent, *ent["axes"].values()]
            for row in rows:
                assert isinstance(row["count"], int) and row["count"] > 0, op
                assert isinstance(row["bytes"], int) and row["bytes"] >= 0, op
            assert sum(r["count"] for r in rows[1:]) == ent["count"], op

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
