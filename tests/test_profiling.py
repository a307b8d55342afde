"""Performance-observability tests (``utils/profiling.py``).

Covers the three tentpole pieces: named profiler regions (in-graph names
land in compiled-HLO op metadata; host regions land in the timeline),
on-demand capture (``SMP_PROFILE=steps=N:M`` brackets exactly that window
into a per-rank dir; SIGUSR2 arms a one-step capture), and roofline/MFU
attribution (toy values match hand-computed FLOPs/bytes; gauges publish;
the telemetry-report CLI renders them). The compile-cache hit-rate
assertion rides the end-to-end run: a deterministic CPU-safe gate, with
no wall time in it. Plus the trace_fuse per-phase skew satellite over
synthetic two-rank timelines.
"""

import importlib.util
import io
import json
import os
import signal
import sys
import time
import types

import pytest

import jax
import jax.numpy as jnp
import optax

import smdistributed_modelparallel_tpu as smp
from smdistributed_modelparallel_tpu.backend.state import state
from smdistributed_modelparallel_tpu.utils import profiling
from smdistributed_modelparallel_tpu.utils.telemetry import telemetry
from smdistributed_modelparallel_tpu.utils.timeline import Timeline

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SCRIPTS = os.path.join(_REPO, "scripts")


def _load_script(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(_SCRIPTS, name + ".py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _gauge(report, name, **labels):
    fam = report.get("metrics", {}).get(name)
    for s in (fam or {}).get("series", []):
        if all(s["labels"].get(k) == v for k, v in labels.items()):
            return s.get("value")
    return None


# ----------------------------------------------------------------------
# Named regions
# ----------------------------------------------------------------------


class TestRegions:
    def test_named_region_in_compiled_hlo_and_cost_join(self):
        """One compile covers both halves: the in-graph region name lands
        in the compiled HLO's op metadata, and roofline() joins that same
        executable's cost analysis with a wall time."""

        def f(x):
            with profiling.named_region("smp/test/matmul_region"):
                return x @ x

        compiled = jax.jit(f).lower(jnp.ones((32, 32))).compile()
        assert "matmul_region" in compiled.as_text()

        rep = profiling.roofline(
            "hlo_join", step_time_s=0.01, compiled=compiled,
            peak_flops=1e12, peak_bytes_per_s=1e9,
        )
        assert rep.flops is not None and rep.flops > 0
        assert rep.bytes_accessed is not None and rep.bytes_accessed > 0
        assert rep.mfu == pytest.approx(rep.flops / 0.01 / 1e12)

    def test_region_records_timeline_span(self, tmp_path):
        path = str(tmp_path / "tl.json")
        tl = Timeline(path=path)
        assert tl.enabled
        old = state.timeline
        state.timeline = tl
        try:
            with profiling.region("unit/phase"):
                time.sleep(0.002)
        finally:
            state.timeline = old
        tl.flush()
        with open(tl.path) as f:
            events = json.load(f)["traceEvents"]
        spans = [e for e in events
                 if e.get("name") == "smp_phase/unit/phase"
                 and e.get("ph") == "X"]
        assert spans and spans[0]["dur"] > 0

    def test_region_noop_without_timeline(self):
        old = state.timeline
        state.timeline = None
        try:
            with profiling.region("unit/nothing"):
                pass
        finally:
            state.timeline = old


class _Annotations:
    """Stands in for ``jax.profiler.TraceAnnotation``: keeps what each
    region would have written into the profiler's trace."""

    seen = []

    def __init__(self, name, **stats):
        type(self).seen.append((name, stats))

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def _phase_series():
    series = telemetry.report()["metrics"].get(
        "smp_host_phase_seconds", {}).get("series", [])
    return {s["labels"]["phase"]: s for s in series}


class TestRegionNesting:
    def test_a_region_knows_its_parent_and_its_step(self, monkeypatch):
        monkeypatch.setattr(jax.profiler, "TraceAnnotation", _Annotations)
        _Annotations.seen = []
        with profiling.region("unit/outer", step=7) as outer:
            with profiling.region("unit/inner") as inner:
                with profiling.region("unit/leaf", step=9) as leaf:
                    pass
            with profiling.region("unit/second") as second:
                pass
        with profiling.region("unit/alone") as alone:
            pass
        assert (outer.parent, outer.step) == (None, 7)
        assert (inner.parent, inner.step) == ("smp_phase/unit/outer", 7)
        assert (leaf.parent, leaf.step) == ("smp_phase/unit/inner", 9)
        assert (second.parent, second.step) == ("smp_phase/unit/outer", 7)
        assert (alone.parent, alone.step) == (None, None)
        assert _Annotations.seen == [
            ("smp_phase/unit/outer", {"step": 7}),
            ("smp_phase/unit/inner",
             {"step": 7, "parent": "smp_phase/unit/outer"}),
            ("smp_phase/unit/leaf",
             {"step": 9, "parent": "smp_phase/unit/inner"}),
            ("smp_phase/unit/second",
             {"step": 7, "parent": "smp_phase/unit/outer"}),
            ("smp_phase/unit/alone", {}),
        ]

    def test_an_exception_leaves_no_region_open(self):
        with pytest.raises(ValueError):
            with profiling.region("unit/raises", step=1):
                with profiling.region("unit/raises_inner"):
                    raise ValueError("boom")
        with profiling.region("unit/after") as after:
            pass
        assert (after.parent, after.step) == (None, None)

    def test_every_region_observes_the_one_histogram_family(self):
        from smdistributed_modelparallel_tpu.utils.telemetry import (
            HOST_PHASE_BUCKETS,
        )

        before = _phase_series().get("unit/timed", {"count": 0, "sum": 0.0})
        for _ in range(3):
            with profiling.region("unit/timed"):
                time.sleep(0.002)
        series = _phase_series()["unit/timed"]
        assert series["count"] - before["count"] == 3
        assert series["sum"] - before["sum"] >= 0.006
        assert tuple(series["buckets"]) == HOST_PHASE_BUCKETS
        assert HOST_PHASE_BUCKETS[0] == 5e-6      # phases take microseconds

    def test_timeline_event_carries_step_and_parent(self, tmp_path):
        tl = Timeline(path=str(tmp_path / "tl.json"))
        tl._native = None          # the Python recorder keeps event args
        old = state.timeline
        state.timeline = tl
        try:
            tl.start_step(4)
            with profiling.region("unit/outer", step=4):
                with profiling.region("unit/inner"):
                    pass
        finally:
            state.timeline = old
        events = {e["name"]: e for e in tl._events if e.get("ph") == "X"}
        assert events["smp_phase/unit/inner"]["args"] == {
            "step": 4, "parent": "smp_phase/unit/outer"}
        assert events["smp_phase/unit/outer"]["args"] == {"step": 4}


# ----------------------------------------------------------------------
# On-demand capture
# ----------------------------------------------------------------------


class TestCapture:
    def test_parse_spec(self):
        assert profiling._parse_profile_spec("steps=1:2") == (1, 2)
        assert profiling._parse_profile_spec("steps=3") == (3, 3)
        assert profiling._parse_profile_spec("4:7") == (4, 7)
        for bad in ("steps=2:1", "steps=-1", "steps=a:b", "", "1:2:3"):
            with pytest.raises(ValueError):
                profiling._parse_profile_spec(bad)

    def test_sigusr2_arms_one_step_window(self, monkeypatch, tmp_path):
        calls = []
        monkeypatch.setattr(
            jax.profiler, "start_trace", lambda d: calls.append(("start", d))
        )
        monkeypatch.setattr(
            jax.profiler, "stop_trace", lambda: calls.append(("stop",))
        )
        monkeypatch.setenv(profiling.PROFILE_PATH_ENV, str(tmp_path))
        monkeypatch.delenv(profiling.PROFILE_ENV, raising=False)
        cap = profiling.ProfileCapture()
        prev = signal.getsignal(signal.SIGUSR2)
        try:
            cap.install_signal()
            os.kill(os.getpid(), signal.SIGUSR2)
            deadline = time.time() + 5
            while not cap._sig_request and time.time() < deadline:
                time.sleep(0.005)
            assert cap._sig_request, "signal handler never ran"
            cap.on_step_begin(7)
            assert cap.active
            cap.on_step_end(7)
            assert not cap.active
        finally:
            signal.signal(signal.SIGUSR2, prev)
        assert [c[0] for c in calls] == ["start", "stop"]
        assert calls[0][1].endswith("rank0")
        assert cap.last_window == (7, 7)

    def test_sigusr2_does_not_cancel_armed_window(self, monkeypatch):
        monkeypatch.setenv(profiling.PROFILE_ENV, "steps=100:102")
        cap = profiling.ProfileCapture()
        cap._sig_request = True      # as if SIGUSR2 arrived before step 5
        cap.on_step_begin(5)
        assert not cap.active
        assert cap.window == (100, 102)   # the configured window survives

    def test_stop_if_active_records_last_seen_step(self, monkeypatch):
        monkeypatch.setattr(jax.profiler, "start_trace", lambda d: None)
        monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
        monkeypatch.setenv(profiling.PROFILE_ENV, "steps=1:5")
        cap = profiling.ProfileCapture()
        cap.on_step_begin(1)
        cap.on_step_end(1)
        cap.on_step_begin(2)
        cap.on_step_end(2)
        assert cap.active                 # window runs through step 5
        cap.stop_if_active()              # run died after step 2
        assert cap.last_window == (1, 2)

    def test_disarmed_hooks_are_noops(self, monkeypatch):
        monkeypatch.delenv(profiling.PROFILE_ENV, raising=False)
        cap = profiling.ProfileCapture()
        cap.on_step_begin(0)
        cap.on_step_end(0)
        assert not cap.active and cap.last_window is None


# ----------------------------------------------------------------------
# The scope vocabulary, whole (PR 39)
# ----------------------------------------------------------------------

_PACKAGE = os.path.join(_REPO, "smdistributed_modelparallel_tpu")
_TINY = dict(
    num_layers=2, hidden_size=32, num_attention_heads=2,
    attention_head_size=16, intermediate_size=64, vocab_size=64,
    num_positions=16, attention_dropout_prob=0.0, hidden_dropout_prob=0.0,
    embedding_dropout_prob=0.0, causal_mask_size=16, pre_layernorm=True,
    post_layernorm=False, final_layernorm=True)


def _scope_strings():
    """Every quoted ``smp/<subsystem>/<name>`` in the package's source
    (an f-string's field reads ``<kind>``), but the vocabulary's own."""
    import re

    found = {}
    for folder, _, files in os.walk(_PACKAGE):
        for name in files:
            if not name.endswith(".py"):
                continue
            path = os.path.join(folder, name)
            with open(path, encoding="utf-8") as f:
                text = f.read()
            if path.endswith(os.path.join("utils", "profiling.py")):
                text = text.replace(
                    text[text.index("SCOPES = {"):text.index("def _timeline")],
                    "")
            for quoted in re.findall(
                    r"""["'](smp/[\w\-]+/[\w\-{}.' ]+?)["']""", text):
                scope = re.sub(r"\{[^}]*\}", "<kind>", quoted)
                found.setdefault(scope, os.path.relpath(path, _REPO))
    return found


def _step_scopes(cfg, module, loss_mode=False, seq=8):
    """Every scope in the op index of one compiled tiny step."""
    from smdistributed_modelparallel_tpu.utils import hlo_audit

    smp.reset()
    smp.init(cfg)
    model = smp.DistributedModel(module)
    opt = smp.DistributedOptimizer(optax.sgd(0.1), model)

    @smp.step
    def step_fn(model, ids):
        if getattr(module, "loop_steps", 1) > 1:
            loss = smp.nn.exit_gated_loss(*model(ids, targets=ids), 0.05)[0]
        elif loss_mode:
            loss = jnp.mean(model(ids, targets=ids))
        else:
            logits = model(ids).astype(jnp.float32)
            lse = jax.scipy.special.logsumexp(logits, axis=-1)
            picked = jnp.take_along_axis(
                logits, ids[..., :logits.shape[1], None], axis=-1)[..., 0]
            loss = jnp.mean(lse - picked)
        model.backward(loss)
        return loss

    step_fn(model, jax.random.randint(jax.random.key(0), (4, seq), 0, 64))
    opt.step()
    index = hlo_audit.op_index(hlo_audit.step_program())
    smp.reset()
    return {scope for rec in index.values()
            for scope in rec.get("scopes") or (rec["scope"],) if scope}


@pytest.fixture(scope="module")
def compiled_scopes():
    """The scopes of a handful of compiled tiny steps: both stacks at
    pp = 1 (a patterned one, one of latent-attention layers on two
    residual streams, and one run twice over its own output with branch
    norms and an exit gate), the CPU mesh's pp = 2 under each executor."""
    from smdistributed_modelparallel_tpu.models.transformer_lm import (
        TransformerLM,
    )
    from smdistributed_modelparallel_tpu.nn.transformer import (
        DistributedTransformerLMHead,
    )

    expert = dict(num_experts=4, moe_top_k=2, moe_dropless=True,
                  intermediate_size=16, use_mlp_bias=False)
    pp2 = {"pipeline_parallel_degree": 2, "ddp": True, "microbatches": 4}
    tp_stack = lambda **kw: DistributedTransformerLMHead(  # noqa: E731
        **dict(_TINY, **kw))
    seen = {
        "zoo": _step_scopes(
            {"microbatches": 2, "bf16": True},
            TransformerLM(vocab_size=64, max_len=16, d_model=32, n_layers=2,
                          n_heads=2, window=4), loss_mode=True),
        "tp_stack": _step_scopes(
            {"microbatches": 2, "bf16": True}, tp_stack(), loss_mode=True),
        "patterned": _step_scopes(
            {"microbatches": 2, "bf16": True}, tp_stack(
                num_layers=4, tie_input_output_embedding=False,
                head_positions=0.5, layernorm_type="rms",
                layer_pattern=("lead", "routed", "noisy", "mixed"),
                layer_kinds={
                    "lead": {},
                    "mixed": dict(conv_mixer=3),
                    "routed": dict(expert, window_size=4, qk_norm=True,
                                   num_key_value_heads=1,
                                   moe_shared_intermediate_size=16),
                    "noisy": dict(expert, block_diffusion=2)})),
        "streams": _step_scopes(
            {"microbatches": 2, "bf16": True}, tp_stack(
                layernorm_type="rms", tie_input_output_embedding=False,
                hyper_connection={"streams": 2},
                layer_pattern=("latent", "latent"),
                layer_kinds={"latent": dict(
                    rotary_emb_base=10000.0, latent_attention=dict(
                        q_lora_rank=8, kv_lora_rank=8, qk_nope_head_dim=8,
                        qk_rope_head_dim=4, v_head_dim=8,
                        softmax_scale=0.3))})),
        "looped": _step_scopes(
            {"microbatches": 2, "bf16": True}, tp_stack(
                layernorm_type="rms", tie_input_output_embedding=False,
                branch_layernorm=True, loop_steps=2,
                activation_checkpointing=True), loss_mode=True),
        "1f1b": _step_scopes(pp2, tp_stack()),
        "virtual": _step_scopes(
            dict(pp2, virtual_pipeline_degree=2), tp_stack(num_layers=4)),
        "zero_bubble": _step_scopes(
            dict(pp2, pipeline="zero_bubble"), tp_stack()),
        "simple": _step_scopes(dict(pp2, pipeline="simple"), tp_stack()),
    }
    return seen


class TestScopeVocabulary:
    def test_every_scope_string_in_the_package_is_listed(self):
        found = _scope_strings()
        assert len(found) >= 30
        missing = {s: where for s, where in found.items()
                   if s not in profiling.SCOPES}
        assert not missing, f"scopes not in profiling.SCOPES: {missing}"

    def test_every_listed_scope_is_written_somewhere(self):
        found = _scope_strings()
        assert sorted(s for s in profiling.SCOPES if s not in found) == []
        for scope, round_ in profiling.SCOPES.items():
            assert scope.startswith("smp/") and scope.count("/") == 2
            assert round_ and "\n" not in round_

    @pytest.mark.parametrize("scope", sorted(profiling.SCOPES))
    def test_a_listed_scope_appears_in_a_compiled_tiny_step(
            self, compiled_scopes, scope):
        """Of the stack that should write it: the kind of a patterned
        stack stands for ``<kind>``."""
        want = "smp/layer/routed" if scope == "smp/layer/<kind>" else scope
        where = {name for name, scopes in compiled_scopes.items()
                 if want in scopes}
        if scope == "smp/pipeline/cooldown_weight":
            # The zero-bubble executor names a weight-only last segment so;
            # no schedule its builder makes today (2 or 4 stages, 2 to 8
            # microbatches, any window, 1 or 2 chunks) ends in one.
            assert not where
            return
        assert where, f"{scope} is in no compiled step's op index"
        if scope.startswith("smp/pipeline/"):
            assert where <= {"1f1b", "virtual", "zero_bubble", "simple"}
        if scope == "smp/model/stack":      # the executors run the layers
            assert where == {"zoo", "tp_stack", "patterned", "streams",
                             "looped"}
        if scope in ("smp/model/loop", "smp/head/exit_gate",
                     "smp/layer/branch_norm"):
            assert where == {"looped"}      # and no other stack's step
        elif scope.startswith(("smp/attn/q", "smp/attn/core", "smp/attn/out",
                               "smp/head/", "smp/model/", "smp/mlp/")):
            # both stacks write the parts of a layer and the head
            assert {"zoo", "tp_stack"} <= where or scope == "smp/attn/qk_norm"

    def test_both_stacks_write_the_same_tree_at_pp_1(self, compiled_scopes):
        common = {"smp/step/user", "smp/step/cast_params",
                  "smp/step/accumulate", "smp/optimizer/update",
                  "smp/model/embed", "smp/model/stack", "smp/head/norm",
                  "smp/head/logits",
                  "smp/head/loss", "smp/layer/block", "smp/attn/qkv",
                  "smp/attn/core", "smp/attn/out", "smp/mlp/dense"}
        assert compiled_scopes["zoo"] == common | {"smp/attn/window"}
        assert compiled_scopes["tp_stack"] == common | {"smp/attn/full"}
        # a kind keeps its name, and the user's own loss is no head's
        assert "smp/layer/block" not in compiled_scopes["patterned"]
        assert "smp/head/loss" not in compiled_scopes["patterned"]


# ----------------------------------------------------------------------
# A capture's own report (PR 39)
# ----------------------------------------------------------------------


class TestScopeReport:
    _TRACE = os.path.join(_REPO, "benchmark", "testdata",
                          "train-1chip.scopes.trimmed.xplane.pb")

    def test_the_reduction_agrees_with_the_benchmarks_on_a_chip_trace(self):
        """Two reductions written apart, one recorded TPU trace: device
        0's self seconds by instruction, to the nanosecond."""
        sys.path.insert(0, _REPO)
        try:
            from benchmark import trace_reduce
        finally:
            sys.path.remove(_REPO)
        plane, seconds = profiling.device_op_seconds(self._TRACE)
        theirs = trace_reduce.reduce(self._TRACE)
        assert plane == theirs["devices"][0] == "/device:TPU:0"
        assert set(seconds) == set(theirs["op_self_s"]) and len(seconds) > 100
        for name, s in seconds.items():
            assert abs(s - theirs["op_self_s"][name]) < 1e-12, name
        assert abs(sum(seconds.values())
                   - theirs["busy_s_by_device"][0]) < 1e-9

    def test_self_seconds_leave_a_while_what_its_body_does_not_take(self):
        events = [("while.1", 0, 100), ("fusion.2", 10, 30),
                  ("fusion.2", 50, 30), ("copy.3", 55, 5),
                  ("fusion.4", 120, 10)]
        assert profiling._self_seconds(events) == {
            "while.1": 40e-9, "fusion.2": 55e-9, "copy.3": 5e-9,
            "fusion.4": 10e-9}

    def test_report_of_a_recorded_trace_sums_to_busy(self):
        with open(os.path.join(os.path.dirname(self._TRACE),
                               "train-1chip.op_index.json")) as f:
            index = json.load(f)["op_index"]
        report = profiling.scope_report(self._TRACE, program=index)
        assert report["device"] == "/device:TPU:0"
        parts = sum(row["seconds"] for row in report["tree"]) \
            + report["unscoped"]["seconds"]
        assert abs(parts - report["busy_s"]) < 1e-9 and report["busy_s"] > 0
        assert report["tree"][0]["seconds"] >= report["tree"][-1]["seconds"]
        table = profiling.scope_table(report)
        assert "(no scope)" in table and "smp/optimizer/update" in table
        json.dumps(report)                       # plain data

    def test_nothing_to_report_without_a_trace_or_an_index(
            self, tmp_path, monkeypatch):
        from smdistributed_modelparallel_tpu.utils import hlo_audit

        assert profiling.newest_xplane(str(tmp_path)) is None
        assert profiling.write_scope_report(str(tmp_path)) is None
        monkeypatch.setattr(hlo_audit, "audits", {})
        assert profiling.scope_report(self._TRACE) is None

    def test_a_capture_on_the_cpu_writes_its_report(self, tmp_path,
                                                    monkeypatch):
        """``SMP_PROFILE=steps=1:2`` over a tiny step: the report lands
        beside the ``.xplane.pb``, its parts sum to busy, the tree holds
        the step's scopes, and the reduction is charged to the capture."""
        from smdistributed_modelparallel_tpu.models.transformer_lm import (
            TransformerLM,
        )

        monkeypatch.setenv(profiling.PROFILE_ENV, "steps=1:2")
        monkeypatch.setenv(profiling.PROFILE_PATH_ENV, str(tmp_path))
        profiling.capture.reset()
        smp.reset()
        smp.init({"microbatches": 2})
        model = smp.DistributedModel(TransformerLM(
            vocab_size=32, max_len=12, d_model=16, n_layers=2, n_heads=2))
        opt = smp.DistributedOptimizer(optax.sgd(0.1), model)

        @smp.step
        def train(model, ids):
            loss = jnp.mean(model(ids, targets=ids))
            model.backward(loss)
            return loss

        ids = jax.random.randint(jax.random.key(0), (4, 12), 0, 32)
        for _ in range(4):
            train(model, ids)
            opt.step()
        assert profiling.capture.last_window == (1, 2)
        path = profiling.capture.last_report
        assert path and os.path.basename(path) == profiling.REPORT_NAME
        assert path.startswith(os.path.join(str(tmp_path), "rank0"))
        assert [f for f in os.listdir(os.path.dirname(path))
                if f.endswith(".xplane.pb")]
        with open(path) as f:
            report = json.load(f)
        assert report["program"] == "step" and report["window"] == [1, 2]
        assert report["busy_s"] > 0
        parts = sum(row["seconds"] for row in report["tree"]) \
            + report["unscoped"]["seconds"]
        assert abs(parts - report["busy_s"]) < 1e-9
        paths = {tuple(row["path"]) for row in report["tree"]}
        assert any(p[0] == "smp/step/user" and p[-1] == "smp/attn/core"
                   for p in paths)
        assert ("smp/optimizer/update",) in paths
        assert abs(sum(report["by_phase"].values())
                   - report["busy_s"]) < 1e-9
        overhead = _gauge(telemetry.report(),
                          "smp_profile_overhead_seconds_total")
        assert overhead >= report["reduce_seconds"] > 0
        profiling.capture.reset()


# ----------------------------------------------------------------------
# Roofline / MFU attribution
# ----------------------------------------------------------------------


class TestRoofline:
    def test_toy_values_match_hand_computed(self):
        rep = profiling.roofline(
            "toy", step_time_s=0.5, flops=1e12, bytes_accessed=1e10,
            bubble_fraction=0.2, peak_flops=4e12, peak_bytes_per_s=1e11,
        )
        assert rep.mfu == pytest.approx(0.5)          # 1e12 / 0.5 / 4e12
        assert rep.achieved_flops_per_s == pytest.approx(2e12)
        assert rep.achieved_bytes_per_s == pytest.approx(2e10)
        assert rep.arithmetic_intensity == pytest.approx(100.0)
        assert rep.ridge_intensity == pytest.approx(40.0)
        assert rep.bound == "compute"                 # 100 >= 40
        assert rep.compute_s == pytest.approx(0.25)   # 1e12 / 4e12
        assert rep.memory_s == pytest.approx(0.1)     # 1e10 / 1e11
        assert rep.bubble_s == pytest.approx(0.1)     # 0.2 * 0.5
        assert rep.comm_s == pytest.approx(0.15)      # 0.5 - 0.25 - 0.1
        # Published gauges match the report.
        report = telemetry.report()
        assert _gauge(report, "smp_mfu", step="toy") == pytest.approx(0.5)
        assert _gauge(
            report, "smp_roofline_comm_seconds", step="toy"
        ) == pytest.approx(0.15)
        assert _gauge(
            report, "smp_roofline_compute_bound", step="toy"
        ) == 1.0

    def test_memory_bound_classification(self):
        rep = profiling.roofline(
            "toy_mem", step_time_s=0.1, flops=1e9, bytes_accessed=1e9,
            bubble_fraction=0.0, peak_flops=1e12, peak_bytes_per_s=1e10,
        )
        assert rep.arithmetic_intensity == pytest.approx(1.0)
        assert rep.ridge_intensity == pytest.approx(100.0)
        assert rep.bound == "memory"

    def test_device_peak_env_overrides(self, monkeypatch):
        monkeypatch.setenv(profiling.PEAK_TFLOPS_ENV, "2")
        monkeypatch.setenv(profiling.PEAK_GBPS_ENV, "4")
        flops, bps = profiling.device_peaks()
        assert flops == pytest.approx(2e12)
        assert bps == pytest.approx(4e9)

    def test_unknown_backend_yields_no_mfu(self, monkeypatch):
        monkeypatch.delenv(profiling.PEAK_TFLOPS_ENV, raising=False)
        monkeypatch.delenv(profiling.PEAK_GBPS_ENV, raising=False)
        # CPU device kind is not in the spec table: MFU must be absent,
        # never fabricated.
        rep = profiling.roofline(
            "toy_cpu", step_time_s=0.1, flops=1e9, bytes_accessed=1e9,
            bubble_fraction=0.0, publish=False,
        )
        assert rep.mfu is None
        assert rep.achieved_flops_per_s == pytest.approx(1e10)

    @pytest.mark.parametrize("index, key", [
        (0, "bf16_flops_per_s"), (1, "hbm_bytes_per_s"),
    ])
    def test_v5e_peaks_are_the_benchmarks(self, monkeypatch, index, key):
        """The package's spec table and ``benchmark/peaks.py`` both carry
        the v5e's peaks; until one owns the number they must agree."""
        monkeypatch.delenv(profiling.PEAK_TFLOPS_ENV, raising=False)
        monkeypatch.delenv(profiling.PEAK_GBPS_ENV, raising=False)
        monkeypatch.syspath_prepend(_REPO)
        from benchmark.peaks import PEAKS

        kind = "TPU v5 lite"
        device = types.SimpleNamespace(device_kind=kind)
        assert profiling.device_peaks(device)[index] == PEAKS[kind][key]


# ----------------------------------------------------------------------
# End-to-end: capture window + smp_mfu + compile-cache gate (CPU smoke)
# ----------------------------------------------------------------------


class TestEndToEnd:
    def test_capture_window_mfu_and_cache_hit_rate(self, tmp_path,
                                                   monkeypatch):
        prof_dir = tmp_path / "prof"
        monkeypatch.setenv(profiling.PROFILE_ENV, "steps=1:2")
        monkeypatch.setenv(profiling.PROFILE_PATH_ENV, str(prof_dir))
        # The CPU mesh has no spec-table peaks; the override is what makes
        # smp_mfu appear on the smoke run (acceptance criterion).
        monkeypatch.setenv(profiling.PEAK_TFLOPS_ENV, "0.001")
        monkeypatch.setenv(profiling.PEAK_GBPS_ENV, "1.0")
        profiling.capture.reset()

        smp.init({"microbatches": 2})
        import flax.linen as nn

        class Net(nn.Module):
            @nn.compact
            def __call__(self, x):
                return nn.Dense(8)(x)

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
        for _ in range(4):
            train(model, x, y)
            opt.step()
        # The step engine publishes no roofline of its own (it would have
        # to block a step to time it): whoever measured a step time joins
        # it with the compiled step's cost.
        profiling.roofline(
            "step", step_time_s=0.02,
            compiled=train._last_runner.holder["compiled"],
        )

        # Capture bracketed exactly steps 1..2, into the per-rank dir.
        assert profiling.capture.last_window == (1, 2)
        rank_dir = os.path.join(str(prof_dir), "rank0")
        assert os.path.isdir(rank_dir)
        trace_files = [
            os.path.join(r, f)
            for r, _, fs in os.walk(rank_dir) for f in fs
        ]
        assert trace_files, "capture produced no trace files"
        assert sum(os.path.getsize(f) for f in trace_files) > 0

        report = telemetry.report()
        assert _gauge(report, "smp_profile_active") == 0.0
        assert _gauge(report, "smp_profile_last_first_step") == 1.0
        assert _gauge(report, "smp_profile_last_last_step") == 2.0
        assert _gauge(report, "smp_profile_captures_total") == 1.0

        # smp_mfu + roofline decomposition, self-consistent with the
        # published FLOPs / step time / peak (hand-computable chain).
        mfu = _gauge(report, "smp_mfu", step="step")
        flops = _gauge(report, "smp_roofline_flops", step="step")
        step_s = _gauge(report, "smp_roofline_step_seconds", step="step")
        peak = _gauge(report, "smp_roofline_peak_flops_per_s", step="step")
        comp = _gauge(report, "smp_roofline_compute_seconds", step="step")
        comm = _gauge(report, "smp_roofline_comm_seconds", step="step")
        bub = _gauge(report, "smp_roofline_bubble_seconds", step="step")
        assert mfu is not None and mfu > 0
        assert peak == pytest.approx(1e9)             # 0.001 TFLOP/s
        assert mfu == pytest.approx(flops / step_s / peak, rel=1e-6)
        assert comp == pytest.approx(flops / peak, rel=1e-6)
        assert bub == pytest.approx(0.0)              # no pipeline
        assert comp + comm + bub == pytest.approx(step_s, rel=1e-6)

        # Regression-gate half: CPU-smoke compile-cache hit rate (no wall
        # time — 4 identical steps must be 1 miss + 3 hits).
        assert _gauge(
            report, "smp_step_compile_cache_total", event="miss"
        ) == 1.0
        assert _gauge(
            report, "smp_step_compile_cache_total", event="hit"
        ) == 3.0

        # The report CLI renders the Performance section from this dump.
        tr = _load_script("telemetry_report")
        buf = io.StringIO()
        tr.render(report, out=buf)
        text = buf.getvalue()
        assert "-- performance --" in text
        assert "MFU" in text and "decomposition:" in text


# ----------------------------------------------------------------------
# trace_fuse: per-phase skew from smp_phase/* region spans
# ----------------------------------------------------------------------


class TestTraceFusePhases:
    def _timeline_payload(self, rank, wall0_us, dispatch_ms):
        return {"traceEvents": [
            {"name": f"smp_clock_anchor/{wall0_us}/{rank}", "ph": "i",
             "ts": 0.0, "pid": 0, "tid": "sync", "s": "g"},
            {"name": "step_0_begin", "ph": "i", "ts": 100.0, "pid": 0,
             "tid": "pipeline", "s": "g"},
            {"name": "smp_phase/step/dispatch", "ph": "X", "ts": 120.0,
             "dur": dispatch_ms * 1e3, "pid": 0, "tid": "phase",
             "args": {"step": 0}},
            {"name": "step_0_end", "ph": "i",
             "ts": 150.0 + dispatch_ms * 1e3, "pid": 0, "tid": "pipeline",
             "s": "g"},
        ]}

    def test_per_phase_skew_report(self, tmp_path):
        tf = _load_script("trace_fuse")
        wall = 1_700_000_000_000_000
        for rank, ms in ((0, 10.0), (1, 25.0)):
            with open(tmp_path / f"tl.json.rank{rank}", "w") as f:
                json.dump(self._timeline_payload(rank, wall, ms), f)
        streams = tf.collect_inputs([str(tmp_path)])
        assert len(streams) == 2
        clock = tf.align(streams)
        buf = io.StringIO()
        tf.render_report(streams, clock, out=buf)
        text = buf.getvalue()
        assert "per-phase skew" in text
        assert "step/dispatch" in text
        assert "<- slowest" in text
        # Rank 1's 25 ms dispatch must be attributed as the slow one.
        phases = tf.phase_table(streams)
        durs = phases[(0, "step/dispatch")]
        assert durs[1] > durs[0]
        assert max(durs, key=durs.get) == 1
