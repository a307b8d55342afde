"""The readers that join the traced window's device ops with the program's
op index (``benchmark/metrics/_scopes.py``): each on a synthetic reduced
trace and index, and the join on a recorded pair (a trimmed trace of one
``gpt2-xl.train-1chip`` step on a v5e chip and the op index of the same
compile)."""

import json
import os
import types

import pytest

import benchtiny
from benchmark import loader
from benchmark import trace_reduce as tr

NEW = {
    "step.forward_time_share": "model step",
    "step.backward_time_share": "model step",
    "step.recompute_time_share": "model step",
    "optimizer.time_share": "optimizer",
    "step.unattributed_time_share": "model step",
    "collectives.time_share.tp": "pipeline executors and TP layers",
    "collectives.time_share.pp": "pipeline executors and TP layers",
    "pipeline.bubble_share": "pipeline executors and TP layers",
    "step.host_outside_dispatch_ms": "step engine",
}
FOUR_CHIP_ONLY = ("collectives.time_share.tp", "collectives.time_share.pp",
                  "pipeline.bubble_share")
PHASE_METRICS = {
    "step.forward_time_share": "forward",
    "step.backward_time_share": "backward",
    "step.recompute_time_share": "recompute",
    "optimizer.time_share": "optimizer",
    "step.unattributed_time_share": "other",
}
ONE, FOUR = "gpt2-xl.train-1chip", "pythia-1.4b.train-pp2tp2"

# Device 0's self seconds by op, and the op index of "the same compile".
OPS = {
    "fusion.1": 0.30, "fusion.2": 0.20, "fusion.3.remat": 0.10,
    "fusion.4": 0.05, "copy.9": 0.03, "not_in_index.1": 0.02,
    "all-reduce.93": 0.12, "all-reduce-start.1": 0.01,
    "all-reduce-done.1": 0.05, "all-gather.7": 0.08,
    "collective-permute-done.2": 0.03, "all-to-all.5": 0.01,
}
INDEX = {
    "fusion.1": {"phase": "backward", "scope": None},
    "fusion.2": {"phase": "forward", "scope": None},
    "fusion.3.remat": {"phase": "recompute", "scope": None},
    "fusion.4": {"phase": "optimizer", "scope": "smp/optimizer/update"},
    "copy.9": {"phase": "other", "scope": None},
    "all-reduce.93": {"phase": "forward", "scope": "smp/pipeline/tick_fwd",
                      "op": "all-reduce", "axis": "tp", "bytes": 1 << 24},
    "all-reduce-start.1": {"phase": "backward", "scope": None,
                           "op": "all-reduce", "axis": "tp", "bytes": 64},
    "all-reduce-done.1": {"phase": "backward", "scope": None,
                          "op": "all-reduce", "axis": "tp", "bytes": 0,
                          "done": True},
    "all-gather.7": {"phase": "backward", "scope": "smp/pipeline/tick_bwd",
                     "op": "all-gather", "axis": "pp", "bytes": 1 << 20},
    "collective-permute-done.2": {
        "phase": "other", "scope": "smp/pipeline/steady",
        "op": "collective-permute", "axis": "pp", "bytes": 0, "done": True},
    "all-to-all.5": {"phase": "other", "scope": None, "op": "all-to-all",
                     "axis": "world", "bytes": 8},
}


def reader(name):
    return loader.Manifest().cell(FOUR).metric_reader(name)


def context(chips=4, ops=OPS):
    reduced = tr.Reduced(op_self_s=dict(ops), op_text={}, window_s=1.25,
                         busy_s_by_device=[sum(ops.values())] * chips)
    run = types.SimpleNamespace(devices=list(range(chips)))
    return {"trace": reduced, "run": run}


@pytest.fixture
def audited(monkeypatch):
    """The program holds one audited ``step*`` program with ``INDEX``."""
    from smdistributed_modelparallel_tpu.utils import hlo_audit

    monkeypatch.setattr(hlo_audit, "audits", {
        "step_pipeline_1f1b": types.SimpleNamespace(op_index=INDEX),
        "serve_decode": types.SimpleNamespace(op_index={"x": {}}),
    })
    return hlo_audit


@pytest.mark.parametrize("metric,phase", sorted(PHASE_METRICS.items()))
def test_phase_share_is_self_time_of_the_phase_over_busy(audited, metric,
                                                         phase):
    busy = sum(OPS.values())
    mine = sum(s for name, s in OPS.items()
               if INDEX.get(name, {"phase": "other"})["phase"] == phase)
    assert reader(metric)(context()) == pytest.approx(100 * mine / busy)
    assert reader(metric)(context(chips=1)) == pytest.approx(
        100 * mine / busy)


def test_the_five_phase_shares_sum_to_100(audited):
    shares = {m: reader(m)(context()) for m in PHASE_METRICS}
    assert sum(shares.values()) == pytest.approx(100.0, abs=1e-9)
    # A name the index does not hold is unattributed, with phase "other".
    assert shares["step.unattributed_time_share"] == pytest.approx(
        100 * (0.03 + 0.02 + 0.03 + 0.01) / sum(OPS.values()))


def test_axis_shares_add_up_to_the_collective_share(audited):
    ctx = context()
    scopes = loader.load_sibling(
        os.path.join(benchtiny.ROOT, "benchmark", "metrics", "x.py"),
        "_scopes")
    by_axis = scopes.collective_seconds_by_axis(ctx)
    assert by_axis == pytest.approx(
        {"tp": 0.18, "pp": 0.11, "world": 0.01})
    assert sum(by_axis.values()) == pytest.approx(ctx["trace"].collective_s())
    tp = reader("collectives.time_share.tp")(ctx)
    pp = reader("collectives.time_share.pp")(ctx)
    rest = 100 * by_axis["world"] / 1.25
    assert tp == pytest.approx(100 * 0.18 / 1.25)
    assert pp == pytest.approx(100 * 0.11 / 1.25)
    assert tp + pp + rest == pytest.approx(
        reader("collectives.time_share")(ctx))


def test_a_collective_the_index_lacks_is_labelled_unindexed(audited):
    ctx = context(ops=dict(OPS, **{"all-gather.99": 0.5}))
    scopes = loader.load_sibling(
        os.path.join(benchtiny.ROOT, "benchmark", "metrics", "x.py"),
        "_scopes")
    assert scopes.collective_seconds_by_axis(ctx)["unindexed"] == 0.5


@pytest.mark.parametrize("metric", FOUR_CHIP_ONLY)
def test_axis_and_bubble_metrics_give_nothing_on_one_chip(audited, metric,
                                                          monkeypatch):
    from smdistributed_modelparallel_tpu.utils import telemetry as tel

    monkeypatch.setattr(tel, "telemetry", tel.TelemetryRegistry())
    assert reader(metric)(context(chips=1)) is None


@pytest.mark.parametrize("audits", [
    {}, {"serve_decode": types.SimpleNamespace(op_index={"x": {}})},
    {"step": types.SimpleNamespace(op_index={})},
    {"step": types.SimpleNamespace(op_index=INDEX),
     "step_pipeline": types.SimpleNamespace(op_index=INDEX)},
], ids=["none", "no-step-program", "audit-off", "two-step-programs"])
def test_no_single_audited_step_program_gives_nothing(monkeypatch, audits):
    from smdistributed_modelparallel_tpu.utils import hlo_audit

    monkeypatch.setattr(hlo_audit, "audits", audits)
    for metric in PHASE_METRICS:
        assert reader(metric)(context()) is None
    assert reader("collectives.time_share.tp")(context()) is None


def test_a_program_without_the_accessor_gives_nothing(monkeypatch):
    """The parent of the PR that brought the index: the readers raise
    nothing there and the metrics are left out."""
    from smdistributed_modelparallel_tpu.utils import hlo_audit
    from smdistributed_modelparallel_tpu.utils import telemetry as tel

    monkeypatch.delattr(hlo_audit, "op_index")
    monkeypatch.setattr(tel, "telemetry", tel.TelemetryRegistry())
    for metric in NEW:
        assert reader(metric)(context()) is None


def test_bubble_share_is_the_executors_own_gauge(monkeypatch):
    from smdistributed_modelparallel_tpu.utils import telemetry as tel

    monkeypatch.setattr(tel, "telemetry", tel.TelemetryRegistry())
    assert reader("pipeline.bubble_share")(context()) is None
    tel.record_pipeline_occupancy("1f1b", 2, 8, busy_slots=32,
                                  total_slots=36)
    assert reader("pipeline.bubble_share")(context()) == pytest.approx(
        100 * (2 - 1) / (8 + 2 - 1))
    tel.telemetry.reset()                   # smp.shutdown() does
    assert reader("pipeline.bubble_share")(context()) == pytest.approx(
        100 * (2 - 1) / (8 + 2 - 1))


def test_host_time_outside_dispatch_sums_the_phase_medians(monkeypatch):
    from smdistributed_modelparallel_tpu.utils import telemetry as tel

    monkeypatch.setattr(tel, "telemetry", tel.TelemetryRegistry())
    read = reader("step.host_outside_dispatch_ms")
    assert read(context()) is None
    hist = tel.telemetry.histogram(
        "smp_host_phase_seconds", buckets=tel.HOST_PHASE_BUCKETS)
    times = {"step/prepare": 160e-6, "step/lookup": 125e-6,
             "step/place": 275e-6, "step/install": 19e-6,
             "step/bookkeeping": 86e-6, "optimizer/step": 50e-6,
             "step/dispatch": 0.390, "step": 0.3917, "step/compile": 30.0}
    for phase, seconds in times.items():
        for _ in range(5):
            hist.labels(phase=phase).observe(seconds)
        hist.labels(phase=phase).observe(seconds * 40)     # a first call
    expected = sum(v for k, v in times.items() if k in (
        "step/prepare", "step/lookup", "step/place", "step/install",
        "step/bookkeeping", "optimizer/step"))
    # A log-bucketed median: within the buckets' growth factor of 1.3.
    assert expected / 1.3 < read(context()) / 1e3 < expected * 1.3
    # The driver frees the program before the readers run: the registry
    # is dropped and the reader finds the closed session's report.
    tel.telemetry.reset()
    assert "smp_host_phase_seconds" not in tel.telemetry.report()["metrics"]
    assert expected / 1.3 < read(context()) / 1e3 < expected * 1.3


@pytest.mark.parametrize("metric", sorted(NEW))
def test_manifest_resolves_every_new_entry(manifest, metric):
    cells = [FOUR] if metric in FOUR_CHIP_ONLY else [ONE, FOUR]
    entry = benchtiny.entry_listing(manifest, metric, cells)
    assert entry["layer"] == NEW[metric]
    assert entry["moves"] == "train.tokens_per_s_per_chip"
    assert entry["better"] == "lower"
    assert callable(manifest.cell(FOUR).metric_reader(metric))
    path = os.path.join(manifest.dir, "metrics", metric + ".py")
    with open(path) as f:
        assert f.read().startswith('"""')


# ----------------------------------------------------------------------
# The join on a recorded pair: real op names, not only synthetic ones
# ----------------------------------------------------------------------

TESTDATA = os.path.join(benchtiny.ROOT, "benchmark", "testdata")
RECORDED_TRACE = os.path.join(TESTDATA, "train-1chip.scopes.trimmed.xplane.pb")
RECORDED_INDEX = os.path.join(TESTDATA, "train-1chip.op_index.json")


@pytest.fixture(scope="module")
def recorded():
    with open(RECORDED_INDEX) as f:
        beside = json.load(f)
    return tr.reduce(RECORDED_TRACE, n_devices=1), beside


def test_recorded_pair_is_one_whole_step_of_the_same_compile(recorded):
    reduced, beside = recorded
    assert os.path.getsize(RECORDED_TRACE) <= os.path.getsize(
        os.path.join(TESTDATA, "train-1chip.trimmed.xplane.pb"))
    assert reduced["window_s"] == pytest.approx(beside["window_s"], abs=1e-9)
    assert reduced["busy_s"] == pytest.approx(beside["busy_s"], abs=2e-9)
    step = [s for name, s in reduced["module_s"].items()
            if name.startswith("jit_full_impl")]
    assert step == [pytest.approx(0.387906505, abs=2e-9)]
    # Every op name of the trace is in the index of that compile, bar the
    # two of the window's tiny programs (the batch slice).
    index = beside["op_index"]
    unknown = sorted(n for n in reduced["op_self_s"] if n not in index)
    assert unknown == beside["names_not_in_index"] == [
        "constant_dynamic-slice_fusion", "copy.1"]
    assert set(index) == set(reduced["op_self_s"]) - set(unknown)
    assert sum(reduced["op_self_s"][n] for n in unknown) < 1e-5


@pytest.mark.parametrize("metric,phase", sorted(PHASE_METRICS.items()))
def test_join_on_the_recorded_pair_gives_the_recorded_shares(
        recorded, monkeypatch, metric, phase):
    from smdistributed_modelparallel_tpu.utils import hlo_audit

    reduced, beside = recorded
    monkeypatch.setattr(hlo_audit, "audits", {
        "step": types.SimpleNamespace(op_index=beside["op_index"])})
    ctx = {"trace": reduced, "run": types.SimpleNamespace(devices=[0])}
    share = reader(metric)(ctx)
    assert share == pytest.approx(beside["phase_share_pct"][phase], abs=1e-6)
    # What the chip said of one step of this PR's tree (PERF.md, PR 24).
    assert share == pytest.approx({
        "forward": 26.9331, "backward": 50.3011, "recompute": 16.2490,
        "optimizer": 6.2074, "other": 0.3093}[phase], abs=1e-3)


def test_recorded_scopes_and_host_spans(recorded):
    reduced, beside = recorded
    by_scope = {}
    for name, seconds in reduced["op_self_s"].items():
        scope = str(beside["op_index"].get(name, {}).get("scope"))
        by_scope[scope] = by_scope.get(scope, 0.0) + seconds
    assert by_scope == pytest.approx(beside["scope_busy_s"], abs=1e-9)
    share = {k: 100 * v / reduced["busy_s"] for k, v in by_scope.items()}
    assert share["smp/step/accumulate"] == pytest.approx(8.83, abs=0.01)
    assert share["smp/optimizer/update"] == pytest.approx(6.21, abs=0.01)
    assert share["smp/step/cast_params"] == pytest.approx(1.07, abs=0.01)
    # The program's own spans are in the same file on the same clock,
    # each with the region open round it in its stats.
    planes = tr.load(RECORDED_TRACE)
    spans = [(name, text) for lines in planes["/host:CPU"].values()
             for name, _, _, text in lines if name.startswith("smp_phase/")]
    names = {name for name, _ in spans}
    assert names == {"smp_phase/" + p for p in (
        "step", "step/prepare", "step/lookup", "step/place", "step/dispatch",
        "step/install", "step/bookkeeping", "optimizer/step")}
    for name, text in spans:
        child = name.startswith("smp_phase/step/")
        assert text.endswith(" smp_phase/step") == child
