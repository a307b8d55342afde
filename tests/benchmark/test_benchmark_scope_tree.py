"""The eight readers of the program's scope tree (PR 39;
``benchmark/metrics/_tree.py``): each on a made-up context, on a program
without the index, the join or the scope, their entries in
``BENCHMARK.json`` (listed by PR 41), and on a recorded pair: one step of a
small
Mellum-family stack traced on the chip with the op index of that compile
beside it, so the rule that gives the grouped products their scope is held
to real instruction names."""

import json
import os
import types

import pytest

import benchtiny
from benchmark import loader
from benchmark import trace_reduce as tr

GPT2 = "gpt2-xl.train-1chip"
PYTHIA = "pythia-1.4b.train-pp2tp2"
LAGUNA = "laguna-s-2.1.train-8k-1chip"
MELLUM = "mellum2-12b-a2.5b.train-8k-group-1chip"
SDAR = "sdar-30b-a3b.train-8k-block-diffusion-1chip"
FIVE = [GPT2, PYTHIA, LAGUNA, MELLUM, SDAR]
# metric -> (layer, the cells it was listed for: a `--trace 1` run of each
# on the chip printed a value, ``PERF.md`` section 6, PR 41)
ENTRIES = {
    "step.unscoped_time_share": ("model step", FIVE),
    "step.user_code_time_share": ("model step", FIVE),
    "head.time_share": ("model step", FIVE),
    "attn.time_share": ("model step", FIVE),
    # the SDAR and Mellum cells have no dense MLP
    "mlp.time_share": ("model step", [GPT2, PYTHIA, LAGUNA]),
    # the 1F1B executors add gradients up inside their ticks, under no
    # ``smp/step/accumulate``: nothing to read in the four-chip cell
    "step.accumulate_time_share": (
        "model step", [GPT2, LAGUNA, MELLUM, SDAR]),
    "moe.grouped_products_time_share": ("kernels", [LAGUNA, MELLUM, SDAR]),
    "pipeline.glue_time_share": (
        "pipeline executors and TP layers", [PYTHIA]),
}
U, L = "smp/step/user", "smp/layer/full"


def reader(metric):
    return loader.Manifest().cell(ENTRIES[metric][1][0]).metric_reader(metric)


def tree_of(read):
    return read.__globals__["_tree"]


def one_chip_context():
    """A traced window of 32 busy seconds on one chip and the op index of
    its step: every part of the tree, a product the index found no scope
    for, a compiler's copy, and a name the index lacks."""
    index = {
        "fusion.1": {"scopes": (U, "smp/model/embed")},
        "fusion.2": {"scopes": (U, L, "smp/attn/full", "smp/attn/qkv")},
        "smp_flash_fwd.3": {"scopes": (U, L, "smp/attn/full",
                                       "smp/attn/core")},
        "fusion.4": {"scopes": (U, "smp/layer/lead", "smp/mlp/dense")},
        "fusion.5": {"scopes": (U, L, "smp/moe/shared")},
        "ragged-dot-none.6": {"scopes": (U, L, "smp/moe/experts"),
                              "kernel": "ragged_dot", "inherited": True},
        "ragged-dot-none.7": {"scope": None, "kernel": "ragged_dot"},
        "fusion.8": {"scopes": (U, "smp/head/norm")},
        "convolution.9": {"scopes": (U, "smp/head/logits")},
        "reduce.10": {"scope": U},
        "fusion.11": {"scope": "smp/step/accumulate"},
        "fusion.12": {"scope": "smp/optimizer/update"},
        "copy.13": {"scope": None},
    }
    seconds = {"fusion.1": 0.5, "fusion.2": 2.0, "smp_flash_fwd.3": 4.0,
               "fusion.4": 8.0, "fusion.5": 1.0, "ragged-dot-none.6": 5.0,
               "ragged-dot-none.7": 1.0, "fusion.8": 0.25,
               "convolution.9": 3.0, "reduce.10": 2.0, "fusion.11": 1.5,
               "fusion.12": 2.25, "copy.13": 1.0, "unknown.14": 0.5}
    for rec in index.values():
        rec.setdefault("phase", "forward")
        if "scopes" in rec:
            rec["scope"] = rec["scopes"][-1]
    return {"trace": {"op_self_s": seconds, "busy_s_by_device": [32.0]}}, index


ONE_CHIP = {
    "step.unscoped_time_share": 100 * (1.0 + 1.0 + 0.5) / 32,
    "step.user_code_time_share": 100 * 2.0 / 32,
    "head.time_share": 100 * 3.25 / 32,
    "attn.time_share": 100 * 6.0 / 32,
    "mlp.time_share": 100 * 8.0 / 32,
    "step.accumulate_time_share": 100 * 1.5 / 32,
    "moe.grouped_products_time_share": 100 * 6.0 / 32,
    "pipeline.glue_time_share": None,
}


def pipeline_context():
    """16 busy seconds under a 1F1B executor: sub-steps, glue of the tick
    loop, the head with the user's loss inside it, what follows the loop."""
    S, T = "smp/pipeline/steady", "smp/pipeline/tick_bwd"
    index = {
        "fusion.1": {"scopes": (S, "smp/pipeline/tick_fwd",
                                "smp/layer/block", "smp/mlp/dense")},
        "fusion.2": {"scopes": (S, T, "smp/layer/block", "smp/attn/full",
                                "smp/attn/out")},
        "fusion.3": {"scope": S},
        "all-reduce.4": {"scopes": ("smp/pipeline/warmup",), "op":
                         "all-reduce", "axis": "pp", "bytes": 8},
        "fusion.5": {"scopes": (S, "smp/pipeline/head", "smp/head/logits")},
        "fusion.6": {"scopes": (S, "smp/pipeline/head", U)},
        "fusion.7": {"scopes": ("smp/pipeline/embed", "smp/model/embed")},
        "fusion.8": {"scopes": ("smp/pipeline/finish", "smp/model/embed")},
        "fusion.9": {"scope": "smp/optimizer/update"},
    }
    seconds = {"fusion.1": 4.0, "fusion.2": 3.0, "fusion.3": 1.5,
               "all-reduce.4": 0.5, "fusion.5": 2.0, "fusion.6": 1.0,
               "fusion.7": 0.5, "fusion.8": 1.5, "fusion.9": 1.0,
               "copy.10": 1.0}
    for rec in index.values():
        rec.setdefault("phase", "forward")
        rec["scope"] = rec.get("scopes", (rec.get("scope"),))[-1]
    return {"trace": {"op_self_s": seconds, "busy_s_by_device": [16.0]}}, index


PIPELINE = {
    "step.unscoped_time_share": 100 * 1.0 / 16,
    "step.user_code_time_share": 100 * 1.0 / 16,
    "head.time_share": 100 * 3.0 / 16,
    "attn.time_share": 100 * 3.0 / 16,
    "mlp.time_share": 100 * 4.0 / 16,
    "step.accumulate_time_share": None,
    "moe.grouped_products_time_share": None,
    "pipeline.glue_time_share": 100 * 2.0 / 16,
}


@pytest.mark.parametrize("metric", sorted(ENTRIES))
@pytest.mark.parametrize("made,expected", [
    (one_chip_context, ONE_CHIP), (pipeline_context, PIPELINE)],
    ids=["one_chip", "pipeline"])
def test_reader_on_a_made_up_context(monkeypatch, metric, made, expected):
    read = reader(metric)
    ctx, index = made()
    monkeypatch.setattr(tree_of(read)._scopes, "step_index", lambda: index)
    if expected[metric] is None:
        assert read(ctx) is None
    else:
        assert read(ctx) == pytest.approx(expected[metric])


def test_the_shares_of_a_cell_add_up_to_busy(monkeypatch):
    """head + user + attention + mlp + accumulate + the expert layers +
    optimizer + embed + unscoped: every second once."""
    ctx, index = one_chip_context()
    value = {}
    for metric in ENTRIES:
        read = reader(metric)
        monkeypatch.setattr(tree_of(read)._scopes, "step_index",
                            lambda: index)
        value[metric] = read(ctx)
    tree = tree_of(reader("attn.time_share"))
    monkeypatch.setattr(tree._scopes, "step_index", lambda: index)
    rest = sum(tree.share(ctx, tree.under(scope)) for scope in (
        "smp/moe/", "smp/optimizer/update", "smp/model/embed"))
    assert sum(value[m] for m in ENTRIES if value[m] is not None
               and m != "moe.grouped_products_time_share") \
        + rest == pytest.approx(100.0)


@pytest.mark.parametrize("metric", sorted(ENTRIES))
@pytest.mark.parametrize("index", [None, {}], ids=["no_index", "empty"])
def test_reader_returns_nothing_without_an_index(monkeypatch, metric, index):
    read = reader(metric)
    ctx, _ = one_chip_context()
    monkeypatch.setattr(tree_of(read)._scopes, "step_index", lambda: index)
    assert read(ctx) is None


@pytest.mark.parametrize("metric", sorted(ENTRIES))
def test_reader_returns_nothing_on_a_program_without_the_join(
        monkeypatch, metric):
    """The parent's tree under this PR's benchmark files: an op index and
    no ``seconds_by_scope``. Nothing read, nothing raised."""
    from smdistributed_modelparallel_tpu.utils import hlo_audit

    read = reader(metric)
    ctx, index = one_chip_context()
    monkeypatch.setattr(tree_of(read)._scopes, "step_index", lambda: index)
    monkeypatch.delattr(hlo_audit, "seconds_by_scope")
    assert read(ctx) is None


@pytest.mark.parametrize("metric", sorted(
    set(ENTRIES) - {"step.unscoped_time_share"}))
def test_reader_returns_nothing_without_its_scope(monkeypatch, metric):
    """An index of the parent's build (a compile cache that handed back its
    executable): scopes of before this PR alone."""
    read = reader(metric)
    ctx, _ = one_chip_context()
    index = {"fusion.2": {"phase": "forward", "scope": "smp/layer/full"},
             "fusion.12": {"phase": "optimizer",
                           "scope": "smp/optimizer/update"},
             "ragged-dot-none.6": {"phase": "forward", "scope": None}}
    monkeypatch.setattr(tree_of(read)._scopes, "step_index", lambda: index)
    assert read(ctx) is None
    unscoped = reader("step.unscoped_time_share")
    monkeypatch.setattr(tree_of(unscoped)._scopes, "step_index",
                        lambda: index)
    assert unscoped(ctx) == pytest.approx(100 * (32.0 - 2.0 - 2.25) / 32)


@pytest.mark.parametrize("metric", sorted(ENTRIES))
def test_entry_lists_the_cells_the_reader_was_read_in(manifest, metric):
    """The entry is ``ENTRIES``'s wherever it stands in ``per_layer``, and
    the cells that printed a value for it report it."""
    assert os.path.isfile(os.path.join(
        benchtiny.ROOT, "benchmark", "metrics", metric + ".py"))
    layer, cells = ENTRIES[metric]
    assert benchtiny.entry_listing(manifest, metric, cells) == {
        "name": metric, "unit": "%", "better": "lower",
        "source": "device_trace", "layer": layer,
        "moves": "train.tokens_per_s_per_chip"}


def test_no_reader_takes_the_name_of_a_listed_metric(manifest):
    names = [m["name"] for m in manifest.data["per_layer"]]
    assert len(names) == len(set(names))
    assert set(ENTRIES) <= set(names)


# ----------------------------------------------------------------------
# The readers on a recorded pair: real names of the compiler's kernels
# ----------------------------------------------------------------------

TESTDATA = os.path.join(benchtiny.ROOT, "benchmark", "testdata")
RECORDED_TRACE = os.path.join(TESTDATA, "mellum-tiny.scopes.trimmed.xplane.pb")
RECORDED_INDEX = os.path.join(TESTDATA, "mellum-tiny.op_index.json")


@pytest.fixture(scope="module")
def recorded():
    with open(RECORDED_INDEX) as f:
        beside = json.load(f)
    for rec in beside["op_index"].values():
        if "scopes" in rec:
            rec["scopes"] = tuple(rec["scopes"])
    return tr.reduce(RECORDED_TRACE, n_devices=1), beside


def test_recorded_pair_is_one_step_of_one_compile(recorded):
    reduced, beside = recorded
    assert os.path.getsize(RECORDED_TRACE) < 300_000
    assert os.path.getsize(RECORDED_INDEX) < 300_000
    assert reduced["busy_s"] == pytest.approx(beside["busy_s"], abs=2e-9)
    assert len(reduced["module_s"]) == 1
    index = beside["op_index"]
    unknown = sorted(n for n in reduced["op_self_s"] if n not in index)
    assert unknown == beside["names_not_in_index"]
    assert sum(reduced["op_self_s"][n] for n in unknown) \
        < 1e-3 * reduced["busy_s"]


def test_every_grouped_product_of_the_recorded_step_lies_under_experts(
        recorded):
    """The compiler's kernels as the chip names them: twelve products and
    the metadata they read, forward, recomputed and transposed, each with
    no path in its own ``op_name`` and each given ``smp/moe/experts`` by
    the index's rule."""
    reduced, beside = recorded
    products = {n: r for n, r in beside["op_index"].items()
                if n.startswith("ragged-dot")}
    assert len([n for n in products if "metadata" not in n]) == 12
    for name, rec in products.items():
        assert rec["inherited"] is True, name
        assert rec["scope"] == "smp/moe/experts", name
        assert rec["scopes"][0] == "smp/step/user", name
        assert rec["kernel"] == ("ragged_dot_metadata" if "metadata" in name
                                 else "ragged_dot"), name
    seconds = sum(reduced["op_self_s"][n] for n in products)
    assert seconds > 0.02 * reduced["busy_s"]


def test_readers_on_the_recorded_pair(recorded, monkeypatch):
    """What the chip said of one step of this small stack (my chip run,
    PR 39): the shares the eight readers give, the parts adding up, and the
    readers of before this PR counting the products once."""
    from smdistributed_modelparallel_tpu.utils import hlo_audit

    reduced, beside = recorded
    monkeypatch.setattr(hlo_audit, "audits", {
        "step": types.SimpleNamespace(op_index=beside["op_index"])})
    ctx = {"trace": reduced, "run": types.SimpleNamespace(devices=[0])}
    value = {m: reader(m)(ctx) for m in ENTRIES}
    assert value["pipeline.glue_time_share"] is None
    assert value["mlp.time_share"] is None        # every layer is routed
    assert {m: round(v, 2) for m, v in value.items() if v is not None} == {
        "step.unscoped_time_share": 11.20, "step.user_code_time_share": 14.90,
        "head.time_share": 0.35, "attn.time_share": 11.02,
        "step.accumulate_time_share": 2.70,
        "moe.grouped_products_time_share": 8.83}
    busy = sum(reduced["op_self_s"].values())
    assert value["moe.grouped_products_time_share"] == pytest.approx(
        100 * beside["kernel_seconds"]["ragged_dot"] / busy)
    assert value["step.unscoped_time_share"] == pytest.approx(
        100 * beside["unscoped_seconds"] / busy)
    assert value["step.user_code_time_share"] == pytest.approx(
        100 * beside["user_only_seconds"] / busy)
    tree = tree_of(reader("attn.time_share"))
    rest = sum(tree.share(ctx, tree.under(scope)) for scope in (
        "smp/moe/", "smp/optimizer/update", "smp/model/embed",
        "smp/step/cast_params"))
    layers = tree.share(ctx, tree.under("smp/layer/"))
    parts = sum(value[m] for m in (
        "step.unscoped_time_share", "step.user_code_time_share",
        "head.time_share", "attn.time_share",
        "step.accumulate_time_share")) + rest
    # what is left is the layers' own: norms and residual adds
    assert parts <= 100.0 + 1e-6
    assert 100.0 - parts <= layers
    # PR 31's reader adds the products by name only where the index gives
    # them no scope: with the scope they are counted once, under it
    experts = loader.Manifest().cell(MELLUM).metric_reader(
        "moe.experts_time_share")
    moe = loader.Manifest().cell(LAGUNA).metric_reader("moe.time_share")
    under = tree.share(ctx, tree.under("smp/moe/experts"))
    assert experts(ctx) == pytest.approx(
        under * busy / reduced["busy_s_by_device"][0])
    assert moe(ctx) >= experts(ctx) > 0
    assert (experts(ctx), moe(ctx)) == (pytest.approx(19.777, abs=1e-3),
                                        pytest.approx(47.375, abs=1e-3))
    stripped = {n: (dict(r, scope=None, scopes=()) if n.startswith(
        "ragged-dot") else r) for n, r in beside["op_index"].items()}
    monkeypatch.setattr(hlo_audit, "audits", {
        "step": types.SimpleNamespace(op_index=stripped)})
    assert experts(ctx) == pytest.approx(
        under * busy / reduced["busy_s_by_device"][0])   # by name, as before
