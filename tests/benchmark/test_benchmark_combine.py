"""``moe.combine_time_share`` (PR 38): the reader on a made-up context, on a
program without the scope, and its entry, which lists the cells it was
written for wherever it stands in ``per_layer``."""

import types

import pytest

import benchtiny
from benchmark import loader

METRIC = "moe.combine_time_share"
CELLS = ["laguna-s-2.1.train-8k-1chip",
         "mellum2-12b-a2.5b.train-8k-group-1chip"]


def reader_context():
    """A traced window of 20 busy seconds: the kernel forward and backward,
    a scatter-add fusion of a program from before it, the sum with the
    shared expert, and ops of other scopes or none."""
    seconds = {"smp_row_scatter_add.3": 1.0, "smp_row_scatter_add.5": 0.75,
               "fusion.1066": 2.0, "add_convert_fusion.4": 0.25,
               "sort.2": 1.0, "smp_grouped_wgrad.28": 3.0,
               "ragged-dot-none.8": 2.0, "unknown.1": 5.0}
    index = {
        "smp_row_scatter_add.3": {
            "scopes": ("smp/layer/full", "smp/moe/combine")},
        "smp_row_scatter_add.5": {
            "scopes": ("smp/layer/window", "smp/moe/combine")},
        "fusion.1066": {"scopes": ("smp/layer/window", "smp/moe/combine")},
        "add_convert_fusion.4": {"scope": "smp/moe/combine"},
        "sort.2": {"scopes": ("smp/layer/full", "smp/moe/dispatch")},
        "smp_grouped_wgrad.28": {
            "scopes": ("smp/layer/full", "smp/moe/experts")},
        "ragged-dot-none.8": {"phase": "backward", "scope": None},
    }
    trace = dict(op_self_s=seconds, busy_s_by_device=[20.0])
    return {"trace": trace, "cell": types.SimpleNamespace(config={})}, index


@pytest.mark.parametrize("cell", CELLS)
def test_reader_sums_the_ops_under_the_combine_scope(monkeypatch, cell):
    read = loader.Manifest().cell(cell).metric_reader(METRIC)
    ctx, index = reader_context()
    scopes = read.__globals__["_moe"]._scopes
    monkeypatch.setattr(scopes, "step_index", lambda: index)
    assert read(ctx) == pytest.approx(100 * (1.0 + 0.75 + 2.0 + 0.25) / 20)
    # the parent's program: the same scope on its scatter-add fusions alone
    before = {k: v for k, v in index.items() if "smp_row" not in k}
    monkeypatch.setattr(scopes, "step_index", lambda: before)
    assert read(ctx) == pytest.approx(100 * (2.0 + 0.25) / 20)


@pytest.mark.parametrize("index", [
    None,
    {},
    {"fusion.1066": {"phase": "other", "scope": None},
     "sort.2": {"scopes": ("smp/layer/full", "smp/moe/dispatch")}},
], ids=["no_index", "an_empty_index", "no_such_scope"])
def test_reader_returns_nothing_without_the_scope(monkeypatch, index):
    """A program without an expert layer, without the scopes or without an
    op index: nothing to read, nothing raised, and the line leaves the
    metric out."""
    read = loader.Manifest().cell(CELLS[1]).metric_reader(METRIC)
    ctx, _ = reader_context()
    scopes = read.__globals__["_moe"]._scopes
    monkeypatch.setattr(scopes, "step_index", lambda: index)
    assert read(ctx) is None


def test_metric_is_listed_for_the_mellum_and_laguna_cells(manifest):
    assert benchtiny.entry_listing(manifest, METRIC, CELLS) == {
        "name": METRIC, "unit": "%", "better": "lower",
        "source": "device_trace", "layer": "expert layers",
        "moves": "train.tokens_per_s_per_chip"}
