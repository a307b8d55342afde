"""``BENCHMARK.json`` against the contract's schema and naming rules, every
file a cell names found by name, and the one rule on what an entry's
``workloads`` list may hold. Every test runs on the committed manifest and
on a copy grown as a later PR grows it (``benchtiny.grow``: entries
appended last, files added): nothing here, and nothing in the other files'
tests that take ``manifest``, holds an entry to a place in its list."""

import os
import re

import pytest

import benchtiny
from benchmark import loader

COMMITTED = benchtiny.manifest_data()
VARIANTS = {"committed": COMMITTED, "grown": benchtiny.grown(COMMITTED)}
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTH_WORDS = ("hidden", "intermediate", "latent", "state", "proj", "n_embd",
               "n_inner", "head_dim", "expansion", "experts_per")
# The per-layer entries that give no ``workloads`` list and so belong to
# every cell that reports what they ``move``, a later PR's cells too. They
# stay these four: a new entry gives its list, so that appending one never
# changes what an accepted cell reports.
FOR_EVERY_TRAIN_CELL = {"step.mfu", "step.dispatch_ms",
                        "device.idle_share.train", "device.hbm_peak_gb.train"}


def entries_of(*groups):
    """``(variant, group, entry)`` of every entry of ``groups``, and ids."""
    rows = [(v, g, e) for v, data in VARIANTS.items() for g in groups
            for e in data[g]]
    return dict(argvalues=rows,
                ids=[f"{v}:{g}:{e['name']}" for v, g, e in rows])


def one_line(text, limit=200):
    return 1 <= len(text) <= limit and "\n" not in text and "\t" not in text


def test_top_level_keys_and_limits(manifest):
    data = manifest.data
    assert set(data) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= len(data["paths"]) <= 16
    assert all(PATH.match(p) and not p.startswith("/") and ".." not in p
               for p in data["paths"])
    assert 1 <= len(data["command"]) <= 32
    assert all(one_line(w) for w in data["command"])
    assert isinstance(data["run_seconds"], int)
    assert 1 <= data["run_seconds"] <= 51
    assert 1 <= len(data["configs"]) <= 24
    assert 1 <= len(data["workloads"]) <= 24
    assert 1 <= len(data["end_to_end"]) <= 16
    assert 1 <= len(data["per_layer"]) <= 128
    size = os.path.getsize(os.path.join(manifest.root, "BENCHMARK.json"))
    assert size <= 64 * 1024


def test_check_fits_the_allowance_with_24_cells(manifest):
    """2 + 14 x cells runs of run_seconds + 60 s, 180 s more a cell, 1200 s
    spare, inside 43200 s with the full 24 cells."""
    runs = 2 + 14 * 24
    total = runs * (manifest.data["run_seconds"] + 60) + 24 * 180 + 1200
    assert total <= 43200


def test_command_names_only_the_benchmarks_files(manifest):
    for word in manifest.data["command"][1:]:
        if os.path.exists(os.path.join(manifest.root, word)):
            assert any(word.startswith(p + "/")
                       for p in manifest.data["paths"])


@pytest.mark.parametrize(
    "variant,group,entry",
    **entries_of("configs", "workloads", "end_to_end", "per_layer"))
def test_entry_keys_and_names(variant, group, entry):
    data = VARIANTS[variant]
    keys = {
        "configs": {"name", "source", "file", "reduced", "why"},
        "workloads": {"name", "config", "traffic", "chips", "why"},
        "end_to_end": {"name", "unit", "better", "bound", "source"},
        "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
    }[group]
    optional = {"workloads"} if group in ("end_to_end", "per_layer") else set()
    assert keys <= set(entry) <= keys | optional
    assert NAME.match(entry["name"])
    if group == "configs":
        assert one_line(entry["source"]) and one_line(entry["why"])
        assert len(entry["reduced"]) <= 16
        for key in entry["reduced"]:
            assert NAME.match(key)
            assert not key.endswith(("_dim", "_rank"))
            assert not any(w in key for w in WIDTH_WORDS), key
    elif group == "workloads":
        assert NAME.match(entry["config"]) and NAME.match(entry["traffic"])
        assert entry["chips"] in (1, 4) and one_line(entry["why"])
    else:
        assert UNIT.match(entry["unit"])
        assert entry["better"] in ("lower", "higher")
        assert entry["source"] in SOURCES
    if group == "end_to_end":
        assert entry["source"] in ("host_clock", "device_trace")
        assert 0.01 <= entry["bound"] <= 0.1
    if group == "per_layer":
        assert one_line(entry["layer"])
        assert entry["moves"] in {m["name"] for m in data["end_to_end"]}


@pytest.mark.parametrize("variant,group,entry",
                         **entries_of("end_to_end", "per_layer"))
def test_a_metrics_list_names_cells_that_report_what_it_moves(
        variant, group, entry, roots):
    """The one rule on ``workloads`` lists, for every entry of the
    manifest. Each name of a list is a cell and stands there once; a cell
    listed under a per-layer metric reports the end-to-end metric it
    ``moves``; a listed per-layer metric has a reader file that loads; a
    per-layer entry without a list is one of the four that are so today.
    Which cells a reader is listed for beyond those its own tests name is
    this rule's business and no other test's: a later PR appends a cell's
    name to a list, and an entry to ``per_layer``, and edits no test."""
    manifest = loader.Manifest(roots[variant])
    cells = [w["name"] for w in manifest.data["workloads"]]
    listed = entry.get("workloads")
    if listed is not None:
        assert listed and len(listed) == len(set(listed))
        assert set(listed) <= set(cells)
    if group == "end_to_end":
        return
    if listed is None:
        assert entry["name"] in FOR_EVERY_TRAIN_CELL
    reporting = [c for c in cells if entry["name"] in {
        m["name"] for m in manifest.cell(c).per_layer()}]
    assert reporting and (listed is None or reporting == [
        c for c in cells if c in listed])
    for cell in reporting:
        assert entry["moves"] in {
            m["name"] for m in manifest.cell(cell).end_to_end()}, cell
    assert callable(manifest.cell(reporting[0]).metric_reader(entry["name"]))


def test_names_are_unique_and_configs_are_used(manifest):
    data = manifest.data
    for group in ("configs", "workloads"):
        names = [e["name"] for e in data[group]]
        assert len(names) == len(set(names))
    metrics = [m["name"] for m in data["end_to_end"] + data["per_layer"]]
    assert len(metrics) == len(set(metrics))
    assert "setup_s" in metrics
    used = {w["config"] for w in data["workloads"]}
    assert used == {c["name"] for c in data["configs"]}
    pairs = [(w["config"], w["traffic"]) for w in data["workloads"]]
    assert len(pairs) == len(set(pairs))
    files = [c["file"] for c in data["configs"]]
    assert len(files) == len(set(files))
    four = sum(w["chips"] == 4 for w in data["workloads"])
    assert four <= max(1, len(data["workloads"]) // 4)


@pytest.mark.parametrize("variant,group,entry", **entries_of("workloads"))
def test_cell_files_are_found_by_name(variant, group, entry, roots):
    manifest = loader.Manifest(roots[variant])
    cell = manifest.cell(entry["name"])
    assert cell.config["builder"] and cell.traffic["kind"]
    assert hasattr(cell.driver(), "run")
    assert hasattr(cell.builder(), "module")
    e2e = [m["name"] for m in cell.end_to_end()]
    assert "setup_s" in e2e and len(e2e) >= 2
    per_layer = cell.per_layer()
    assert per_layer
    for metric in per_layer:
        assert callable(cell.metric_reader(metric["name"]))
        assert metric["moves"] in e2e
    limits = os.path.join(manifest.dir, "limits", cell.name + ".json")
    assert os.path.isfile(limits)


@pytest.mark.parametrize("variant,group,entry", **entries_of("configs"))
def test_config_file_states_its_cut(variant, group, entry, roots):
    cell_cfg = loader._read_json(os.path.join(roots[variant], entry["file"]))
    assert entry["file"].startswith(
        tuple(p + "/" for p in VARIANTS[variant]["paths"]))
    assert cell_cfg["source"] == entry["source"]
    assert sorted(cell_cfg["reduced"]) == sorted(entry["reduced"])
    for key in ("assumed", "deployment", "builder", "smp"):
        assert key in cell_cfg


def test_files_under_paths_are_named_from_the_allowed_characters(manifest):
    for base in manifest.data["paths"]:
        for folder, _, files in os.walk(os.path.join(manifest.root, base)):
            if "__pycache__" in folder:
                continue
            for name in files:
                rel = os.path.relpath(os.path.join(folder, name),
                                      manifest.root)
                assert PATH.match(rel), rel
