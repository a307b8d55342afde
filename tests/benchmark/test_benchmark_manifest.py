"""``BENCHMARK.json`` against the contract's schema and naming rules, and
every file a cell names found by name."""

import os
import re

import pytest

import benchtiny
from benchmark import loader

DATA = benchtiny.manifest_data()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTH_WORDS = ("hidden", "intermediate", "latent", "state", "proj", "n_embd",
               "n_inner", "head_dim", "expansion", "experts_per")
ENTRIES = [(g, e) for g in ("configs", "workloads", "end_to_end", "per_layer")
           for e in DATA[g]]


def one_line(text, limit=200):
    return 1 <= len(text) <= limit and "\n" not in text and "\t" not in text


def test_top_level_keys_and_limits():
    assert set(DATA) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= len(DATA["paths"]) <= 16
    assert all(PATH.match(p) and not p.startswith("/") and ".." not in p
               for p in DATA["paths"])
    assert 1 <= len(DATA["command"]) <= 32
    assert all(one_line(w) for w in DATA["command"])
    assert isinstance(DATA["run_seconds"], int)
    assert 1 <= DATA["run_seconds"] <= 51
    assert 1 <= len(DATA["configs"]) <= 24
    assert 1 <= len(DATA["workloads"]) <= 24
    assert 1 <= len(DATA["end_to_end"]) <= 16
    assert 1 <= len(DATA["per_layer"]) <= 128
    size = os.path.getsize(os.path.join(benchtiny.ROOT, "BENCHMARK.json"))
    assert size <= 64 * 1024


def test_check_fits_the_allowance_with_24_cells():
    """2 + 14 x cells runs of run_seconds + 60 s, 180 s more a cell, 1200 s
    spare, inside 43200 s with the full 24 cells."""
    runs = 2 + 14 * 24
    total = runs * (DATA["run_seconds"] + 60) + 24 * 180 + 1200
    assert total <= 43200


def test_command_names_only_the_benchmarks_files():
    for word in DATA["command"][1:]:
        if os.path.exists(os.path.join(benchtiny.ROOT, word)):
            assert any(word.startswith(p + "/") for p in DATA["paths"])


@pytest.mark.parametrize(
    "group,entry", ENTRIES, ids=[f"{g}:{e['name']}" for g, e in ENTRIES])
def test_entry_keys_and_names(group, entry):
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
        cells = {w["name"] for w in DATA["workloads"]}
        assert set(entry.get("workloads", [])) <= cells
    if group == "end_to_end":
        assert entry["source"] in ("host_clock", "device_trace")
        assert 0.01 <= entry["bound"] <= 0.1
    if group == "per_layer":
        assert one_line(entry["layer"])
        assert entry["moves"] in {m["name"] for m in DATA["end_to_end"]}


def test_names_are_unique_and_configs_are_used():
    for group in ("configs", "workloads"):
        names = [e["name"] for e in DATA[group]]
        assert len(names) == len(set(names))
    metrics = [m["name"] for m in DATA["end_to_end"] + DATA["per_layer"]]
    assert len(metrics) == len(set(metrics))
    assert "setup_s" in metrics
    used = {w["config"] for w in DATA["workloads"]}
    assert used == {c["name"] for c in DATA["configs"]}
    pairs = [(w["config"], w["traffic"]) for w in DATA["workloads"]]
    assert len(pairs) == len(set(pairs))
    files = [c["file"] for c in DATA["configs"]]
    assert len(files) == len(set(files))
    four = sum(w["chips"] == 4 for w in DATA["workloads"])
    assert four <= max(1, len(DATA["workloads"]) // 4)


@pytest.mark.parametrize("cell_name", [w["name"] for w in DATA["workloads"]])
def test_cell_files_are_found_by_name(cell_name):
    cell = loader.Manifest().cell(cell_name)
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
    limits = os.path.join(benchtiny.ROOT, "benchmark", "limits",
                          cell_name + ".json")
    assert os.path.isfile(limits)


@pytest.mark.parametrize("config", DATA["configs"], ids=lambda c: c["name"])
def test_config_file_states_its_cut(config):
    cell_cfg = loader._read_json(os.path.join(benchtiny.ROOT, config["file"]))
    assert config["file"].startswith(tuple(p + "/" for p in DATA["paths"]))
    assert cell_cfg["source"] == config["source"]
    assert sorted(cell_cfg["reduced"]) == sorted(config["reduced"])
    for key in ("assumed", "deployment", "builder", "smp"):
        assert key in cell_cfg


def test_files_under_paths_are_named_from_the_allowed_characters():
    for base in DATA["paths"]:
        for folder, _, files in os.walk(os.path.join(benchtiny.ROOT, base)):
            if "__pycache__" in folder:
                continue
            for name in files:
                rel = os.path.relpath(os.path.join(folder, name),
                                      benchtiny.ROOT)
                assert PATH.match(rel), rel
