"""Adding a configuration, a mix, a cell and a per-layer metric needs only
new files and entries appended last to their lists (``benchtiny.grow``): a
dummy of each goes into a temporary copy and the loader finds them. The
same growth of a copy of the manifest as committed is the ``grown`` case of
every test that takes the ``manifest`` fixture (``conftest.py``), so every
assertion about ``BENCHMARK.json`` in these tests holds after it. And the
CLI refuses a run with no chip."""

import os
import subprocess
import sys

import pytest

import benchtiny
from benchmark import loader

CELL = benchtiny.GROWN_CELL
OWN = ["dummy.twice", "dummy.absent"]


@pytest.fixture()
def extended(tmp_path):
    return benchtiny.grow(benchtiny.tiny_root(tmp_path))


def test_growth_appends_and_leaves_every_entry_where_it_was():
    """What the driver's check asks of a PR that is no ``benchmark`` PR:
    every entry of the parent in its place with its keys, lists only
    longer, new entries last."""
    before = benchtiny.manifest_data()
    after = benchtiny.grown(before)
    assert after != before
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        assert len(after[group]) >= len(before[group])
        for old, new in zip(before[group], after[group]):
            assert {k: v for k, v in new.items() if k != "workloads"} == \
                {k: v for k, v in old.items() if k != "workloads"}
            listed = old.get("workloads", [])
            assert new.get("workloads", [])[:len(listed)] == listed
    assert after["workloads"][-1]["name"] == CELL
    assert [m["name"] for m in after["per_layer"][-2:]] == OWN
    for key in ("command", "paths", "run_seconds"):
        assert after[key] == before[key]


def test_new_entries_are_found_and_old_cells_are_untouched(extended):
    manifest = loader.Manifest(extended)
    cell = manifest.cell(CELL)
    assert cell.builder().module(cell.config) == ("dummy", 8)
    assert {m["name"] for m in cell.end_to_end()} == {benchtiny.RATE,
                                                      "setup_s"}
    # its own entries, and the four that belong to every training cell
    assert [m["name"] for m in cell.per_layer()
            if "workloads" in m] == OWN
    assert len(cell.per_layer()) == len(OWN) + 4
    committed = loader.Manifest()
    for old in committed.cells():
        same = manifest.cell(old.name)
        assert same.per_layer() == old.per_layer()
        assert [m["name"] for m in same.end_to_end()] == [
            m["name"] for m in old.end_to_end()]


def test_the_harness_runs_the_dummy_cell_and_leaves_out_absent_metrics(
        extended):
    import jax

    from benchmark import harness

    cell = loader.Manifest(extended).cell(CELL)
    run = harness.Run(cell, 1, 1.0, 0, jax.devices()[:1], extended)
    with run.window():
        pass
    outcome = cell.driver().run(run)
    line = harness.result_line(run, outcome)
    assert line["correct"] is True
    assert line["metrics"][benchtiny.RATE] == {
        "value": 3.0, "unit": "tokens/s/chip"}
    assert line["metrics"]["setup_s"]["value"] > 0
    ctx = dict(outcome["context"])
    values = {name: cell.metric_reader(name)(ctx) for name in OWN}
    assert values == {"dummy.twice": 6, "dummy.absent": None}


def test_missing_files_are_named(extended):
    os.remove(os.path.join(extended, "benchmark", "drivers", "dummy_kind.py"))
    cell = loader.Manifest(extended).cell(CELL)
    with pytest.raises(loader.BenchmarkError, match="dummy_kind.py"):
        cell.driver()
    with pytest.raises(loader.BenchmarkError, match="no workloads entry"):
        loader.Manifest(extended).cell("nope")


def test_cli_refuses_a_run_with_no_chip():
    cell = benchtiny.manifest_data()["workloads"][0]["name"]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", cell, "--seed",
         "1", "--seconds", "1", "--trace", "0"],
        cwd=benchtiny.ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert done.returncode != 0
    assert "found no TPU" in done.stderr
    assert not any(line.startswith('{"correct"')
                   for line in done.stdout.splitlines())
