"""Adding a configuration, a mix, a cell and a per-layer metric needs only
new files and new entries: a dummy of each goes into a temporary copy and
the loader finds them. And the CLI refuses a run with no chip."""

import json
import os
import subprocess
import sys

import pytest

import benchtiny
from benchmark import loader


@pytest.fixture()
def extended(tmp_path):
    root = benchtiny.tiny_root(tmp_path)
    bench = os.path.join(root, "benchmark")
    with open(os.path.join(bench, "configs", "dummy-model.json"), "w") as f:
        json.dump({"source": "https://example.org/dummy", "reduced": {},
                   "assumed": {}, "deployment": "none",
                   "builder": "dummy_builder", "smp": {}, "width": 8}, f)
    with open(os.path.join(bench, "builders", "dummy_builder.py"), "w") as f:
        f.write("def module(cfg):\n    return ('dummy', cfg['width'])\n")
    with open(os.path.join(bench, "traffic", "dummy-mix.json"), "w") as f:
        json.dump({"kind": "dummy_kind", "steps": 3}, f)
    with open(os.path.join(bench, "drivers", "dummy_kind.py"), "w") as f:
        f.write(
            "def run(run):\n"
            "    n = run.cell.traffic['steps']\n"
            "    return {'correct': True, 'attempted': n, 'failed': 0,\n"
            "            'end_to_end': {'dummy.rate': float(n)},\n"
            "            'context': {'steps': n}}\n")
    with open(os.path.join(bench, "metrics", "dummy.twice.py"), "w") as f:
        f.write("def read(ctx):\n    return 2 * ctx['steps']\n")
    with open(os.path.join(bench, "metrics", "dummy.absent.py"), "w") as f:
        f.write("def read(ctx):\n    return None\n")
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        data = json.load(f)
    data["configs"].append({
        "name": "dummy-model", "source": "https://example.org/dummy",
        "file": "benchmark/configs/dummy-model.json", "reduced": [],
        "why": "dummy"})
    data["workloads"].append({
        "name": "dummy-model.dummy-mix", "config": "dummy-model",
        "traffic": "dummy-mix", "chips": 1, "why": "dummy"})
    data["end_to_end"].append({
        "name": "dummy.rate", "unit": "steps/s", "better": "higher",
        "bound": 0.05, "source": "host_clock",
        "workloads": ["dummy-model.dummy-mix"]})
    for name in ("dummy.twice", "dummy.absent"):
        data["per_layer"].append({
            "name": name, "unit": "steps", "better": "higher",
            "source": "program_counter", "layer": "dummy",
            "moves": "dummy.rate"})
    with open(path, "w") as f:
        json.dump(data, f)
    return root


def test_new_entries_are_found_and_old_cells_are_untouched(extended):
    manifest = loader.Manifest(extended)
    cell = manifest.cell("dummy-model.dummy-mix")
    assert cell.builder().module(cell.config) == ("dummy", 8)
    assert {m["name"] for m in cell.end_to_end()} == {"dummy.rate", "setup_s"}
    assert [m["name"] for m in cell.per_layer()] == [
        "dummy.twice", "dummy.absent"]
    for old in benchtiny.manifest_data()["workloads"]:
        names = {m["name"] for m in manifest.cell(old["name"]).per_layer()}
        assert not names & {"dummy.twice", "dummy.absent"}


def test_the_harness_runs_the_dummy_cell_and_leaves_out_absent_metrics(
        extended):
    import jax

    from benchmark import harness

    cell = loader.Manifest(extended).cell("dummy-model.dummy-mix")
    run = harness.Run(cell, 1, 1.0, 0, jax.devices()[:1], extended)
    with run.window():
        pass
    outcome = cell.driver().run(run)
    line = harness.result_line(run, outcome)
    assert line["correct"] is True
    assert line["metrics"]["dummy.rate"] == {"value": 3.0, "unit": "steps/s"}
    assert line["metrics"]["setup_s"]["value"] > 0
    ctx = dict(outcome["context"])
    values = {m["name"]: cell.metric_reader(m["name"])(ctx)
              for m in cell.per_layer()}
    assert values == {"dummy.twice": 6, "dummy.absent": None}


def test_missing_files_are_named(extended):
    os.remove(os.path.join(extended, "benchmark", "drivers", "dummy_kind.py"))
    cell = loader.Manifest(extended).cell("dummy-model.dummy-mix")
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
