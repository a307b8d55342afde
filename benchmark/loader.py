"""Finds a cell's files by the names ``BENCHMARK.json`` gives them.

Pure Python, no JAX: the tests and the CLI's argument checks use it before
any device is touched.

    cell "gpt2-xl.serve-chat" -> config "gpt2-xl"  -> configs/gpt2-xl.json
                                 traffic "serve-chat" -> traffic/serve-chat.json
                                 its "kind"        -> drivers/<kind>.py
    config's "builder"                             -> builders/<builder>.py
    per-layer metric "step.mfu"                    -> metrics/step.mfu.py

A later PR adds files and entries; nothing here names a cell, a
configuration, a mix or a metric.
"""

import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class BenchmarkError(Exception):
    """The manifest or one of the files it names is missing or malformed."""


def _read_json(path):
    if not os.path.isfile(path):
        raise BenchmarkError(f"missing file: {path}")
    with open(path) as f:
        return json.load(f)


def load_module(path, name):
    """Import one file by path (metric names hold dots, so not by name)."""
    if not os.path.isfile(path):
        raise BenchmarkError(f"missing file: {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_sibling(file, name):
    """The module ``<name>.py`` beside ``file``: readers that share their
    arithmetic (a metric split by the end-to-end metric it moves) keep it
    in one ``_``-prefixed file of the same directory."""
    folder = os.path.dirname(os.path.abspath(file))
    return load_module(os.path.join(folder, name + ".py"),
                       "benchmark_shared_" + name.lstrip("_"))


class Manifest:
    """``BENCHMARK.json`` and the directory of the benchmark's files."""

    def __init__(self, root=ROOT, bench_dir=None):
        self.root = root
        self.dir = bench_dir or os.path.join(root, "benchmark")
        self.data = _read_json(os.path.join(root, "BENCHMARK.json"))

    def _entry(self, group, name):
        for entry in self.data[group]:
            if entry["name"] == name:
                return entry
        raise BenchmarkError(f"no {group} entry named {name!r}")

    def cell(self, name):
        return Cell(self, self._entry("workloads", name))

    def cells(self):
        return [Cell(self, w) for w in self.data["workloads"]]

    def metrics_for(self, group, cell_name):
        """Entries of ``end_to_end`` or ``per_layer`` this cell reports: a
        metric with no ``workloads`` key belongs to every cell that reports
        what it ``moves`` (every cell, for an end-to-end metric)."""
        e2e_here = {
            m["name"] for m in self.data["end_to_end"]
            if cell_name in m.get("workloads", [cell_name])
        }
        if group == "end_to_end":
            return [m for m in self.data[group] if m["name"] in e2e_here]
        return [
            m for m in self.data[group]
            if (cell_name in m["workloads"] if "workloads" in m
                else m["moves"] in e2e_here)
        ]


class Cell:
    def __init__(self, manifest, entry):
        self.manifest = manifest
        self.name = entry["name"]
        self.chips = int(entry["chips"])
        self.config_entry = manifest._entry("configs", entry["config"])
        self.config = _read_json(
            os.path.join(manifest.root, self.config_entry["file"]))
        self.traffic_name = entry["traffic"]
        self.traffic = _read_json(os.path.join(
            manifest.dir, "traffic", entry["traffic"] + ".json"))
        self._loaded = {}

    def _module(self, folder, name):
        """One module object per file and cell, however often it is asked
        for (a test can then patch what the driver will see)."""
        key = (folder, name)
        if key not in self._loaded:
            self._loaded[key] = load_module(
                os.path.join(self.manifest.dir, folder, name + ".py"),
                f"benchmark_{folder}_{name}")
        return self._loaded[key]

    def driver(self):
        return self._module("drivers", self.traffic["kind"])

    def builder(self):
        return self._module("builders", self.config["builder"])

    def end_to_end(self):
        return self.manifest.metrics_for("end_to_end", self.name)

    def per_layer(self):
        return self.manifest.metrics_for("per_layer", self.name)

    def metric_reader(self, metric_name):
        path = os.path.join(self.manifest.dir, "metrics", metric_name + ".py")
        return load_module(
            path, "benchmark_metric_" + metric_name.replace(".", "_")).read
