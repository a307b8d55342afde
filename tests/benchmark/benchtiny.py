"""Helpers of the benchmark's tests: a temporary copy of the benchmark with
tiny sizes, and a ``Run`` on the CPU that skips the harness's look for a
chip. Nothing here touches JAX at import."""

import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

TINY_MODEL = dict(n_embd=64, n_head=2, n_layer=2, n_positions=128,
                  vocab_size=128)
TINY_NEOX = dict(hidden_size=64, num_attention_heads=4, num_hidden_layers=4,
                 intermediate_size=256, vocab_size=128,
                 max_position_embeddings=32)

# The serving cell is not in ``BENCHMARK.json`` yet (``PERF.md``, Open
# questions, row 1): its configuration, mix, driver and readers are, and
# the tests list the cell in their temporary copy.
SERVE_CELL = "gpt2-xl.serve-chat"
SERVE_ENTRIES = {
    "configs": [{"name": "gpt2-xl", "source": "x", "reduced": [], "why": "x",
                 "file": "benchmark/configs/gpt2-xl.json"}],
    "workloads": [{"name": SERVE_CELL, "config": "gpt2-xl",
                   "traffic": "serve-chat", "chips": 1, "why": "x"}],
    "end_to_end": [
        {"name": n, "unit": u, "better": b, "bound": 0.05,
         "source": "host_clock", "workloads": [SERVE_CELL]}
        for n, u, b in (("serve.ttft_p90_ms", "ms", "lower"),
                        ("serve.itl_p95_ms", "ms", "lower"),
                        ("serve.out_tokens_per_s", "tokens/s", "higher"))],
    "per_layer": [
        {"name": n, "unit": "x", "better": "lower", "source": "host_clock",
         "layer": "serving engine", "moves": "serve.out_tokens_per_s",
         "workloads": [SERVE_CELL]}
        for n in ("engine.tick_ms", "engine.batch_occupancy",
                  "engine.prefill_tokens_per_tick")],
}
TINY_TRAIN_MIX = dict(batch=8, seq=32, batch_pool=4)
TINY_SERVE_MIX = dict(
    rate_rps=6.0, max_total=128,
    prompt={"median": 24, "sigma": 0.9, "min": 4, "max": 90},
    output={"median": 10, "sigma": 0.7, "min": 2, "max": 30})

# Limits for the tiny sizes on the CPU (bf16 compute through XLA:CPU). Set
# as the chip's are: above what sound tiny runs read, below the control's.
TINY_TRAIN_LIMITS = {
    "loss_gap_step1": 0.002, "loss_gap_step2": 0.002,
    "loss_gap_step3": 0.002, "first_grad_norm_gap": 0.0025,
    "param_change_norm_gap": 0.4, "loss_rise_over_window": 0.0,
    "flash_kernels_missing": 3,        # the CPU path has no Pallas kernels
}
TINY_SERVE_LIMITS = {
    "greedy_regret_max": 0.002, "requests_not_finished": 0,
    "requests_refused": 0, "programs_unexpected": 0,
}


def manifest_data(root=ROOT):
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


# What a later PR brings with a model, each entry the last of its list: a
# configuration, a cell, the cell's name in the rate's list, two per-layer
# entries with their readers. ``grow`` makes the additions in a copy, and
# the tests hold every assertion about the manifest on that copy too.
RATE = "train.tokens_per_s_per_chip"
GROWN_CELL = "dummy-model.dummy-mix"
GROWTH = {
    "configs": [{
        "name": "dummy-model", "source": "https://example.org/dummy",
        "file": "benchmark/configs/dummy-model.json", "reduced": [],
        "why": "dummy"}],
    "workloads": [{
        "name": GROWN_CELL, "config": "dummy-model", "traffic": "dummy-mix",
        "chips": 1, "why": "dummy"}],
    "per_layer": [
        {"name": name, "unit": "steps", "better": "higher",
         "source": "program_counter", "layer": "dummy", "moves": RATE,
         "workloads": [GROWN_CELL]}
        for name in ("dummy.twice", "dummy.absent")],
}
GROWN_FILES = {
    "configs/dummy-model.json": json.dumps({
        "source": "https://example.org/dummy", "reduced": {}, "assumed": {},
        "deployment": "none", "builder": "dummy_builder", "smp": {},
        "width": 8}),
    "builders/dummy_builder.py":
        "def module(cfg):\n    return ('dummy', cfg['width'])\n",
    "traffic/dummy-mix.json": json.dumps({"kind": "dummy_kind", "steps": 3}),
    "drivers/dummy_kind.py": (
        "def run(run):\n"
        "    n = run.cell.traffic['steps']\n"
        "    return {'correct': True, 'attempted': n, 'failed': 0,\n"
        "            'end_to_end': {'%s': float(n)},\n"
        "            'context': {'steps': n}}\n" % RATE),
    "limits/%s.json" % GROWN_CELL: json.dumps({"limits": {}}),
    "metrics/dummy.twice.py": "def read(ctx):\n    return 2 * ctx['steps']\n",
    "metrics/dummy.absent.py": "def read(ctx):\n    return None\n",
}


def grown(data):
    """``data`` with ``GROWTH`` appended, each entry last in its list."""
    data = json.loads(json.dumps(data))
    for group, entries in GROWTH.items():
        data[group] += entries
    rate = next(m for m in data["end_to_end"] if m["name"] == RATE)
    rate["workloads"].append(GROWN_CELL)
    return data


def grow(root):
    """Make the additions in the copy of the benchmark under ``root``:
    new files, and entries appended to its ``BENCHMARK.json``."""
    for name, text in GROWN_FILES.items():
        with open(os.path.join(root, "benchmark", name), "w") as f:
            f.write(text)
    data = grown(manifest_data(root))
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(data, f)
    return root


def grown_root(tmp_path):
    """A copy of ``BENCHMARK.json`` and ``benchmark/`` as committed, grown."""
    root = str(tmp_path / "root")
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__", "testdata"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    return grow(root)


def entry_listing(manifest, metric, cells):
    """The per-layer entry ``metric`` without its ``workloads``, found by
    name wherever it stands, once it is seen to list ``cells`` (others too,
    maybe: ``test_benchmark_manifest.py`` holds the rule on those) and each
    of them to report it."""
    entry = dict(manifest._entry("per_layer", metric))
    assert set(cells) <= set(entry.pop("workloads")), metric
    for cell in cells:
        assert metric in {m["name"] for m in manifest.cell(cell).per_layer()}
    return entry


def _rewrite(path, **changes):
    with open(path) as f:
        data = json.load(f)
    data.update(changes)
    with open(path, "w") as f:
        json.dump(data, f)


def tiny_root(tmp_path):
    """A copy of ``BENCHMARK.json`` and ``benchmark/`` under ``tmp_path``
    with every configuration and mix cut to a tiny size."""
    root = str(tmp_path / "root")
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__", "testdata"))
    data = manifest_data()
    if not any(w["name"] == SERVE_CELL for w in data["workloads"]):
        for group, entries in SERVE_ENTRIES.items():
            data[group] = data[group] + entries
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(data, f)
    bench = os.path.join(root, "benchmark")
    for name in os.listdir(os.path.join(bench, "configs")):
        path = os.path.join(bench, "configs", name)
        with open(path) as f:
            cfg = json.load(f)
        if cfg.get("model_type") == "gpt2":
            changes = dict(TINY_MODEL)
            changes["smp"] = {k: v for k, v in cfg["smp"].items()
                              if k != "fused_step_donation"}
            if "serve" in cfg:
                changes["serve"] = {"max_slots": 4}
            _rewrite(path, **changes)
        elif cfg.get("model_type") == "gpt_neox":
            _rewrite(path, **TINY_NEOX, smp={
                k: v for k, v in cfg["smp"].items()
                if k != "fused_step_donation"} | {"microbatches": 4})
    for name in os.listdir(os.path.join(bench, "traffic")):
        path = os.path.join(bench, "traffic", name)
        with open(path) as f:
            kind = json.load(f)["kind"]
        _rewrite(path, **(TINY_TRAIN_MIX if kind == "train_steps"
                          else TINY_SERVE_MIX))
        limits = (TINY_TRAIN_LIMITS if kind == "train_steps"
                  else TINY_SERVE_LIMITS)
        for cell in data["workloads"]:
            if cell["traffic"] + ".json" == name:
                # The tp-capable stack rounds a little more at this size.
                own = dict(limits, first_grad_norm_gap=0.01) \
                    if cell["chips"] == 4 else limits
                with open(os.path.join(
                        bench, "limits", cell["name"] + ".json"), "w") as f:
                    json.dump({"limits": own}, f)
    return root


def cpu_run(root, cell_name, seed=7, seconds=1.5, trace=0):
    """The rest of a run once the look for a chip is skipped: a ``Run`` on
    as many (virtual) CPU devices as the cell has chips."""
    import jax

    from benchmark import harness, loader

    cell = loader.Manifest(root).cell(cell_name)
    run = harness.Run(cell, seed, seconds, trace,
                      jax.devices()[:cell.chips], root)
    return cell, run


def cells_of_kind(kind):
    """Names of the cells (the committed ones and the tests' serving cell)
    whose mix is of ``kind``."""
    out = []
    cells = manifest_data()["workloads"]
    if not any(w["name"] == SERVE_CELL for w in cells):
        cells = cells + SERVE_ENTRIES["workloads"]
    for cell in cells:
        with open(os.path.join(ROOT, "benchmark", "traffic",
                               cell["traffic"] + ".json")) as f:
            if json.load(f)["kind"] == kind:
                out.append(cell["name"])
    return out
