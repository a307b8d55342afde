"""The comparisons that decide ``correct``. Every number compared is
printed beside its limit, in every run (``harness.say("compared", ...)``).

Limits are data: ``benchmark/limits/<cell>.json`` holds ``{number: limit}``
for the cell, set from readings on the chip (``PERF.md`` gives them). A
number with no limit in the file fails the run: nothing passes unjudged.
"""

import json
import math
import os
import statistics


def load_limits(bench_dir, cell_name):
    with open(os.path.join(bench_dir, "limits", cell_name + ".json")) as f:
        return json.load(f)["limits"]


def worst_leaf_gap(got, want):
    """The widest gap between two per-leaf norms: |got - want| of a leaf
    against the reference's norm of that leaf or of the median leaf,
    whichever is larger (some leaves' norms are all but zero). Returns
    ``(gap, leaf name)``."""
    floor = statistics.median(want.values())
    worst, where = 0.0, None
    for name, ref in want.items():
        gap = abs(got[name] - ref) / max(ref, floor)
        if not gap <= worst:        # NaN counts as the worst
            worst, where = gap, name
    return worst, where


def train_numbers(program, reference):
    """Numbers compared in a training cell. ``program`` and ``reference``
    hold ``losses`` (list), ``first_grad`` and ``change`` (per-leaf norms
    under Hugging Face names)."""
    numbers = {}
    for i, (a, b) in enumerate(zip(program["losses"], reference["losses"])):
        numbers[f"loss_gap_step{i + 1}"] = abs(a - b)
    numbers["first_grad_norm_gap"], g_leaf = worst_leaf_gap(
        program["first_grad"], reference["first_grad"])
    numbers["param_change_norm_gap"], c_leaf = worst_leaf_gap(
        program["change"], reference["change"])
    return numbers, {"first_grad_norm_gap": g_leaf,
                     "param_change_norm_gap": c_leaf}


def judge(numbers, limits):
    """``(correct, rows)``: each row is ``{number, value, limit, ok}``. A
    value passes when it is finite and at most its limit."""
    rows, correct = [], True
    for name, value in numbers.items():
        limit = limits.get(name)
        ok = (limit is not None and isinstance(value, (int, float))
              and math.isfinite(value) and value <= limit)
        rows.append({"number": name, "value": value, "limit": limit,
                     "ok": ok})
        correct = correct and ok
    return correct, rows
