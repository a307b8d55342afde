#!/usr/bin/env python3
"""The control of a cell's ``correct``: the plain reference put in the
program's place and computed in the nearest precision below the one the
configuration states (float8 for bfloat16). It has to come out as *not*
correct; the limits in ``limits/<cell>.json`` are set between what sound
runs read and what this reads (``PERF.md`` has both).

    python3 benchmark/control.py --workload <cell> --seed <n> --seconds <s>

Runs the cell as ``run.py`` does (same set-up, a short window at the
cell's own load) and prints one more line, ``{"info": "control", ...}``,
with the control's value of each number compared. The benchmark's own runs
never run it; ``tests/benchmark`` keeps it at a size a test run can hold.
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import harness, loader  # noqa: E402

BELOW = {"bfloat16": "float8"}


def main(argv=None):
    import argparse

    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, default=3.0)
    parser.add_argument("--precision", default=None,
                        help="default: the one below the configuration's")
    args = parser.parse_args(argv)
    manifest = loader.Manifest()
    cell = manifest.cell(args.workload)
    stated = "bfloat16" if cell.config["smp"].get("bf16") else "float32"
    devices = harness.find_chips(cell.chips)
    harness.configure_compile_cache(manifest.root)
    run = harness.Run(cell, args.seed, args.seconds, 0, devices,
                      manifest.root)
    run.control = args.precision or BELOW[stated]
    driver = cell.driver()
    if hasattr(driver, "control"):
        # The control's readings need neither the program nor a window.
        driver.control(run)
        return 0
    outcome = driver.run(run)
    print(json.dumps({"info": "program", "correct": outcome["correct"]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
