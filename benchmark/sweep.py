#!/usr/bin/env python3
"""Find a serving cell's knee once, on the chip: the highest offered rate
the system sustains. One process, one engine, the mix's own lengths; each
rate runs the cell's open loop for ``--seconds`` with ``rate_rps``
overridden, and prints what it read. The knee is the highest rate at which
the backlog does not grow (requests unfinished when arrivals stop stay
under ``max_slots``) and at least 90% of the requests due had finished
inside the window; ``traffic/<mix>.json`` then gets 0.8 of it, as a number
(``PERF.md`` records the sweep).

    python3 benchmark/sweep.py --workload <cell> --rates 2,4,6,8,10 --seconds 20 --seed 1
"""

import json
import math
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import harness, loader, stats, traffic  # noqa: E402


def main(argv=None):
    import argparse

    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--rates", required=True)
    parser.add_argument("--seconds", type=float, default=20.0)
    parser.add_argument("--seed", type=int, default=1)
    args = parser.parse_args(argv)

    manifest = loader.Manifest()
    cell = manifest.cell(args.workload)
    devices = harness.find_chips(cell.chips)
    harness.configure_compile_cache(manifest.root)
    run = harness.Run(cell, args.seed, args.seconds, 0, devices,
                      manifest.root)
    driver = cell.driver()
    smp, engine = driver.build_engine(run)
    cfg = cell.config
    engine.run([smp.serving.ServeRequest("warmup", list(range(40)), 3)],
               timeout_s=1200)
    engine.drain_dirty()
    import jax

    for n in range(1, cell.traffic["output"]["max"] + 1):
        jax.random.key_data(jax.random.split(jax.random.key(0), n))
    for k, rate in enumerate(float(r) for r in args.rates.split(",")):
        mix = dict(cell.traffic, rate_rps=rate)
        reqs = traffic.requests(mix, args.seed + k, args.seconds,
                                cfg["vocab_size"])
        for r in reqs:
            r["id"] = f"rate{k}-{r['id']}"
        compiles0 = run.compiles.count
        loop = driver.serve(run, engine, smp, reqs, args.seconds,
                            mix["drain_limit_s"])
        e2e, failed, detail = driver.latency_metrics(
            reqs, loop, args.seconds)
        in_window = sum(
            1 for r in reqs if loop["token_t"][r["id"]]
            and len(loop["token_t"][r["id"]]) == r["max_new_tokens"]
            and loop["token_t"][r["id"]][-1] <= args.seconds)
        finite = [x for x in detail["ttft_ms"] if math.isfinite(x)]
        print(json.dumps({
            "info": "sweep", "rate_rps": rate, "requests": len(reqs),
            "finished_in_window_share": in_window / len(reqs),
            "unfinished_at_window_end": len(reqs) - in_window,
            "failed": failed, **e2e,
            "ttft_p50_ms": stats.median(finite) if finite else None,
            "tick_ms_median": 1e3 * stats.median(loop["tick_s"]),
            "compiles": run.compiles.count - compiles0,
        }), flush=True)
        # The next rate starts on an empty engine: finish what this one
        # left (above the knee that is a backlog).
        engine.run(timeout_s=1200)
        engine.drain_dirty()
    engine.close()
    smp.shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(main())
