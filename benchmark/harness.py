"""What every run shares: the look for a chip, the compile cache, the
window's compile counter, the traced window, and the result line.

A driver (``drivers/<kind>.py``) builds the system under test, warms it up,
runs the measured window through ``Run.window()`` and hands back what it
measured; this module turns that into the one JSON line the contract fixes.
"""

import contextlib
import json
import os
import shutil
import sys
import time

from benchmark import loader, peaks

T_PROCESS_START = time.perf_counter()


def say(what, **fields):
    """An information line (anything but the last line of the output)."""
    print(json.dumps({"info": what, **fields}), flush=True)


def find_chips(needed):
    """The first ``needed`` TPU devices, or exit non-zero with no result."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"benchmark: found no TPU (JAX reports platform "
                 f"{devices[0].platform!r}); a run means nothing off the chip.")
    if len(devices) < needed:
        sys.exit(f"benchmark: the cell needs {needed} chip(s), JAX finds "
                 f"{len(devices)}.")
    peaks.peaks_for(devices[0].device_kind)      # unknown device: an error
    return devices[:needed]


def configure_compile_cache(root):
    """JAX's persistent cache at ``JAX_COMPILATION_CACHE_DIR`` if set, else
    at the fixed path ``<checkout>/.jax_cache``."""
    import jax

    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    cache_dir = env_dir or os.path.join(root, ".jax_cache")
    if not env_dir:
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cache_dir


class CompileCounter:
    """Counts XLA compilations (cache hits included: a hit still builds an
    executable) from JAX's own monitoring events."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        from jax import monitoring

        self.count = 0
        self.seconds = 0.0
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self.EVENT:
            self.count += 1
            self.seconds += duration


class Run:
    """One run of one cell: arguments, devices, and the measured window."""

    def __init__(self, cell, seed, seconds, trace, devices, root):
        self.cell = cell
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.devices = devices
        self.root = root
        self.compiles = CompileCounter()
        self.trace_dir = os.path.join(root, ".bench_trace", cell.name)
        self.compiles_in_window = None
        self.window_s = None
        self.setup_s = None
        self.memory_peak_bytes = None
        # Set by ``control.py`` alone: the lower precision whose readings
        # the driver then reports beside the program's.
        self.control = None
        self.laps = {}
        self._lap_t = T_PROCESS_START

    def lap(self, name):
        """Seconds since the last lap (or the process's start) under
        ``name``: the make-up of ``setup_s``, printed, not a metric."""
        now = time.perf_counter()
        self.laps[name] = self.laps.get(name, 0.0) + now - self._lap_t
        self._lap_t = now

    @contextlib.contextmanager
    def window(self):
        """The measured window. Set-up ends where it starts; with
        ``--trace 1`` the profiler runs over exactly this span. The driver
        must end its work (``block_until_ready``) before leaving it."""
        import jax

        if self.trace:
            shutil.rmtree(self.trace_dir, ignore_errors=True)
            # Device ops and the benchmark's own spans only: the Python
            # tracer would add an event per call and slow the host.
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 1
            options.enable_hlo_proto = False
            jax.profiler.start_trace(self.trace_dir, profiler_options=options)
        whole = self.span("window")
        whole.__enter__()
        compiles0 = self.compiles.count
        self.setup_s = time.perf_counter() - T_PROCESS_START
        t0 = time.perf_counter()
        try:
            yield t0
        finally:
            self.window_s = time.perf_counter() - t0
            self.compiles_in_window = self.compiles.count - compiles0
            whole.__exit__(None, None, None)
            if self.trace:
                jax.profiler.stop_trace()
            self.memory_peak_bytes = max(
                (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                for d in self.devices)

    def span(self, name):
        """A host span on the profiler's clock (``bench.<name>``)."""
        import jax

        if not self.trace:
            return contextlib.nullcontext()
        return jax.profiler.TraceAnnotation("bench." + name)


def reduce_trace(run):
    from benchmark import trace_reduce

    path = trace_reduce.find_xplane(run.trace_dir)
    reduced = trace_reduce.reduce(path, n_devices=len(run.devices))
    shutil.rmtree(run.trace_dir, ignore_errors=True)
    return reduced


def result_line(run, outcome):
    """The last line. ``outcome`` is the driver's: ``correct``,
    ``attempted``, ``failed``, ``end_to_end`` values, and ``context`` for
    the per-layer readers."""
    cell = run.cell
    correct = bool(outcome["correct"]) and run.compiles_in_window == 0
    device = {
        "platform": run.devices[0].platform,
        "kind": run.devices[0].device_kind,
        "count": len(run.devices),
        "memory_peak_bytes": run.memory_peak_bytes,
    }
    line = {"correct": correct, "attempted": outcome["attempted"],
            "failed": outcome["failed"], "metrics": {}, "device": device}
    if not run.trace:
        values = dict(outcome["end_to_end"], setup_s=run.setup_s)
        for m in cell.end_to_end():
            line["metrics"][m["name"]] = {
                "value": values[m["name"]], "unit": m["unit"]}
        return line

    reduced = reduce_trace(run)
    device["busy_s"] = reduced["busy_s"]
    device["window_s"] = reduced["window_s"]
    line["breakdown"] = {
        "device_ops": reduced["top_ops"][:10],
        "idle_gaps": reduced["top_gaps"][:10],
    }
    context = dict(outcome["context"], trace=reduced, run=run, cell=cell,
                   peaks=peaks.peaks_for(run.devices[0].device_kind))
    for m in cell.per_layer():
        value = cell.metric_reader(m["name"])(context)
        if value is not None:
            line["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}
    return line


def main(argv=None):
    import argparse

    parser = argparse.ArgumentParser(description="Run one benchmark cell.")
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)

    manifest = loader.Manifest()
    cell = manifest.cell(args.workload)
    # The program under test: without it there is nothing to measure.
    if not os.path.isdir(
            os.path.join(manifest.root, "smdistributed_modelparallel_tpu")):
        sys.exit("benchmark: the program under test is not in this checkout.")
    devices = find_chips(cell.chips)
    cache_dir = configure_compile_cache(manifest.root)
    say("environment", workload=cell.name, seed=args.seed,
        seconds=args.seconds, trace=args.trace, compile_cache_dir=cache_dir,
        device_kind=devices[0].device_kind, chips=len(devices))

    run = Run(cell, args.seed, args.seconds, args.trace, devices,
              manifest.root)
    run.lap("import_and_find_chips")
    outcome = cell.driver().run(run)
    say("window", setup_s=run.setup_s, setup_laps=run.laps, window_s=run.window_s,
        compiles_in_window=run.compiles_in_window,
        compiles_total=run.compiles.count,
        compile_seconds_total=run.compiles.seconds)
    print(json.dumps(result_line(run, outcome)), flush=True)
    return 0
