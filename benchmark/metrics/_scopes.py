"""What the traced window's device time was spent on, by joining the
reduced trace (``ctx["trace"]["op_self_s"]``: device 0's self seconds by
HLO instruction name) with the program's own op index of the compiled
step (``hlo_audit.op_index``: instruction name -> phase, scope, and for a
collective its mesh axis), and the step engine's host phases
(``smp_host_phase_seconds``).

The join is by instruction name. The window also runs a few tiny programs
(``jit__mean``, the batch slice) whose instruction names can collide with
the step's (``copy.1``); they are charged to whatever the step's
instruction of that name is. They take microseconds a step, and a name the
index does not hold counts as unattributed, which bounds the error from
the other side.

A program without the index (``SMP_HLO_AUDIT=off``, or a tree from before
the index existed) gives ``None`` everywhere, and the metric is left out.
"""

import collections

from benchmark.trace_reduce import COLLECTIVES

PHASES = ("forward", "backward", "recompute", "optimizer", "other")
# The step engine's phases round the executable call, and the optimizer's
# call that follows it: host time of a step in which the chip may idle.
HOST_PHASES = ("step/prepare", "step/lookup", "step/place", "step/install",
               "step/bookkeeping", "optimizer/step")


def step_index():
    """The op index of the one ``step*`` program the run compiled
    (``step``, ``step_pipeline_1f1b``, ...), or ``None``."""
    from smdistributed_modelparallel_tpu.utils import hlo_audit

    if not hasattr(hlo_audit, "op_index"):
        return None
    names = [n for n in hlo_audit.audits if n.startswith("step")]
    if len(names) != 1:
        return None
    return hlo_audit.op_index(names[0]) or None


def phase_share(ctx, phase):
    """Share (%) of device 0's busy time in ops of ``phase``; an op the
    index does not hold is ``other``. The five shares sum to 100."""
    index = step_index()
    if index is None:
        return None
    seconds = collections.Counter()
    for name, s in ctx["trace"]["op_self_s"].items():
        rec = index.get(name)
        seconds[rec["phase"] if rec else "other"] += s
    busy = sum(seconds.values())
    return 100.0 * seconds[phase] / busy if busy else None


def collective_seconds_by_axis(ctx):
    """``{axis label: seconds}`` over the ops ``trace.collective_s()``
    sums (so the labels add up to it); ``unindexed`` for a collective the
    index does not hold. ``None`` on one chip."""
    if len(ctx["run"].devices) == 1:
        return None
    index = step_index()
    if index is None:
        return None
    seconds = collections.Counter()
    for name, s in ctx["trace"]["op_self_s"].items():
        if name.startswith(COLLECTIVES):
            seconds[index.get(name, {}).get("axis", "unindexed")] += s
    return seconds


def axis_share(ctx, axis):
    """Share (%) of the traced window device 0 spent in collectives over
    mesh axis ``axis``."""
    seconds = collective_seconds_by_axis(ctx)
    if seconds is None:
        return None
    return 100.0 * seconds[axis] / ctx["trace"]["window_s"]


def _series(metric):
    """The series of one of the program's metrics: live, or, once
    ``smp.shutdown()`` has dropped the registry (the driver frees the
    program before the readers run), from the report it kept of the
    session it closed."""
    from smdistributed_modelparallel_tpu.utils.telemetry import telemetry

    for report in (telemetry.report(),
                   getattr(telemetry, "closed_report", None) or {}):
        if metric in report.get("metrics", {}):
            return report["metrics"][metric]["series"]
    return []


def host_phase_p50_s():
    """``{phase: median seconds}`` of the program's host phases, from its
    ``smp_host_phase_seconds`` histogram (every call of the process: the
    window's steps outnumber set-up's four). ``{}`` without it."""
    from smdistributed_modelparallel_tpu.utils.telemetry import (
        quantile_from_counts,
    )

    return {
        s["labels"]["phase"]: quantile_from_counts(
            s["buckets"], s["counts"], 0.5)
        for s in _series("smp_host_phase_seconds") if s["count"]
    }


def bubble_fraction():
    """The pipeline executor's own count of idle schedule slots / slots
    (``smp_pipeline_bubble_fraction``), or ``None`` with no pipeline."""
    series = _series("smp_pipeline_bubble_fraction")
    return series[0]["value"] if len(series) == 1 else None
