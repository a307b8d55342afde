"""Readers of device 0's time by the program's scope tree: the traced
window's self seconds by HLO instruction name (``ctx["trace"]["op_self_s"]``)
handed to the program's own join, ``hlo_audit.seconds_by_scope``, with the op
index of the one compiled ``step*`` program (``_scopes.step_index``). The
join is the program's: these readers call it and add nothing but a sum and a
division. Every share is a percentage of the seconds the join was given (its
``busy_s``), so a cell's shares and ``step.unscoped_time_share`` add up.

A program without the index (``SMP_HLO_AUDIT=off``), without the join (a tree
from before it existed) or with no seconds where a reader looks gives
``None``, and the line leaves the metric out.
"""

from benchmark import loader

_scopes = loader.load_sibling(__file__, "_scopes")


def joined(ctx):
    """``hlo_audit.seconds_by_scope`` of the traced window, or ``None``."""
    from smdistributed_modelparallel_tpu.utils import hlo_audit

    if not hasattr(hlo_audit, "seconds_by_scope"):
        return None
    index = _scopes.step_index()
    if index is None:
        return None
    return hlo_audit.seconds_by_scope(ctx["trace"]["op_self_s"], index)


def share(ctx, seconds_of):
    """Share (%) of busy time in ``seconds_of(the join's record)``;
    ``None`` where that finds nothing."""
    record = joined(ctx)
    seconds = record and seconds_of(record)
    return 100.0 * seconds / record["busy_s"] if seconds else None


def under(*scopes):
    """For ``share``: the seconds of the paths that hold a scope starting
    with one of ``scopes`` (``hlo_audit.seconds_under``)."""
    def seconds_of(record):
        from smdistributed_modelparallel_tpu.utils import hlo_audit

        return hlo_audit.seconds_under(record["tree"], *scopes)

    return seconds_of
