"""Readers shared by the metrics of the layers a patterned stack brings
(expert layers, window attention, a leading dense layer): device 0's self
seconds of the traced ops by the ``jax.named_scope``s round them, from the
program's op index of the compiled step (``hlo_audit.op_index``: an
instruction's ``scope``, and ``scopes`` where they are nested), joined to
the trace by instruction name as ``_scopes.py`` joins the phases. The
program writes ``smp/moe/{route, dispatch, experts, shared, combine}``,
``smp/attn/{full, window}`` and ``smp/layer/<kind>``. A program without
the index or without these scopes gives no seconds and the metric is left
out."""

from benchmark import loader

_scopes = loader.load_sibling(__file__, "_scopes")


def seconds_under(ctx, scopes, named=""):
    """Self seconds of the ops that sit under a scope starting with one of
    ``scopes`` and whose instruction name holds ``named``; ``None`` without
    an index."""
    index = _scopes.step_index()
    if index is None:
        return None
    total = 0.0
    for name, seconds in ctx["trace"]["op_self_s"].items():
        rec = index.get(name) or {}
        round_it = rec.get("scopes") or (rec.get("scope") or "",)
        if named in name and any(s.startswith(scopes) for s in round_it):
            total += seconds
    return total


def share_of_busy(ctx, scopes, named=""):
    seconds = seconds_under(ctx, scopes, named)
    busy = ctx["trace"]["busy_s_by_device"][0]
    return 100.0 * seconds / busy if seconds and busy else None
