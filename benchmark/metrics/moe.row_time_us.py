"""Device time of one routed row, in microseconds: the traced window's
seconds in the routed experts' own work (``_experts.py``) divided by the
rows the router sent to the experts held here in that window (the
program's ``smp_moe_local_assignments`` summed over its steps): forward and
backward of one token on one held expert, all expert layers alike."""

from benchmark import loader

_experts = loader.load_sibling(__file__, "_experts")


def read(ctx):
    rows = (ctx.get("moe") or {}).get("rows_in_window")
    seconds = _experts.seconds(ctx)
    return 1e6 * seconds / rows if seconds and rows else None
