"""Median host time of one ``engine.step()`` that did work (a decode step,
a prefill chunk, or both, with their readbacks)."""

import statistics


def read(ctx):
    if not ctx.get("tick_s"):
        return None
    return 1e3 * statistics.median(ctx["tick_s"])
