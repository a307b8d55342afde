"""Rows routed to held experts for each position of the two-copy stream
and expert layer: ``moe.rows_per_step`` divided by the step's positions
(twice its data tokens: the noisy and the clean copy) and the layers the
configuration runs, all routed. 1.0 is the deployment's own load (8 of 128
experts a token, 16 held). Half of the noisy copy holds the mask id and
chooses one set of experts a layer, so this moves in steps of a quarter
when one more or one fewer of that set is held: what the rate follows from
seed to seed (``PERF.md`` section 6, PR 37)."""

from benchmark import loader

_rows = loader.load_sibling(__file__, "moe.rows_per_step")


def read(ctx):
    rows = _rows.read(ctx)
    if not rows:
        return None
    layers = len(ctx["cell"].config["layer_types"])
    return rows / (2 * ctx["tokens_per_step"]) / layers
