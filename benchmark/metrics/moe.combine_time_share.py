"""Share of device 0's busy time the expert layers spend summing the routed
rows back to their tokens (``smp/moe/combine``): each chunk's rows added to
the per-token fp32 sum, forward (the experts' output) and in the written-out
backward (the tokens' gradient), by the ``smp_row_scatter_add`` kernel or,
where it stands aside and in programs from before it, by XLA's scatter-add
fusions; with them the sum with the shared expert and the final cast. It is
a part of ``moe.dispatch_time_share``."""

from benchmark import loader

_moe = loader.load_sibling(__file__, "_moe")


def read(ctx):
    return _moe.share_of_busy(ctx, ("smp/moe/combine",))
