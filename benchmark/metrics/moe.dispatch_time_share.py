"""Share of device 0's busy time the expert layers spend round their
matrix products: the router and top-k (``smp/moe/route``), the sort by
expert and the gathers of token rows (``smp/moe/dispatch``), the
scatter-adds and the sum with the shared expert (``smp/moe/combine``)."""

from benchmark import loader

_moe = loader.load_sibling(__file__, "_moe")


def read(ctx):
    return _moe.share_of_busy(
        ctx, ("smp/moe/route", "smp/moe/dispatch", "smp/moe/combine"))
