"""Share of device 0's busy time in the expert layers: ops under any
``smp/moe/*`` scope (router, sort and gather, the grouped products, the
shared expert, scatter-add and sum)."""

from benchmark import loader

_moe = loader.load_sibling(__file__, "_moe")


def read(ctx):
    return _moe.share_of_busy(ctx, ("smp/moe/",))
