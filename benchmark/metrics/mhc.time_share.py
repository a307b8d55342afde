"""Share of device 0's busy time in the hyper-connections: ops under any
``smp/mhc/*`` scope (a sub-layer's coefficients, their Sinkhorn rounds, the
pre mix and the post / residual mix) of every layer whose residual path has
more than one stream, forward, recomputed and transposed. A program with
one stream, or from before the scopes, gives nothing."""

from benchmark import loader

_moe = loader.load_sibling(__file__, "_moe")


def read(ctx):
    return _moe.share_of_busy(ctx, ("smp/mhc/",))
