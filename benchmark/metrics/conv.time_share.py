"""Share of device 0's busy time in the short-convolution mixers: ops under
any ``smp/conv/*`` scope (the input projection to three streams, the
gate-conv-gate core, the output projection) of every layer that has one,
forward, recomputed and transposed. A program with no such mixer, or from
before the scopes, gives nothing."""

from benchmark import loader

_moe = loader.load_sibling(__file__, "_moe")


def read(ctx):
    return _moe.share_of_busy(ctx, ("smp/conv/",))
