"""Share of device 0's busy time in the short-convolution mixers'
gate-conv-gate stage: ops under ``smp/conv/core`` (the first gate, the
causal depthwise convolution over the taps, the second gate; element-wise
work between two projections), forward, recomputed and transposed."""

from benchmark import loader

_moe = loader.load_sibling(__file__, "_moe")


def read(ctx):
    return _moe.share_of_busy(ctx, ("smp/conv/core",))
