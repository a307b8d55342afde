"""Share of device 0's busy time in the leading dense layer (attention and
its whole-width gated MLP): ops under ``smp/layer/lead_dense``."""

from benchmark import loader

_moe = loader.load_sibling(__file__, "_moe")


def read(ctx):
    return _moe.share_of_busy(ctx, ("smp/layer/lead_dense",))
