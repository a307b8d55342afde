"""Share of device 0's busy time in attention (``smp/attn/*``): the q/k/v
projections, the q/k norms, rotary, the flash kernels or the plain path and
the output projection, of every layer, forward, recomputed and
transposed."""

from benchmark import loader

_tree = loader.load_sibling(__file__, "_tree")


def read(ctx):
    return _tree.share(ctx, _tree.under("smp/attn/"))
