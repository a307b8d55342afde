"""Share of device 0's busy time in the layers' dense feed-forward
(``smp/mlp/dense``), forward, recomputed and transposed. An expert layer's
shared expert is the expert layer's (``smp/moe/shared``)."""

from benchmark import loader

_tree = loader.load_sibling(__file__, "_tree")


def read(ctx):
    return _tree.share(ctx, _tree.under("smp/mlp/"))
