"""Share of device 0's busy time in the norms of a layer's branch outputs
(``branch_layernorm``: ``x + N(f(N(x)))``): ops under
``smp/layer/branch_norm``, two a layer a pass, forward, recomputed and
transposed. A program whose layers have no such norm, or from before the
scope, gives nothing."""

from benchmark import loader

_tree = loader.load_sibling(__file__, "_tree")


def read(ctx):
    return _tree.share(ctx, _tree.under("smp/layer/branch_norm"))
