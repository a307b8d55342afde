"""Share of device 0's busy time adding each microbatch's gradients into
the accumulator (``smp/step/accumulate``)."""

from benchmark import loader

_tree = loader.load_sibling(__file__, "_tree")


def read(ctx):
    return _tree.share(ctx, _tree.under("smp/step/accumulate"))
