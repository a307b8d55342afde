"""Share of device 0's busy time in the grouped matrix products
themselves: the kernels the compiler makes of each ``lax.ragged_dot``,
which the program's op index marks ``kernel: "ragged_dot"`` whatever scope
it found for them. Nothing where the program holds none."""

from benchmark import loader

_tree = loader.load_sibling(__file__, "_tree")


def read(ctx):
    return _tree.share(
        ctx, lambda record: record["kernels"].get("ragged_dot"))
