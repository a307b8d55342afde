"""Share of device 0's busy time in code the step function wrote itself:
instructions whose innermost scope is ``smp/step/user`` (a loss written out
after ``model(ids)``), forward and transposed. Nothing where the program
writes no such scope."""

from benchmark import loader

_tree = loader.load_sibling(__file__, "_tree")


def read(ctx):
    return _tree.share(ctx, lambda record: record["user_only_s"])
