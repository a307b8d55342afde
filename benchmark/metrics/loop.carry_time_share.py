"""Share of device 0's busy time in a looped stack's own work: ops under
``smp/model/loop`` and outside ``smp/model/stack`` (the norm after every
pass, the carried state, the passes' states and the layers' residuals
stacked for the head and the backward pass, the shared weights' gradients
added up over the passes), forward, recomputed and transposed. A stack
that runs once, or a program from before the scope, gives nothing."""

from benchmark import loader

_tree = loader.load_sibling(__file__, "_tree")


def _outside_the_layers(record):
    return sum(seconds for path, seconds in record["tree"].items()
               if "smp/model/loop" in path and "smp/model/stack" not in path)


def read(ctx):
    return _tree.share(ctx, _outside_the_layers)
