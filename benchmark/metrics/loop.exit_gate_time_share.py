"""Share of device 0's busy time in a looped model's exit gate: ops under
``smp/head/exit_gate`` (the gate's product on each pass's state, and
``nn/exit_gate.exit_gated_loss``: the exit distribution, its entropy, the
weighted sum of the passes' losses), forward, recomputed and transposed. A
program with no gate, or from before the scope, gives nothing."""

from benchmark import loader

_tree = loader.load_sibling(__file__, "_tree")


def read(ctx):
    return _tree.share(ctx, _tree.under("smp/head/exit_gate"))
