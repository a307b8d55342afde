"""Layers a looped stack runs in one optimizer step: the program's gauge
``smp_loop_layer_passes`` (passes x layers a forward, set while the stack
is built) times the microbatches of the configuration's ``smp`` dict. 56
in ``ouro-2.6b.train-8k-looped-1chip`` (4 passes of 7 layers, 2
microbatches); a stack that runs once sets no such gauge and gives
nothing."""

from benchmark import loader

_scopes = loader.load_sibling(__file__, "_scopes")


def read(ctx):
    series = _scopes._series("smp_loop_layer_passes")
    if len(series) != 1:
        return None
    return series[0]["value"] * ctx["cell"].config["smp"]["microbatches"]
