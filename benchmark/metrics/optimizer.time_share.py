"""Share of device 0's busy time in the optimizer update fused into the
step: self time of the traced ops under the ``smp/optimizer/update``
scope (``phase == "optimizer"`` in ``hlo_audit.op_index``)."""

from benchmark import loader

_scopes = loader.load_sibling(__file__, "_scopes")


def read(ctx):
    return _scopes.phase_share(ctx, "optimizer")
