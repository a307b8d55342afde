"""Share of device 0's busy time in ops of the recompute pass: self time of
the traced ops whose record in the compiled step's op index
(``hlo_audit.op_index``) says ``phase == "recompute"``."""

from benchmark import loader

_scopes = loader.load_sibling(__file__, "_scopes")


def read(ctx):
    return _scopes.phase_share(ctx, "recompute")
