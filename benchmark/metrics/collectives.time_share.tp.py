"""Share of the traced window device 0 spent in collectives over the
``tp`` mesh axis: ``collectives.time_share`` split by the axis the
compiled step's op index gives each collective's replica groups. Nothing
on one chip."""

from benchmark import loader

_scopes = loader.load_sibling(__file__, "_scopes")


def read(ctx):
    return _scopes.axis_share(ctx, "tp")
