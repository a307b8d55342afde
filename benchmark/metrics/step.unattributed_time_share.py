"""Share of device 0's busy time the op index puts down to no pass: ops of
``phase == "other"`` (no metadata, schedule glue, RNG) and op names the
index does not hold. With the forward, backward, recompute and optimizer
shares it sums to 100; it bounds how wrong those four can be."""

from benchmark import loader

_scopes = loader.load_sibling(__file__, "_scopes")


def read(ctx):
    return _scopes.phase_share(ctx, "other")
