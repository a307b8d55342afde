"""Idle slots of the pipeline schedule baked into the compiled step, as a
share of its slots: the executor's own ``smp_pipeline_bubble_fraction``
gauge. A stage waiting for its peer sits in a collective and counts as
busy, so ``device.idle_share.train`` cannot see this. Nothing without a
pipeline."""

from benchmark import loader

_scopes = loader.load_sibling(__file__, "_scopes")


def read(ctx):
    fraction = _scopes.bubble_fraction()
    return None if fraction is None else 100.0 * fraction
