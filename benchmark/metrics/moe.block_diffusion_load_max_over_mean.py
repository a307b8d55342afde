"""``moe.expert_load_max_over_mean`` for a cell trained by block
diffusion: the largest held expert's load over the mean, averaged over the
expert layers. The one held expert a layer that the mask token chooses
takes a quarter of the positions beside its own share (about 4.7 at 16
held; 3.8 where a second held expert shares them): the cell's own entry
until that reader's list takes it (``PERF.md`` section 7)."""

from benchmark import loader

_load = loader.load_sibling(__file__, "moe.expert_load_max_over_mean")


def read(ctx):
    return _load.read(ctx)
