"""Share of device 0's busy time in the expert layers of a cell trained by
block diffusion, the grouped products included: ``moe.time_share`` (ops
under any ``smp/moe/*`` scope) and the products' own kernels, which carry
no scope and which ``moe.experts_time_share`` counts by name
(``_experts.py``). The cell's own entry until the lists of the ``moe.*``
readers take it (``PERF.md`` section 7). A program without the scopes
gives nothing."""

from benchmark import loader

_moe = loader.load_sibling(__file__, "_moe")
_experts = loader.load_sibling(__file__, "_experts")


def read(ctx):
    scoped = _moe.seconds_under(ctx, ("smp/moe/",))
    experts = _experts.seconds(ctx)
    busy = ctx["trace"]["busy_s_by_device"][0]
    if not scoped or not experts or not busy:
        return None
    products = experts - _moe.seconds_under(ctx, ("smp/moe/experts",))
    return 100.0 * (scoped + products) / busy
