"""Share of device 0's busy time in the routed experts' own work: the
grouped gated FFN of each chunk of sorted rows, forward, and in the
written-out backward its recomputation, its transposes and the fp32 sums
of the weight gradients (``_experts.py``: the ops under the scope
``smp/moe/experts`` and the grouped products' own kernels). The router, the
sort, the gathers and the scatter-adds are ``moe.dispatch_time_share``'s."""

from benchmark import loader

_experts = loader.load_sibling(__file__, "_experts")


def read(ctx):
    seconds = _experts.seconds(ctx)
    busy = ctx["trace"]["busy_s_by_device"][0]
    return 100.0 * seconds / busy if seconds and busy else None
