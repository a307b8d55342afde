"""Share of device 0's busy time in the per-head RMSNorms of the queries
and keys: ops under the scope ``smp/attn/qk_norm``, forward, recomputed and
transposed. A program whose attention has no such norm gives nothing."""

from benchmark import loader

_moe = loader.load_sibling(__file__, "_moe")


def read(ctx):
    return _moe.share_of_busy(ctx, ("smp/attn/qk_norm",))
