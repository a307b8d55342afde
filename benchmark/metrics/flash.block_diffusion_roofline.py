"""The flash kernels' share of their roofline under the block-diffusion
mask: ``flash_roofline``'s arithmetic (the least time for the step's
attention, FLOPs over the bf16 peak or bytes over the HBM peak, whichever
is larger, over the kernels' measured time a step) on what the driver of
such a cell hands over: the live pairs of the mask counted from its
definition and the two-copy stream's bytes (``benchmark/sdar_flops.py``).
Pairs in tiles that are visited and masked are not counted, so this may
never read over 100. Nothing where no kernel ran under the scope
``smp/attn/block_diffusion``."""

from benchmark import loader

_moe = loader.load_sibling(__file__, "_moe")
_roofline = loader.load_sibling(__file__, "flash_roofline")


def read(ctx):
    if not _moe.seconds_under(
            ctx, ("smp/attn/block_diffusion",), named="smp_flash_"):
        return None
    return _roofline.read(ctx)
