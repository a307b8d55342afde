"""Share of device 0's busy time in the flash kernels of the layers under
the block-diffusion mask: ops named ``smp_flash_*`` under the scope
``smp/attn/block_diffusion`` (``flash.window_time_share`` for that scope).
A program with no such scope gives nothing."""

from benchmark import loader

_moe = loader.load_sibling(__file__, "_moe")


def read(ctx):
    return _moe.share_of_busy(
        ctx, ("smp/attn/block_diffusion",), named="smp_flash_")
