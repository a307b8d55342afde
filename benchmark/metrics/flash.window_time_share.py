"""Share of device 0's busy time in the flash kernels of the window
layers: ops named ``smp_flash_*`` under the scope ``smp/attn/window``."""

from benchmark import loader

_moe = loader.load_sibling(__file__, "_moe")


def read(ctx):
    return _moe.share_of_busy(ctx, ("smp/attn/window",), named="smp_flash_")
