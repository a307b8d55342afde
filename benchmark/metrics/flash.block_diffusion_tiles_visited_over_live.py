"""Tiles the flash kernels step into under the block-diffusion mask over
tiles that hold a live pair, summed over the three passes: the program's
gauges ``smp_flash_tiles_visited{pass}`` and ``smp_flash_tiles_live{pass}``
(set while the calls are traced; per head). 1.0 is a kernel that skips
every dead tile; one that visits every tile and masks reads about 3.5 at
8,192 data tokens. A program without the gauges gives nothing."""

from benchmark import loader

_scopes = loader.load_sibling(__file__, "_scopes")


def read(ctx):
    total = {
        name: sum(s["value"] for s in _scopes._series(name))
        for name in ("smp_flash_tiles_visited", "smp_flash_tiles_live")}
    if not total["smp_flash_tiles_live"]:
        return None
    return total["smp_flash_tiles_visited"] / total["smp_flash_tiles_live"]
