"""The hyper-connections' share of their roofline over the traced window:
the least time the chip could take to move what a step's connections must
move (``xing4_flops.mhc_bytes_per_step``: for each sub-layer the streams
read for the coefficients and the pre mix, read again with the sub-layer's
output and written mixed, and in the backward pass their gradients and
they themselves read for the coefficients' gradients: 7 n + 5 [tokens,
hidden] bf16 tensors a sub-layer, no recomputation counted; the arithmetic
is a few dozen operations an element and the coefficient product 24
columns, far under the memory's time) over the HBM peak, divided by the
time of the ops under ``smp/mhc/*`` a step. What recomputes the forward,
reads the streams once more, or keeps a float32 copy reads lower; nothing
can read over 100."""

from benchmark import loader

_moe = loader.load_sibling(__file__, "_moe")


def read(ctx):
    seconds = _moe.seconds_under(ctx, ("smp/mhc/",))
    steps = ctx.get("steps")
    if not seconds or not steps:
        return None
    from benchmark import xing4_flops

    cell = ctx["cell"]
    mix = cell.traffic
    moved = xing4_flops.mhc_bytes_per_step(
        cell.config, mix["batch"], mix["seq"])
    least = moved / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / (seconds / steps)
