"""The routed experts' grouped matrix products' share of their roofline
over the traced window: the least time the chip could take for the rows
the router actually sent here (the program's counter; ``laguna_flops.py``:
18 x hidden x expert width FLOPs a row over the bf16 peak, or the rows'
and the held matrices' bytes over the HBM peak, whichever is larger)
divided by the time of the ops under ``smp/moe/experts``."""

from benchmark import loader

_moe = loader.load_sibling(__file__, "_moe")


def read(ctx):
    moe = ctx.get("moe")
    seconds = _moe.seconds_under(ctx, ("smp/moe/experts",))
    if not moe or not seconds:
        return None
    peaks = ctx["peaks"]
    least = max(moe["grouped_flops_in_window"] / peaks["bf16_flops_per_s"],
                moe["grouped_bytes_in_window"] / peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds
