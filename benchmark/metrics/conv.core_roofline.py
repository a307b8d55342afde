"""The short-convolution mixers' gate-conv-gate stage's share of its
roofline over the traced window: the least time the chip could take to
move what the stage must move (``lfm2_flops.conv_core_bytes_per_step``:
forward reads the three streams and writes the gated output, backward reads
them and the output's gradient and writes three gradients; eleven [tokens,
hidden] bf16 tensors a mixer a step, no recomputation counted; its
arithmetic is a dozen operations an element, far under the memory's time)
over the HBM peak, divided by the time of the ops under ``smp/conv/core``
a step. What recomputes the forward, or moves a tensor twice, reads lower;
nothing can read over 100."""

from benchmark import loader

_moe = loader.load_sibling(__file__, "_moe")


def read(ctx):
    seconds = _moe.seconds_under(ctx, ("smp/conv/core",))
    steps = ctx.get("steps")
    if not seconds or not steps:
        return None
    from benchmark import lfm2_flops

    cell = ctx["cell"]
    mix = cell.traffic
    moved = lfm2_flops.conv_core_bytes_per_step(
        cell.config, mix["batch"], mix["seq"])
    least = moved / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / (seconds / steps)
