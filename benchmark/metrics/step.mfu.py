"""Model FLOP/s utilization of the training step: required FLOPs per step
(``benchmark/flops.py``: matmuls 6 N with the head, causal attention, no
recomputation) x steps per second / (chips x the chip's bf16 peak)."""


def read(ctx):
    if "flops_per_step" not in ctx:
        return None
    flops_per_token = ctx["flops_per_step"] / ctx["tokens_per_step"]
    achieved = flops_per_token * ctx["tokens_per_s_per_chip"]
    return 100.0 * achieved / ctx["peaks"]["bf16_flops_per_s"]
