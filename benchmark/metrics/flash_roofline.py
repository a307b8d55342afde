"""The flash attention kernels' share of their roofline over a training
step: the least time the chip could take for the causal attention of one
step (``benchmark/flops.py``: forward + backward FLOPs over the bf16 peak,
or the q/k/v/o/gradient bytes over the HBM peak, whichever is larger)
divided by the kernels' measured time per step."""


def read(ctx):
    trace = ctx["trace"]
    seconds = trace.matching("smp_flash_")
    if not seconds or not ctx.get("steps"):
        return None
    peaks = ctx["peaks"]
    # Every chip runs the kernels on its share of the step's attention.
    chips = len(ctx["run"].devices)
    least = max(
        ctx["attention_flops_per_step"] / peaks["bf16_flops_per_s"],
        ctx["attention_bytes_per_step"] / peaks["hbm_bytes_per_s"]) / chips
    return 100.0 * least / (seconds / ctx["steps"])
