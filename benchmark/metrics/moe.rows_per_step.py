"""Token-expert assignments that landed on the experts held here, a step,
over the window (all expert layers, all microbatches): the program's
``smp_moe_local_assignments`` summed over the window's steps by the driver
and divided by them. The routed experts' work follows this number, so a
rate is only comparable between runs that read about the same rows."""


def read(ctx):
    moe = ctx.get("moe")
    return moe.get("rows_per_step") if moe else None
