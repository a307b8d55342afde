"""Median host time of one ``train_step(...)`` + ``optimizer.step()``
dispatch: until the calls return, not until the device is done."""

import statistics


def read(ctx):
    if not ctx.get("dispatch_s"):
        return None
    return 1e3 * statistics.median(ctx["dispatch_s"])
