"""Readers shared by metrics that are split by the end-to-end metric they
move (``device.idle_share.train`` / ``.serve`` ...)."""


def idle_share(ctx):
    trace = ctx["trace"]
    return 100.0 * (1.0 - trace["busy_s_by_device"][0] / trace["window_s"])


def hbm_peak_gb(ctx):
    return ctx["run"].memory_peak_bytes / 1e9
