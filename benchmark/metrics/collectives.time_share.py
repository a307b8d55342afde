"""Share of the traced window device 0 spent in collective ops (all-reduce,
all-gather, collective-permute, all-to-all, reduce-scatter), by self time.
Nothing on one chip."""


def read(ctx):
    if len(ctx["run"].devices) == 1:
        return None
    trace = ctx["trace"]
    return 100.0 * trace.collective_s() / trace["window_s"]
