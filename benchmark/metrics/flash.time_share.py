"""Share of device 0's busy time spent in the flash attention kernels
(``smp_flash_fwd``, ``smp_flash_bwd_dq``, ``smp_flash_bwd_dkv``)."""


def read(ctx):
    trace = ctx["trace"]
    seconds = trace.matching("smp_flash_")
    if not seconds:
        return None
    return 100.0 * seconds / trace["busy_s_by_device"][0]
