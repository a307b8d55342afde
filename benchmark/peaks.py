"""Published peaks of the chips the benchmark has been sized for.

Keyed by the exact ``device_kind`` string JAX reports. A device that is not
here is an error, not a default, and no environment variable overrides a
peak: a share of a peak means one thing on every run.
"""

PEAKS = {
    # Google Cloud documentation, "TPU v5e" (system architecture): per chip
    # 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s.
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "cloud.google.com/tpu/docs/v5e (TPU v5e, per chip)",
    },
}


class UnknownDevice(Exception):
    pass


def peaks_for(device_kind):
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"no published peaks for device_kind {device_kind!r}; add the "
            f"exact string to benchmark/peaks.py with its source "
            f"(known: {sorted(PEAKS)})") from None
