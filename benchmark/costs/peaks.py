"""Published peaks of one chip, keyed by ``device_kind`` as jax reports
it.  A device that is not in the table is an error, not a default."""

PEAKS = {
    # Google Cloud documentation, "TPU v5e" (system architecture page):
    # 197 TFLOP/s bf16, 16 GB of HBM2e at 819 GB/s per chip.  The same
    # three numbers as the program's bench.DEVICE_PEAKS.
    "TPU v5 lite": {"bf16_flops": 1.97e14, "hbm_bytes_per_s": 8.19e11, "hbm_bytes": 16e9},
}


def peak(device_kind: str):
    if device_kind not in PEAKS:
        raise KeyError(f"no published peak for device kind {device_kind!r}: "
                       f"add it to benchmark/costs/peaks.py with its source")
    return PEAKS[device_kind]
