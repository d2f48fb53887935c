"""Published peaks of the chips the benchmark may run on, by ``device_kind``.

One table, with its source; a device that is not in it is an error, never
a default.
"""

#: Google Cloud documentation, "TPU v5e" system architecture page: one
#: v5e chip does 197 TFLOP/s in bf16 and has 16 GB of HBM2e at 819 GB/s.
PEAKS = {
    "TPU v5 lite": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peak(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peak for device_kind {device_kind!r} in "
            f"benchmark/peaks.py; add it with its source") from None
