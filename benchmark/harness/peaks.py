"""Published peaks of the chips the benchmark may run on, by `device_kind`.

A device that is not in the table is an error, never a default.
"""

PEAKS = {
    # Google Cloud documentation, "TPU v5e" (system architecture): 16 GB of
    # HBM2e at 819 GB/s, 197 TFLOP/s bf16, 393 TOP/s int8 per chip
    "TPU v5 lite": {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12,
                    "int8_ops": 393e12, "hbm_bytes": 16e9,
                    "source": "cloud.google.com/tpu/docs/v5e"},
}


def peak(device_kind: str, what: str) -> float:
    if device_kind not in PEAKS:
        raise KeyError("no published peaks for device kind %r (known: %s)"
                       % (device_kind, ", ".join(sorted(PEAKS))))
    return PEAKS[device_kind][what]


def hbm_bytes_per_s(device_kind: str) -> float:
    return peak(device_kind, "hbm_bytes_per_s")
