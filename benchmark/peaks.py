"""Published peaks of one chip, keyed by JAX's ``device_kind``.

A kind that is not in the table is an error, never a default.
"""

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16,
    # 393 TOP/s int8, 16 GB of HBM at 819 GB/s
    "TPU v5 lite": {"flops": 197e12, "bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}") from None


def least_seconds(flops: float, nbytes: float, device_kind: str) -> float:
    """The least time the chip could take for the work: the larger of
    operations over peak FLOP/s and bytes over peak bytes/s."""
    p = peaks(device_kind)
    return max(flops / p["flops"], nbytes / p["bytes_per_s"])
