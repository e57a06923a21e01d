"""Published peaks of each accelerator, keyed by JAX's ``device_kind``.

A device whose kind is not in the table is an error, never a default:
a roofline share or a utilisation against the wrong peak is a wrong
number.
"""

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s
    # int8, 16 GB of HBM at 819 GB/s per chip.  JAX names the chip
    # "TPU v5 lite".
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "cloud.google.com/tpu/docs/v5e (TPU v5e: 197 TFLOP/s bf16, 819 GB/s HBM)",
    },
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"bench/peaks.py has {sorted(PEAKS)}") from None
