"""The one table of hardware peaks, keyed by ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture):
197 TFLOP/s bf16 and 393 TOP/s int8 per chip, 16 GB HBM2e at 819 GB/s,
1,600 Gbit/s inter-chip interconnect.  A device that is not in the
table is an error, not a default.
"""

PEAKS = {
    "TPU v5 lite": {
        "flops_bf16": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "ici_bits_per_s": 1600e9,
        "source": "cloud.google.com/tpu/docs/v5e (TPU v5e, per chip)",
    },
}


def for_kind(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(
            f"no peaks for device_kind {device_kind!r}; known: "
            f"{sorted(PEAKS)} — add the chip with its source to "
            f"benchmark/lib/peaks.py")
    return PEAKS[device_kind]
