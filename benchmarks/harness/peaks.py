"""Published peaks per accelerator, keyed by ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture):
16 GB of HBM at 819 GB/s and 197 TFLOP/s in bf16 per chip.  No metric
reads these yet; they are here for the roofline shares that come later.
A kind that is not in the table is an error, never a default.
"""

PEAKS = {
    "TPU v5 lite": {"hbm_bytes": 16e9, "hbm_bytes_per_s": 819e9,
                    "bf16_flops_per_s": 197e12},
}


def peak(kind: str) -> dict:
    if kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {kind!r} "
                       f"(known: {sorted(PEAKS)})")
    return PEAKS[kind]
