"""Published per-chip peaks, keyed by jax's ``device_kind``.

Copied from ``bench.CHIP_PEAKS`` (source: Google Cloud documentation,
"TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM, 16 GB).  A device that is
not in the table is an error, not a default."""

CHIP_PEAKS = {
    "TPU v5 lite": {"tflops_bf16": 197.0, "hbm_gbs": 819.0,
                    "hbm_bytes": 16 * 2 ** 30},
}


def peaks_for(device_kind):
    if device_kind not in CHIP_PEAKS:
        raise ValueError(
            f"no published peaks for device_kind {device_kind!r} in "
            f"benchmarks/lib/peaks.py ({sorted(CHIP_PEAKS)}): add its "
            f"row with a source")
    return CHIP_PEAKS[device_kind]
