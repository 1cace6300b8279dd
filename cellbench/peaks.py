"""The one table of device peaks, keyed by ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" system architecture page:
197 TFLOP/s in bf16 per chip, 16 GB of HBM2e at 819 GB/s. A float32
matmul at JAX's default precision runs as bf16 passes on the MXU, so
the bf16 figure is the ceiling for the programs measured here too. A
device that is not in the table is an error, never a default.
"""

PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "bytes_per_s": 819e9,
                    "hbm_bytes": 16e9,
                    "source": "cloud.google.com/tpu/docs/v5e"},
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no peaks for device kind {device_kind!r}: add a row with "
            f"its source to cellbench/peaks.py") from None
