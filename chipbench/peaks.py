"""Published peaks of the chips the benchmark knows, keyed by jax's
``device_kind``.  A device that is not here is an error, never a default.

Source: Google Cloud documentation, "TPU v5e" system architecture page
(cloud.google.com/tpu/docs/v5e): 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e
at 819 GB/s per chip.  (Copy of ``observability/xla_stats.py:PEAK_TABLE``'s
v5e row; the yardstick keeps its own so that it does not move with the
program.)
"""

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}


def peak(device_kind, key):
    if device_kind not in PEAKS:
        raise KeyError("no published peaks for device kind %r (known: %s)"
                       % (device_kind, sorted(PEAKS)))
    return PEAKS[device_kind][key]
