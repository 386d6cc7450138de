"""The chips' published peaks: ONE table, keyed by ``device_kind`` as JAX
reports it.  A device that is not here is an error, never a default.

The program has a table of its own (``mxtpu.perf.DEVICE_PEAKS``); this is
the benchmark's copy, because the program may change and the yardstick
may not.
"""

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16,
    # 393 TOP/s int8, 16 GB HBM2e at 819 GB/s per chip
    "TPU v5 lite": {"flops_bf16": 197e12, "ops_int8": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}


def of(device_kind):
    if device_kind not in PEAKS:
        raise KeyError("no published peaks for device kind %r; add a row "
                       "with its source to peaks.py" % (device_kind,))
    return PEAKS[device_kind]
