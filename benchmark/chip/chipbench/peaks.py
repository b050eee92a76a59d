"""Published peaks of the chips the benchmark may run on, keyed by JAX's
``device_kind``.  A device that is not here is an error, not a default.

Source: Google Cloud documentation, "TPU v5e" system architecture: 197
TFLOP/s dense bf16, 16 GB of HBM2e at 819 GB/s a chip, 1600 Gbit/s of
inter-chip interconnect (the table ``bench_transformer.py::_PEAK_TFLOPS``
holds the same FLOP/s figure for this kind).
"""

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9, "ici_bits_per_s": 1600e9},
}

# bytes an element of a traffic mix's ``dtype`` takes
DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def peaks_for(device_kind):
    if device_kind not in PEAKS:
        raise SystemExit(
            "chip benchmark: no peak FLOP/s and bytes/s on record for device "
            "kind %r; add it to benchmark/chip/chipbench/peaks.py with its "
            "source" % device_kind)
    return PEAKS[device_kind]


def least_time(flops, nbytes, peak):
    """``(seconds, roof)``: the least time a chip with these peaks could
    take for ``flops`` operations over ``nbytes`` of HBM traffic, and which
    of the two bounds it.  A kernel's roofline share is this over its
    time."""
    t_flops = flops / peak["bf16_flops"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    return (max(t_flops, t_bytes),
            "bf16 FLOP/s" if t_flops >= t_bytes else "HBM bytes/s")
