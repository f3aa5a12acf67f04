"""The chip's published peaks, and the rule that only a TPU is measured.

Copied from ``bench.py::PEAKS`` / ``device_info`` so that no later change to
the program can move the yardstick. A device that is not in the table is an
error, never a default.
"""

PEAKS = {
    "TPU v5 lite": {
        "hbm_bytes_per_s": 819.0e9, "bf16_flop_per_s": 197.0e12,
        "source": "Google Cloud documentation, 'TPU v5e': 819 GB/s HBM "
                  "bandwidth, 197 TFLOP/s bf16 per chip",
    },
}


def peaks_for(device_kind):
    if device_kind not in PEAKS:
        raise KeyError(f"no peak figures for device_kind {device_kind!r}: add "
                       f"a sourced row to benchmark/peaks.py")
    return PEAKS[device_kind]


def device_info(chips, rehearsal=False):
    """What JAX reports; raises unless it is a TPU with ``chips`` devices
    (a number from another backend is never printed under a device name)."""
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if rehearsal:
        return info
    if info["platform"] != "tpu" or info["count"] < chips:
        raise SystemExit(f"the benchmark measures {chips} TPU chip(s); JAX "
                         f"found {info}: not measuring")
    peaks_for(info["kind"])
    info["count"] = chips
    return info
