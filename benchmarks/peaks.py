"""Published per-chip peaks, keyed by the ``device_kind`` JAX reports.

Copied from ``hcache_deepspeed_tpu/platform/tpu.py`` (PR 21) so that no
later PR can move a roofline share by editing the program. Source:
Google Cloud TPU documentation, the system-architecture page of each
generation ("TPU v4", "TPU v5e", "TPU v5p", "TPU v6e"): dense-matmul
TFLOP/s in bf16 and HBM GB/s. A kind that is not here is an error, not
a default.
"""

_V5E = {"bf16_tflops": 197.0, "hbm_gbps": 819.0, "hbm_gb": 16.0}
PEAKS = {
    "TPU v4": {"bf16_tflops": 275.0, "hbm_gbps": 1200.0, "hbm_gb": 32.0},
    "TPU v5 lite": _V5E,
    "TPU v5e": _V5E,
    "TPU v5": {"bf16_tflops": 459.0, "hbm_gbps": 2765.0, "hbm_gb": 95.0},
    "TPU v5p": {"bf16_tflops": 459.0, "hbm_gbps": 2765.0, "hbm_gb": 95.0},
    "TPU v6 lite": {"bf16_tflops": 918.0, "hbm_gbps": 1640.0,
                    "hbm_gb": 32.0},
    "TPU v6e": {"bf16_tflops": 918.0, "hbm_gbps": 1640.0, "hbm_gb": 32.0},
}


class UnknownDeviceKind(KeyError):
    pass


def peaks_for(device_kind):
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDeviceKind(
            f"no published peak for device_kind {device_kind!r}; known: "
            f"{sorted(PEAKS)} (add it to benchmarks/peaks.py with its "
            f"source)") from None
