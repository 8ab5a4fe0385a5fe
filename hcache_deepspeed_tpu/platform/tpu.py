"""TPU platform implementation.

The TPU analog of the reference's ``accelerator/cuda_accelerator.py``: it maps
the small Platform surface onto JAX/XLA. Collectives ride ICI within a slice
and DCN across slices — both are reached through ``jax.lax`` collectives over
mesh axes, so ``communication_backend_name`` names the transport rather than a
library (the reference returns 'nccl' and routes through torch.distributed).
"""

import contextlib

import jax

from .abstract import Platform, UnknownPeakError

# Published per-chip peaks, keyed by the ``device_kind`` JAX reports (one
# JAX device is one chip from v4 on; jax/_src/pallas/mosaic/tpu_info.py
# lists the kind strings). Source: Google Cloud TPU documentation, the
# system-architecture page of each generation ("TPU v4", "TPU v5e",
# "TPU v5p", "TPU v6e"): dense-matmul TFLOP/s (TOP/s for int8) by dtype,
# and HBM GB/s. Only published figures: TPUs have no published fp32
# matmul peak, so asking for one raises rather than guessing.
_V5E = ({"bfloat16": 197.0, "int8": 393.0}, 819.0)
_V5P = ({"bfloat16": 459.0, "int8": 918.0}, 2765.0)
_V6E = ({"bfloat16": 918.0, "int8": 1836.0}, 1640.0)
_PEAKS = {
    "TPU v4": ({"bfloat16": 275.0, "int8": 275.0}, 1200.0),
    "TPU v5 lite": _V5E,
    "TPU v5e": _V5E,
    "TPU v5": _V5P,
    "TPU v5p": _V5P,
    "TPU v6 lite": _V6E,
    "TPU v6e": _V6E,
}


class TPUPlatform(Platform):
    name = "tpu"

    def device_count(self):
        return jax.device_count()

    def local_device_count(self):
        return jax.local_device_count()

    def process_count(self):
        return jax.process_count()

    def process_index(self):
        return jax.process_index()

    def communication_backend_name(self):
        return "xla-ici-dcn"

    def supports_host_offload(self):
        return True

    def supports_pallas(self):
        return True

    def device_kind(self):
        devs = jax.devices()
        return devs[0].device_kind if devs else "unknown"

    def _peaks(self):
        kind = self.device_kind()
        if kind not in _PEAKS:
            raise UnknownPeakError(
                f"no published peak for device_kind {kind!r}; known: "
                f"{sorted(_PEAKS)} (add it to platform/tpu.py with its "
                f"source)")
        return _PEAKS[kind]

    def peak_tflops(self, dtype="bfloat16"):
        by_dtype, _ = self._peaks()
        if dtype not in by_dtype:
            raise UnknownPeakError(
                f"no published {dtype} matmul peak for "
                f"{self.device_kind()!r}; have {sorted(by_dtype)}")
        return by_dtype[dtype]

    def peak_hbm_gbps(self):
        return self._peaks()[1]

    def memory_stats(self, device=None):
        device = device or jax.local_devices()[0]
        stats = device.memory_stats() or {}
        return {
            "bytes_in_use": stats.get("bytes_in_use", 0),
            "bytes_limit": stats.get("bytes_limit", 0),
            "peak_bytes_in_use": stats.get("peak_bytes_in_use", 0),
        }

    def profiler_start(self, log_dir):
        jax.profiler.start_trace(log_dir)

    def profiler_stop(self):
        jax.profiler.stop_trace()

    def annotate(self, name, **attrs):
        return jax.profiler.TraceAnnotation(name, **attrs)


class CPUPlatform(TPUPlatform):
    """Host-only platform (CI, unit tests on a forced multi-device CPU mesh).

    Reference analog: ``accelerator/cpu_accelerator.py`` — used so the whole
    runtime can execute without accelerator hardware.
    """
    name = "cpu"

    def communication_backend_name(self):
        return "xla-host"

    def supports_host_offload(self):
        return False  # arrays already live in host memory

    def supports_pallas(self):
        return False  # interpret mode only

    def _peaks(self):
        raise UnknownPeakError(
            "the host CPU platform has no published peak; MFU and "
            "roofline shares are device metrics and need a TPU")

    def memory_stats(self, device=None):
        try:
            import psutil
            vm = psutil.virtual_memory()
            return {
                "bytes_in_use": vm.used,
                "bytes_limit": vm.total,
                "peak_bytes_in_use": 0,
            }
        except Exception:
            return {"bytes_in_use": 0, "bytes_limit": 0, "peak_bytes_in_use": 0}

    def profiler_start(self, log_dir):
        with contextlib.suppress(Exception):
            jax.profiler.start_trace(log_dir)

    def profiler_stop(self):
        with contextlib.suppress(Exception):
            jax.profiler.stop_trace()
