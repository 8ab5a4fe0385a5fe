"""Platform detection and singleton access.

Reference analog: ``accelerator/real_accelerator.py:51`` ``get_accelerator()``
— env override first (there ``DS_ACCELERATOR``, here ``HDS_PLATFORM``), then
auto-detection. Detection here simply asks JAX for its default backend, since
the PJRT plugin system already did the probing.
"""

import os

from .abstract import Platform, UnknownPeakError
from .tpu import CPUPlatform, TPUPlatform

_PLATFORMS = {
    "tpu": TPUPlatform,
    "cpu": CPUPlatform,
}

_platform = None


def get_platform() -> Platform:
    global _platform
    if _platform is None:
        override = os.environ.get("HDS_PLATFORM")
        if override:
            if override not in _PLATFORMS:
                raise ValueError(
                    f"HDS_PLATFORM={override!r} not in {sorted(_PLATFORMS)}")
            _platform = _PLATFORMS[override]()
        else:
            import jax
            backend = jax.default_backend()
            # the CPU backend gets the host platform (what tier-1 runs
            # on); any accelerator backend gets the TPU platform
            _platform = CPUPlatform() if backend == "cpu" else TPUPlatform()
    return _platform


def device_row():
    """The device as JAX reports it — the three fields every printed
    measurement row carries, so a number can never be read without the
    device it came from."""
    import jax
    devices = jax.devices()
    return {"platform": devices[0].platform,
            "device_kind": devices[0].device_kind,
            "device_count": len(devices)}


def require_chip(tool):
    """For entry points that exist to measure: the device row, or exit
    non-zero saying why when JAX has no TPU. A measurement path never
    falls back to the CPU (the library itself does, for tests: see
    :func:`get_platform`)."""
    row = device_row()
    if row["platform"] != "tpu":
        raise SystemExit(
            f"{tool}: JAX found no TPU (platform {row['platform']!r}, "
            f"{row['device_count']} device); this tool measures the chip "
            "and a CPU run is never recorded under a device metric's name")
    return row


def set_platform(name_or_platform):
    """Force the platform (tests)."""
    global _platform
    if isinstance(name_or_platform, Platform):
        _platform = name_or_platform
    else:
        _platform = _PLATFORMS[name_or_platform]()
    return _platform


__all__ = ["Platform", "TPUPlatform", "CPUPlatform", "UnknownPeakError",
           "get_platform", "set_platform", "device_row", "require_chip"]
