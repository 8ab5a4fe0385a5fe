"""Platform abstraction.

TPU-native re-design of the reference's accelerator abstraction
(``accelerator/abstract_accelerator.py:10`` ``DeepSpeedAccelerator``, ~70
abstract methods). JAX already abstracts devices, streams and RNG, so the
surface here is deliberately small: we keep only what expresses *capability*
differences between platforms (memory stats, host-offload support, collective
transport, profiler, op-registry routing). Everything stream/event/graph
shaped in the reference dissolves into XLA.
"""

from abc import ABC, abstractmethod


class UnknownPeakError(LookupError):
    """The platform has no published peak for what was asked (an
    unknown ``device_kind``, a dtype with no published figure, or the
    host CPU). A missing peak is an error, never a default of 0."""


class Platform(ABC):
    """A hardware platform seen by the framework."""

    #: short name, e.g. "tpu", "cpu"
    name: str = None

    # ------------------------------------------------------------------ #
    # Device topology
    # ------------------------------------------------------------------ #
    @abstractmethod
    def device_count(self):
        """Total addressable devices across all hosts."""

    @abstractmethod
    def local_device_count(self):
        """Devices attached to this host."""

    @abstractmethod
    def process_count(self):
        """Number of controller processes (hosts)."""

    @abstractmethod
    def process_index(self):
        """This controller's index."""

    def is_available(self):
        return self.device_count() > 0

    # ------------------------------------------------------------------ #
    # Capability probes (reference: communication_backend_name(),
    # supports_* predicates on DeepSpeedAccelerator)
    # ------------------------------------------------------------------ #
    @abstractmethod
    def communication_backend_name(self):
        """Transport used for collectives ('xla-ici-dcn', 'xla-host', ...)."""

    def supports_bf16_matmul(self):
        return True

    def supports_host_offload(self):
        """Can arrays live in host memory and be streamed to device?"""
        return False

    def supports_pallas(self):
        """Can Pallas kernels compile natively (not interpret mode)?"""
        return False

    # ------------------------------------------------------------------ #
    # Memory (reference: memory_stats / see_memory_usage surface)
    # ------------------------------------------------------------------ #
    @abstractmethod
    def memory_stats(self, device=None):
        """dict with at least bytes_in_use / bytes_limit when known."""

    def total_memory(self, device=None):
        return self.memory_stats(device).get("bytes_limit", 0)

    def available_memory(self, device=None):
        stats = self.memory_stats(device)
        return stats.get("bytes_limit", 0) - stats.get("bytes_in_use", 0)

    # ------------------------------------------------------------------ #
    # Hardware peak numbers (used by the flops profiler / MFU reporting)
    # ------------------------------------------------------------------ #
    @abstractmethod
    def peak_tflops(self, dtype="bfloat16"):
        """Published peak matmul TFLOP/s per device for ``dtype``;
        raises :class:`UnknownPeakError` when there is none."""

    @abstractmethod
    def peak_hbm_gbps(self):
        """Published peak HBM GB/s per device; raises
        :class:`UnknownPeakError` when there is none."""

    # ------------------------------------------------------------------ #
    # Profiler (reference: range_push/pop NVTX + torch profiler hooks)
    # ------------------------------------------------------------------ #
    @abstractmethod
    def profiler_start(self, log_dir):
        ...

    @abstractmethod
    def profiler_stop(self):
        ...

    def annotate(self, name, **attrs):
        """Context manager adding a named range, with ``attrs`` as its
        arguments, to profiler traces."""
        import contextlib
        return contextlib.nullcontext()

    # ------------------------------------------------------------------ #
    # Synchronisation
    # ------------------------------------------------------------------ #
    def synchronize(self, tree=None):
        """Block until async dispatch for ``tree`` (or all work) completes."""
        import jax
        if tree is not None:
            jax.block_until_ready(tree)
        else:
            import jax.numpy as jnp
            jnp.zeros(()).block_until_ready()
