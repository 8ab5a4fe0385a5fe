"""Op registry.

Reference analog: ``op_builder/`` (4.5k LoC) — per-op builders with
``is_compatible()`` probes, JIT/AOT compilation, and per-accelerator routing
(``op_builder/builder.py:117``, ``accelerator.create_op_builder``).

TPU-native: kernels are Pallas (compiled through XLA, no separate toolchain),
so "building" disappears; what remains is the *routing and probing* surface:
every op has a reference jnp implementation (always correct, runs anywhere —
the analog of the reference's torch fallbacks) and may have a Pallas
implementation used when the platform supports it. ``get_op(name)`` returns
the best available callable; ``HDS_DISABLE_PALLAS=1`` forces references
(the analog of ``DS_BUILD_OPS=0``).

A dispatcher that gives way to its reference on a platform that HAS the
kernel (a shape the tiles cannot cover, ``HDS_DISABLE_PALLAS``) calls
:func:`note_fallback` first: the substitution is counted per
``(op, reason)``, warned once per pair, and read back through
:func:`fallback_report` — a measurement that ran the reference must be
able to say so.
"""

import os

from ..utils.logging import logger

_REGISTRY = {}
#: (op, reason) -> times a dispatcher returned the reference instead of
#: the kernel. Dispatchers run while JAX traces, so this counts traced
#: call sites, not executions of a compiled program.
_FALLBACKS = {}


def kernel_name(kernel):
    """``name=`` and ``metadata=`` of a ``pl.pallas_call``: the custom
    call is then ``%hds_<kernel>.N`` in the compiled program and its
    HLO text, which a device trace names the operation by, carries
    ``kernel_metadata={"hds_kernel":"<kernel>"}``: a trace reader finds
    the kernel whatever its operands' shapes."""
    return {"name": f"hds_{kernel}", "metadata": {"hds_kernel": kernel}}


def note_fallback(op, reason, detail=""):
    """Record that ``op`` is about to return its jnp reference for
    ``reason``; warns the first time each ``(op, reason)`` is seen."""
    key = (op, reason)
    seen = _FALLBACKS.get(key, 0)
    _FALLBACKS[key] = seen + 1
    if not seen:
        logger.warning(
            "%s: running the jnp reference instead of the Pallas kernel "
            "(%s%s). Counted in ops.fallback_report(); further "
            "occurrences of this pair are not logged.", op, reason,
            f"; {detail}" if detail else "")


def fallback_report():
    """``{op: {reason: count}}`` of reference substitutions so far;
    empty when every dispatched op ran its kernel."""
    out = {}
    for (op, reason), n in sorted(_FALLBACKS.items()):
        out.setdefault(op, {})[reason] = n
    return out


def reset_fallback_report():
    _FALLBACKS.clear()


class OpImpl:
    def __init__(self, name, reference_fn, pallas_fn=None, is_compatible=None):
        self.name = name
        self.reference_fn = reference_fn
        self.pallas_fn = pallas_fn
        self._is_compatible = is_compatible

    def _native(self):
        """Does the platform have a Pallas path for this op at all?"""
        if self.pallas_fn is None:
            return False
        if self._is_compatible is not None and not self._is_compatible():
            return False
        from ..platform import get_platform
        return get_platform().supports_pallas()

    def compatible(self):
        """Can the pallas path run natively here? (reference:
        OpBuilder.is_compatible)"""
        return self._native() and \
            os.environ.get("HDS_DISABLE_PALLAS") != "1"

    def best(self):
        if self.compatible():
            return self.pallas_fn
        if self._native():
            note_fallback(self.name, "HDS_DISABLE_PALLAS=1")
        return self.reference_fn


def register_op(name, reference_fn, pallas_fn=None, is_compatible=None):
    _REGISTRY[name] = OpImpl(name, reference_fn, pallas_fn, is_compatible)
    return _REGISTRY[name]


def get_op(name):
    """Best implementation of ``name`` for the current platform."""
    _ensure_loaded()
    if name not in _REGISTRY:
        raise KeyError(f"unknown op '{name}'; have {sorted(_REGISTRY)}")
    return _REGISTRY[name].best()


def get_op_impl(name) -> OpImpl:
    _ensure_loaded()
    return _REGISTRY[name]


def op_report():
    """Reference: bin/ds_report — op-by-op compatibility table."""
    _ensure_loaded()
    lines = [f"{'op':<24} {'pallas':<8} {'active'}"]
    for name, impl in sorted(_REGISTRY.items()):
        native = impl.compatible()
        lines.append(f"{name:<24} {'yes' if impl.pallas_fn else 'no':<8} "
                     f"{'pallas' if native else 'reference'}")
    return "\n".join(lines)


_loaded = False


def _ensure_loaded():
    global _loaded
    if _loaded:
        return
    _loaded = True
    from . import (evoformer_attention, flash_attention,  # noqa: F401
                   fp_quantizer, gated_delta, grouped_gemm, kv_write,
                   latent_attention, paged_attention,
                   quantized_matmul, quantizer, rms_norm, rope)


__all__ = ["register_op", "get_op", "get_op_impl", "op_report",
           "note_fallback", "fallback_report", "reset_fallback_report"]
