"""Profiling (reference: ``deepspeed/profiling/``) + the HLO
async-overlap auditor (``hlo_audit`` — no reference analog; it proves or
refutes collective/compute overlap in the compiled program)."""

from .flops_profiler import (FlopsProfiler, analyze_fn,  # noqa: F401
                             count_params, get_model_profile)
from .hlo_audit import (AuditReport, audit_compiled,  # noqa: F401
                        audit_hlo_text, audit_jit)
