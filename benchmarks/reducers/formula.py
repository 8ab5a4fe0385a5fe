"""``formula``: a quantity worked out from the window's totals and the
configuration's sizes by a function kept in ``benchmarks/flops.py``
(model FLOP/s utilization)."""

from .. import flops
from ..peaks import peaks_for


def read(spec, evidence):
    if spec["formula"] != "train_mfu":
        raise ValueError(f"unknown formula {spec['formula']!r}")
    rate = evidence.get("counters", {}).get("tokens_per_s_per_chip")
    arch = evidence.get("arch")
    if rate is None or arch is None:
        return None
    per_token = flops.train_flops_per_token(arch, evidence["seq_len"])
    peak = peaks_for(evidence["device_kind"])["bf16_tflops"] * 1e12
    return 100.0 * per_token * rate / peak
