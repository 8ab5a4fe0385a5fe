"""``roofline_counts``: a kernel's share of its roofline, in percent,
with the counting functions looked up in the module the metric file
names (``counts_module``, under ``benchmarks/``) instead of
``benchmarks/flops.py`` alone, which ``roofline`` reads.

The least time the chip could take for the calls the runner listed
(``calls``: the evidence key that holds one keyword-argument dict per
call made in the traced stretch) over the device time of the operations
that match ``pattern``. ``kernels`` may list several kernels of one
layer; one that the traced stretch never ran (no operation matches) is
left out with its calls, and the metric is left out when none ran.
"""

import importlib

from ..flops import roofline_seconds
from ..layer_metrics import fill
from ..peaks import peaks_for


def read(spec, evidence):
    reduction = evidence.get("trace")
    if reduction is None:
        return None
    module = spec["counts_module"]
    if not module.replace("_", "").isalnum():
        raise ValueError(f"bad counts_module {module!r}")
    counts = importlib.import_module(f"benchmarks.{module}")
    peaks = peaks_for(evidence["device_kind"])
    least = seconds = 0.0
    for kernel in spec.get("kernels", [spec]):
        pattern = fill(kernel["pattern"], evidence)
        if pattern is None:
            return None
        ops = reduction.matching(pattern, kernel.get("on", "label"))
        calls = evidence.get(kernel["calls"])
        if not ops or not calls:
            continue
        count = getattr(counts, kernel["counts"])
        least += sum(roofline_seconds(count(**c), peaks)[0] for c in calls)
        seconds += sum(op.own for op in ops)
    return 100.0 * least / seconds if seconds else None
