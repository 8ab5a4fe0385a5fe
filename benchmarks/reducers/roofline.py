"""``roofline``: a kernel's share of its roofline, in percent.

The least time the chip could take for the kernel's calls (the larger
of operations over peak FLOP/s and bytes over peak bytes/s, from
``benchmarks/flops.py`` and ``benchmarks/peaks.py``) over the device
time of the operations that match ``pattern``. The calls are where the
runner put them: ``calls`` (a list of keyword arguments of the counting
function, one per call made in the traced stretch), or ``each`` (the
names of the keyword arguments of the calls that every operation
matching ``count_pattern`` stands for: one backward ``dq`` kernel in
the trace stands for one forward and one backward call).
"""

from .. import flops
from ..layer_metrics import fill
from ..peaks import peaks_for


def read(spec, evidence):
    reduction = evidence.get("trace")
    if reduction is None:
        return None
    peaks = peaks_for(evidence["device_kind"])
    least = seconds = 0.0
    for kernel in spec.get("kernels", [spec]):
        pattern = fill(kernel["pattern"], evidence)
        if pattern is None:
            return None
        ops = reduction.matching(pattern, kernel.get("on", "label"))
        if not ops:
            return None
        count = getattr(flops, kernel["counts"])
        if "each" in kernel:
            calls = [evidence.get(name) for name in kernel["each"]]
            if None in calls:
                return None
            counted = reduction.matching(
                fill(kernel.get("count_pattern", kernel["pattern"]),
                     evidence), kernel.get("on", "label"))
            least += len(counted) * sum(
                flops.roofline_seconds(count(**c), peaks)[0] for c in calls)
        else:
            calls = evidence.get(kernel["calls"])
            if not calls:
                return None
            least += sum(flops.roofline_seconds(count(**c), peaks)[0]
                         for c in calls)
        seconds += sum(op.own for op in ops)
    return 100.0 * least / seconds if seconds else None
