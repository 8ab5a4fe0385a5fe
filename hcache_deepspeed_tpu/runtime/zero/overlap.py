"""ZeRO-3 comm/compute overlap planning: prefetch depth + reduce buckets.

Reference analogs:

* ``deepspeed/runtime/zero/partitioned_param_coordinator.py`` — the
  gather **prefetch coordinator** (``stage3_prefetch_bucket_size`` sizes
  the lookahead, ``max_live_parameters`` bounds gathered params alive at
  once),
* ``deepspeed/runtime/zero/stage3.py`` ``__add_grad_to_ipg_bucket`` /
  ``__reduce_and_partition_ipg_grads`` — the **IPG reduce bucket**
  (``reduce_bucket_size``): cotangents are coalesced into a flat buffer
  and reduce-scattered as one collective per bucket.

These functions turn the reference's knobs into the *static plan* the
explicit software-pipelined scan in ``zeropp.py`` compiles against: how
many layers of gather lookahead the scan carry holds, and which
cotangent leaves share a flat reduce-scatter. Everything here is
host-side and shape-driven — no tracing, unit-testable on CPU.
"""

from dataclasses import dataclass
from typing import List, Optional, Sequence

from ...utils.logging import log_dist


@dataclass(frozen=True)
class PrefetchPlan:
    """Gather-pipeline depth for the scan-over-layers ZeRO-3 step.

    ``depth`` is in whole layers (a layer is this pipeline's minimum
    prefetch quantum): 0 = sequential gather->compute (the
    ``overlap_comm=False`` fallback), 1 = double-buffered — layer i+1's
    all-gather is issued while layer i's block compute runs, and the
    scan carry holds at most ``depth + 1`` gathered layers."""
    depth: int
    reason: str

    @property
    def live_layers(self) -> int:
        return self.depth + 1


def derive_prefetch_depth(*, overlap_comm: bool,
                          prefetch_bucket_size: int,
                          max_live_parameters: int,
                          layer_params: int,
                          outer_params: int) -> PrefetchPlan:
    """Derive the gather-pipeline depth from the stage-3 knobs.

    The scan pipeline currently implements depths 0 and 1 (the carry
    holds one in-flight gather); a ``stage3_prefetch_bucket_size`` large
    enough for any lookahead at all requests depth 1, and the
    ``max_live_parameters`` contract can veto it back to 0 — it is a
    cap, never exceeded. Raises nothing: an impossible request
    degrades with a logged reason (config-shape mismatches that should
    *fail* are rejected in ``validate_overlap_config``)."""
    if not overlap_comm:
        return PrefetchPlan(0, "overlap_comm=False: explicit "
                               "serialization fallback")
    if prefetch_bucket_size <= 0:
        return PrefetchPlan(0, "stage3_prefetch_bucket_size=0: prefetch "
                               "disabled")
    # one layer is the minimum (and currently maximum) prefetch quantum
    depth = 1
    live = outer_params + (depth + 1) * layer_params
    if live > max_live_parameters:
        plan = PrefetchPlan(
            0, f"stage3_max_live_parameters={max_live_parameters} < "
               f"outer({outer_params}) + 2 layers({2 * layer_params}): "
               f"prefetch vetoed by the live-parameter contract")
        log_dist(f"zero-overlap: {plan.reason}", ranks=[0])
        return plan
    return PrefetchPlan(
        depth, f"double-buffered gather (bucket="
               f"{prefetch_bucket_size} params >= 1 layer lookahead, "
               f"live {live} <= max_live {max_live_parameters})")


@dataclass(frozen=True)
class ReduceBucket:
    """One flat reduce-scatter: the leaf indices it coalesces and the
    total (full, pre-scatter) element count."""
    leaf_indices: tuple
    elements: int


def plan_reduce_buckets(leaf_sizes: Sequence[Optional[int]],
                        bucket_elements: int) -> List[ReduceBucket]:
    """Greedy first-fit-in-order packing of cotangent leaves into flat
    reduce-scatter buckets of at most ``bucket_elements`` elements
    (the ``reduce_bucket_size`` analog — counted in ELEMENTS like the
    reference, not bytes).

    ``leaf_sizes``: per-leaf full cotangent element counts, ``None``
    for leaves the bucketed path must skip (replicated-param leaves,
    qgZ-quantized leaves). Order is preserved — in-order packing keeps
    the flat layout deterministic so the bucketed reduce is bitwise
    reproducible. A single leaf larger than the bucket is a config
    error, detected by :func:`validate_overlap_config` before tracing.
    """
    buckets: List[ReduceBucket] = []
    cur: List[int] = []
    cur_elems = 0
    for idx, size in enumerate(leaf_sizes):
        if size is None:
            continue
        if cur and cur_elems + size > bucket_elements:
            buckets.append(ReduceBucket(tuple(cur), cur_elems))
            cur, cur_elems = [], 0
        cur.append(idx)
        cur_elems += size
    if cur:
        buckets.append(ReduceBucket(tuple(cur), cur_elems))
    return buckets


def validate_quantized_wire(*, quantized_reduce_scatter: bool,
                            error_feedback: bool, bits: int,
                            quantized_gradients: bool,
                            fused_matmul: bool = False,
                            quantized_weights: bool = False,
                            stage: Optional[int] = None) -> None:
    """Typed rejection of nonsensical quantized-wire knob combinations
    (no silent clamps — the same contract as
    :func:`validate_overlap_config`). Called both at config parse
    (``ZeroConfig``) and at engine build (``validate_zeropp``, where
    ``stage`` is known)."""
    from ..config import HDSConfigError
    if bits not in (4, 8):
        raise HDSConfigError(
            f"zero_quantized_reduce_scatter_bits={bits}: the quantized "
            f"wire ships int8 or nibble-packed int4 payloads — use 8 "
            f"or 4")
    if error_feedback and not quantized_reduce_scatter:
        raise HDSConfigError(
            "zero_reduce_scatter_error_feedback=true without "
            "zero_quantized_reduce_scatter: there is no quantization "
            "error to compensate on the full-width wire — enable "
            "zero_quantized_reduce_scatter or drop the error-feedback "
            "flag")
    if bits != 8 and not quantized_reduce_scatter:
        raise HDSConfigError(
            f"zero_quantized_reduce_scatter_bits={bits} has no effect "
            f"without zero_quantized_reduce_scatter; enable it or "
            f"leave bits at the default")
    if quantized_reduce_scatter and quantized_gradients:
        raise HDSConfigError(
            "zero_quantized_reduce_scatter and zero_quantized_gradients "
            "(qgZ) both define the gradient wire format — per-leaf qgZ "
            "and the bucketed quantized reduce-scatter are mutually "
            "exclusive; pick one")
    if fused_matmul and not quantized_weights:
        raise HDSConfigError(
            "zero_quantized_weights_fused_matmul=true without "
            "zero_quantized_weights (qwZ): there is no int8 gather "
            "payload for the block matmuls to consume")
    if stage is not None and quantized_reduce_scatter and stage != 3:
        raise HDSConfigError(
            "zero_quantized_reduce_scatter requires zero stage 3 (it "
            "rides the explicit layered reduce lane)")


def validate_overlap_config(*, reduce_bucket_elements: int = 0,
                            largest_leaf: int = 0,
                            largest_leaf_name: str = "",
                            max_live_parameters: int = 0,
                            layer_params: int = 0,
                            outer_params: int = 0,
                            knob: str = "reduce_bucket_size") -> None:
    """Build-time rejection of nonsensical overlap knobs — a clear
    error instead of the silent clamping the knobs used to get.

    * ``reduce_bucket_size`` (or ``allgather_bucket_size`` via
      ``knob``) smaller than the largest sharded leaf can never hold
      even one leaf: every "bucket" degenerates to a per-leaf
      collective while claiming to coalesce. Reject.
    * ``stage3_max_live_parameters`` smaller than one layer + the
      outer (embedding/head) leaves cannot run the layered step at all
      (depth 0 already keeps that much alive). Reject.
    """
    from ..config import HDSConfigError
    if largest_leaf > reduce_bucket_elements:
        name = f" ({largest_leaf_name})" if largest_leaf_name else ""
        raise HDSConfigError(
            f"zero_optimization.{knob}="
            f"{reduce_bucket_elements} elements is smaller than the "
            f"largest sharded leaf{name} = {largest_leaf} "
            f"elements; the flat collective bucket must hold at "
            f"least one leaf — raise {knob} to >= "
            f"{largest_leaf}")
    if max_live_parameters and layer_params:
        floor = outer_params + layer_params
        if floor > max_live_parameters:
            raise HDSConfigError(
                f"zero_optimization.stage3_max_live_parameters="
                f"{max_live_parameters} cannot hold even one gathered "
                f"layer + the outer leaves ({floor} params); the "
                f"layered ZeRO-3 step keeps that much alive at depth "
                f"0 — raise stage3_max_live_parameters to >= {floor}")
