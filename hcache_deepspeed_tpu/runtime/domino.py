"""Domino: communication-hiding tensor parallelism.

Reference analog: ``deepspeed/runtime/domino/transformer.py`` (605 LoC) +
``async_linear.py`` — the transformer layer splits each micro-batch into
two half-batches and hand-schedules async TP allreduces so one half's
collective overlaps the other half's compute (NoOper/HANDLE_DIC event
machinery).

TPU re-design: the *mechanism* dissolves — XLA's latency-hiding scheduler
overlaps any collective with any independent compute automatically. What
remains load-bearing is the *program shape*: the layer must present two
independent half-batch compute→allreduce chains for the scheduler to
interleave. ``domino_split`` restructures a TP transformer layer exactly
that way: x → [x0, x1]; attention(x0); attention(x1) (x0's psum now
overlaps x1's attention math); MLP likewise, carrying the halves through
the residual stream and re-concatenating at the end. Numerically
identical to the unsplit layer for any batch-pointwise layer function.

Evidence (``tests/unit/runtime/test_domino_hlo.py``), not assertion:

* The split program compiles to two all-reduces with NO dependence path
  between them, and each has other-half dot ops that are neither its
  ancestors nor descendants — the scheduler is legally free to overlap
  (verified on the optimized HLO's def-use graph).
* Caveat, pinned by test: a backend's all-reduce *combiner* may merge
  the two half collectives (the CPU backend does at default flags),
  degenerating Domino to the unsplit schedule — same math and wire, no
  overlap, no regression. On TPU the combiner is size-thresholded and
  the latency-hiding scheduler emits async start/done pairs; the
  ``tpu``-marked test asserts other-half dots are scheduled inside the
  start..done window on real hardware — which ``DOMINO_TPU_r4.log``
  showed it did NOT (``async_pairs 0``): that round's chip run compiled
  zero async pairs, the finding that motivated the explicit issue helper.

:func:`domino_split_async` is the explicit form: the layer is given as
``compute_fn`` + ``collective_fn`` and the half-batch all-reduces are
routed through :class:`comm.overlap.CollectiveIssue` — issued in
program order between the halves' compute, auditable with
``profiling/hlo_audit.py``, and honoring ``overlap=False`` as a real
serialization (the unsplit layer) instead of a no-op.
"""

import jax.numpy as jnp

from ..comm.overlap import CollectiveIssue


def domino_split(layer_fn, x, *args, **kwargs):
    """Run ``layer_fn`` (a TP block: [B, T, H] -> [B, T, H] containing
    tensor-axis psums) over two half-batches so XLA overlaps one half's
    collectives with the other half's compute.

    ``layer_fn`` must be batch-pointwise (no cross-batch reductions) —
    true of transformer blocks. Odd batches put the extra row in the
    first half.
    """
    B = x.shape[0]
    if B < 2:
        return layer_fn(x, *args, **kwargs)
    h = (B + 1) // 2
    y0 = layer_fn(x[:h], *args, **kwargs)
    y1 = layer_fn(x[h:], *args, **kwargs)
    return jnp.concatenate([y0, y1], axis=0)


def domino_split_async(compute_fn, collective_fn, x, *args,
                       overlap=True, wire_bits=None, axis=None,
                       wire_error=None, group_size=2048, **kwargs):
    """Half-batch split with the collective EXPLICITLY issued through
    :class:`comm.overlap.CollectiveIssue` instead of buried inside an
    opaque layer function — the reference's hand-scheduled form
    (``async_linear.py``: matmul, async allreduce handle, other half's
    matmul, wait).

    ``compute_fn(half, *args, **kwargs)`` is the pre-collective math;
    ``collective_fn(partial)`` the tensor-axis reduction (e.g.
    ``lambda t: jax.lax.psum(t, "tensor")``). Issue order is explicit:

        t0 = compute(x0); ISSUE ar0; t1 = compute(x1); ISSUE ar1;
        WAIT ar0; WAIT ar1

    so ar0 is legally overlappable by x1's compute — which
    ``profiling/hlo_audit.py`` can verify on the compiled program.
    With ``overlap=False`` the layer runs UNSPLIT (one full-batch
    chain, the collective on the critical path) — for a batch-pointwise
    ``compute_fn`` that is value-identical to split-and-concat, and it
    is a REAL serialization the audit sees in the final module
    (``optimization_barrier`` fences are erased by XLA after
    optimization, so a fenced split would still audit as overlappable).

    ``wire_bits`` (opt-in; full-width remains the default): quantize
    each half's all-reduce to an int8 wire with error feedback
    (``comm/quantized.py quantized_allreduce_body`` — the same shared
    residual machinery as the 1-bit optimizers and the ZeRO quantized
    reduce-scatter). ``collective_fn`` is replaced by the quantized
    body, so ``axis`` (the mesh axis the layer reduces over) becomes
    required. ``wire_error`` carries the per-half residual state —
    a ``(e0, e1)`` tuple shaped like the halves' partials (``None``
    seeds zeros) — and the return becomes
    ``(y, (e0_new, e1_new))`` for the caller to thread. Must run
    inside the shard_map region, like the plain collective.
    """
    B = x.shape[0]
    if wire_bits is not None:
        if axis is None:
            raise ValueError(
                "domino_split_async(wire_bits=...) needs the mesh "
                "axis the layer reduces over (axis=...)")
        from ..comm.quantized import quantized_allreduce_body

        def q_collective(t, e):
            return quantized_allreduce_body(
                t, e, axis, group_size=group_size, num_bits=wire_bits)

        if B < 2 or not overlap:
            t = compute_fn(x, *args, **kwargs)
            e = wire_error[0] if wire_error is not None \
                else jnp.zeros(t.shape, jnp.float32)
            y, e_new = q_collective(t, e)
            return y, (e_new,)
        h = (B + 1) // 2
        issue = CollectiveIssue(overlap=True,
                                op_name="domino_half_allreduce_int8")
        t0 = compute_fn(x[:h], *args, **kwargs)
        e0 = wire_error[0] if wire_error is not None \
            else jnp.zeros(t0.shape, jnp.float32)
        k0 = issue.issue(q_collective, t0, e0)
        t1 = compute_fn(x[h:], *args, **kwargs)
        e1 = wire_error[1] if wire_error is not None \
            else jnp.zeros(t1.shape, jnp.float32)
        k1 = issue.issue(q_collective, t1, e1)
        y0, e0_new = issue.wait(k0)
        y1, e1_new = issue.wait(k1)
        return jnp.concatenate([y0, y1], axis=0), (e0_new, e1_new)
    if B < 2 or not overlap:
        return collective_fn(compute_fn(x, *args, **kwargs))
    h = (B + 1) // 2
    issue = CollectiveIssue(overlap=True,
                            op_name="domino_half_allreduce")
    t0 = compute_fn(x[:h], *args, **kwargs)
    k0 = issue.issue(collective_fn, t0)
    t1 = compute_fn(x[h:], *args, **kwargs)
    k1 = issue.issue(collective_fn, t1)
    return jnp.concatenate([issue.wait(k0), issue.wait(k1)], axis=0)


class DominoTransformer:
    """Layer wrapper applying :func:`domino_split` to every call
    (reference: ``DominoTransformerLayer`` — same layer, comm-hiding
    execution shape). When the layer is given in split form
    (``compute_fn`` + ``collective_fn``), the collective is routed
    through the explicit async-issue helper
    (:func:`domino_split_async`)."""

    def __init__(self, layer_fn=None, *, compute_fn=None,
                 collective_fn=None, overlap=True, wire_bits=None,
                 axis=None):
        if (layer_fn is None) == (compute_fn is None):
            raise ValueError(
                "pass either layer_fn (opaque form) or compute_fn + "
                "collective_fn (explicit async-issue form)")
        if compute_fn is not None and collective_fn is None:
            raise ValueError("compute_fn requires collective_fn")
        if wire_bits is not None and compute_fn is None:
            raise ValueError("wire_bits needs the explicit "
                             "compute_fn + collective_fn form")
        self.layer_fn = layer_fn
        self.compute_fn = compute_fn
        self.collective_fn = collective_fn
        self.overlap = overlap
        self.wire_bits = wire_bits
        self.axis = axis

    def __call__(self, x, *args, **kwargs):
        if self.layer_fn is not None:
            return domino_split(self.layer_fn, x, *args, **kwargs)
        return domino_split_async(self.compute_fn, self.collective_fn,
                                  x, *args, overlap=self.overlap,
                                  wire_bits=self.wire_bits,
                                  axis=self.axis, **kwargs)
