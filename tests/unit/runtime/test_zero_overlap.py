"""Tier-1 gates for the explicit ZeRO-3 comm/compute overlap pipeline
(``runtime/zero/zeropp.py`` + ``runtime/zero/overlap.py`` +
``profiling/hlo_audit.py``; docs/zero_overlap.md).

Structural acceptance, on the 2-layer toy ZeRO-3 step, CPU-deterministic:

* prefetch ON (``overlap_comm=True``): the compiled micro step audits
  with >= 1 async all-gather pair carrying >= 1 interleaved dot — the
  double-buffered pipeline exists in the program, not just in the
  Python;
* ``overlap_comm=False``: ZERO such pairs — the serialization fallback
  is real (every gather/reduce sits on the dependence chain);
* the two schedules are BITWISE equal (losses and parameters across 3
  steps): the pipeline reorders the wire, never the math.

Deliberately NOT marked slow: this is the regression gate that fails if
prefetch degenerates back to sequential gather->compute (e.g. a scan
rewrite that re-consumes the gather in-body).
"""

import jax
import numpy as np
import pytest

import hcache_deepspeed_tpu as hds
from hcache_deepspeed_tpu.models.gpt2 import GPT2LMHeadModel, gpt2_tiny
from hcache_deepspeed_tpu.runtime.config import HDSConfigError
from hcache_deepspeed_tpu.runtime.zero.overlap import (derive_prefetch_depth,
                                                       plan_reduce_buckets,
                                                       validate_overlap_config)


def _batch(seed=1):
    rng = np.random.default_rng(seed)
    return {"input_ids": rng.integers(0, 256, (8, 32), dtype=np.int32)}


def _build(overlap, **zero_extra):
    model = GPT2LMHeadModel(gpt2_tiny(n_layer=2, n_embd=64, n_head=4,
                                      use_flash=False))
    zero = {"stage": 3, "min_shard_size": 1,
            "zero_quantized_weights": True, "overlap_comm": overlap}
    zero.update(zero_extra)
    cfg = {
        "train_batch_size": 8,
        "train_micro_batch_size_per_gpu": 1,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
        "zero_optimization": zero,
        "steps_per_print": 10 ** 9,
    }
    engine, _, _, _ = hds.initialize(model=model, config=cfg,
                                     example_batch=_batch())
    return engine


@pytest.fixture(scope="module")
def engines():
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    return _build(True), _build(False)


class TestOverlapStructure:

    def test_prefetch_on_has_overlappable_gather_pairs(self, engines):
        on, _ = engines
        assert on.zero_overlap_plan["depth"] == 1, on.zero_overlap_plan
        report, row = on.zero_overlap_report(_batch())
        pairs = report.pairs("all-gather", min_interleaved=1)
        assert len(pairs) >= 1, row
        assert row["gather_overlap_ratio"] > 0.0, row
        assert row["reduce_overlap_ratio"] > 0.0, row

    def test_overlap_off_is_sequential(self, engines):
        _, off = engines
        assert off.zero_overlap_plan["depth"] == 0, off.zero_overlap_plan
        report, row = off.zero_overlap_report(_batch())
        assert report.pairs("all-gather", min_interleaved=1) == [], row
        assert row["gather_overlap_ratio"] == 0.0, row
        assert row["reduce_overlap_ratio"] == 0.0, row

    def test_bitwise_parity_prefetched_vs_sequential(self, engines):
        """Loss AND parameters identical across 3 steps — grads are
        bitwise too (any grad divergence would show in params via the
        optimizer update)."""
        on, off = engines
        batch = _batch(seed=2)
        la = [float(on.train_batch(batch=batch)) for _ in range(3)]
        lb = [float(off.train_batch(batch=batch)) for _ in range(3)]
        assert la == lb, (la, lb)
        for xa, xb in zip(jax.tree.leaves(on.state["params"]),
                          jax.tree.leaves(off.state["params"])):
            np.testing.assert_array_equal(np.asarray(xa), np.asarray(xb))


class TestDominoAsyncIssue:

    def test_explicit_issue_matches_unsplit(self, eight_devices):
        """Domino's half-batch all-reduce routed through the explicit
        async-issue helper gives the unsplit layer's values, and
        ``overlap=False`` runs unsplit with the collective on the
        critical path. How a backend pairs the split halves' all-reduces
        is its scheduler's business (the CPU backend combines them since
        jax 0.9.0) and is not asserted here; the structural claim for
        the program this repo writes is ``TestOverlapStructure``'s
        ``test_prefetch_on_has_overlappable_gather_pairs``."""
        import jax.numpy as jnp
        from jax.sharding import Mesh
        from jax.sharding import PartitionSpec as P

        from hcache_deepspeed_tpu.profiling.hlo_audit import audit_compiled
        from hcache_deepspeed_tpu.runtime.domino import domino_split_async

        mesh = Mesh(np.array(jax.devices()).reshape(8), ("tensor",))
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.normal(size=(8, 16, 64)), jnp.float32)
        w1 = jnp.asarray(rng.normal(size=(64, 32)), jnp.float32)
        w2 = jnp.asarray(rng.normal(size=(32, 64)), jnp.float32)

        def fn(overlap):
            def f(xx, a, b):
                return domino_split_async(
                    lambda h: jax.nn.gelu(h @ a) @ b,
                    lambda t: jax.lax.psum(t, "tensor"),
                    xx, overlap=overlap)
            return f

        outs = {}
        for overlap in (True, False):
            compiled = jax.jit(jax.shard_map(
                fn(overlap), mesh=mesh,
                in_specs=(P(), P(None, "tensor"), P("tensor",)),
                out_specs=P(), check_vma=False)).lower(x, w1, w2).compile()
            rep = audit_compiled(compiled)
            outs[overlap] = (rep, np.asarray(compiled(x, w1, w2)[0]))
        _, y_on = outs[True]
        off_rep, y_off = outs[False]
        assert off_rep.pairs("all-reduce", min_interleaved=1) == []
        # unsplit fallback is value-equivalent (batch-pointwise layer)
        np.testing.assert_allclose(y_on, y_off, rtol=1e-5, atol=1e-5)


class TestKnobValidation:

    def test_reduce_bucket_smaller_than_leaf_rejected(self, eight_devices):
        with pytest.raises(HDSConfigError, match="reduce_bucket_size"):
            _build(True, reduce_bucket_size=8)

    def test_allgather_bucket_smaller_than_leaf_rejected(
            self, eight_devices):
        with pytest.raises(HDSConfigError, match="allgather_bucket_size"):
            _build(True, allgather_bucket_size=8)

    def test_max_live_below_one_layer_rejected(self, eight_devices):
        with pytest.raises(HDSConfigError,
                           match="stage3_max_live_parameters"):
            _build(True, stage3_max_live_parameters=64)

    def test_nonpositive_bucket_rejected_by_pydantic(self):
        from pydantic import ValidationError
        from hcache_deepspeed_tpu.runtime.config import ZeroConfig
        with pytest.raises(ValidationError):
            ZeroConfig(reduce_bucket_size=0)
        with pytest.raises(ValidationError):
            ZeroConfig(stage3_prefetch_bucket_size=-1)


class TestQuantizedWireConfig:
    """Typed rejection of nonsensical quantized-wire knob combinations
    — parse-time (ZeroConfig validator) and engine-build
    (validate_zeropp), no silent clamps."""

    def test_error_feedback_without_quantized_wire_rejected(self):
        with pytest.raises(HDSConfigError, match="error_feedback"):
            from hcache_deepspeed_tpu.runtime.config import ZeroConfig
            ZeroConfig(zero_reduce_scatter_error_feedback=True)

    def test_bad_bits_rejected(self):
        from hcache_deepspeed_tpu.runtime.config import ZeroConfig
        with pytest.raises(HDSConfigError, match="bits"):
            ZeroConfig(zero_quantized_reduce_scatter=True,
                       zero_quantized_reduce_scatter_bits=16)

    def test_bits_without_quantized_wire_rejected(self):
        from hcache_deepspeed_tpu.runtime.config import ZeroConfig
        with pytest.raises(HDSConfigError, match="no effect"):
            ZeroConfig(zero_quantized_reduce_scatter_bits=4)

    def test_qrs_and_qgz_mutually_exclusive(self):
        from hcache_deepspeed_tpu.runtime.config import ZeroConfig
        with pytest.raises(HDSConfigError, match="mutually exclusive"):
            ZeroConfig(stage=3, zero_quantized_reduce_scatter=True,
                       zero_quantized_gradients=True)

    def test_fused_matmul_without_qwz_rejected(self):
        from hcache_deepspeed_tpu.runtime.config import ZeroConfig
        with pytest.raises(HDSConfigError, match="fused_matmul"):
            ZeroConfig(zero_quantized_weights_fused_matmul=True)

    def test_qrs_requires_stage3(self):
        from hcache_deepspeed_tpu.runtime.zero.overlap import \
            validate_quantized_wire
        with pytest.raises(HDSConfigError, match="stage 3"):
            validate_quantized_wire(
                quantized_reduce_scatter=True, error_feedback=False,
                bits=8, quantized_gradients=False, stage=2)

    def test_valid_combination_accepted(self):
        from hcache_deepspeed_tpu.runtime.config import ZeroConfig
        z = ZeroConfig(stage=3, zero_quantized_weights=True,
                       zero_quantized_reduce_scatter=True,
                       zero_reduce_scatter_error_feedback=True,
                       zero_quantized_reduce_scatter_bits=4,
                       zero_quantized_weights_fused_matmul=True)
        assert z.zero_quantized_reduce_scatter


class TestDominoInt8Wire:

    def test_int8_wire_parity_and_error_feedback(self, eight_devices):
        """Opt-in int8 wire for the half-batch all-reduces: tolerance-
        gated parity against the full-width psum, and the carried
        residual actually compensates (two-step EF average beats the
        one-shot error). Full-width remains the default path."""
        import jax.numpy as jnp
        from jax.sharding import Mesh
        from jax.sharding import PartitionSpec as P

        from hcache_deepspeed_tpu.runtime.domino import domino_split_async

        mesh = Mesh(np.array(jax.devices()).reshape(8), ("tensor",))
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.normal(size=(8, 16, 64)), jnp.float32)
        w1 = jnp.asarray(rng.normal(size=(64, 32)), jnp.float32)
        w2 = jnp.asarray(rng.normal(size=(32, 64)), jnp.float32)
        W = (P(), P(None, "tensor"), P("tensor",))

        def shm(f, ins, outs):
            return jax.jit(jax.shard_map(f, mesh=mesh, in_specs=ins,
                                         out_specs=outs, check_vma=False))

        def full_fn(xx, a, b):
            return domino_split_async(
                lambda h: jax.nn.gelu(h @ a) @ b,
                lambda t: jax.lax.psum(t, "tensor"), xx)

        def q_fn(xx, a, b):
            return domino_split_async(
                lambda h: jax.nn.gelu(h @ a) @ b,
                lambda t: jax.lax.psum(t, "tensor"), xx,
                wire_bits=8, axis="tensor")

        def q_fn2(xx, a, b, e0, e1):
            return domino_split_async(
                lambda h: jax.nn.gelu(h @ a) @ b,
                lambda t: jax.lax.psum(t, "tensor"), xx,
                wire_bits=8, axis="tensor", wire_error=(e0, e1))

        y_full = shm(full_fn, W, P())(x, w1, w2)
        y_q, errs = shm(q_fn, W, (P(), (P(), P())))(x, w1, w2)
        rel = float(jnp.max(jnp.abs(y_q - y_full))
                    / jnp.max(jnp.abs(y_full)))
        assert rel < 0.02, rel
        y_q2, _ = shm(q_fn2, W + (P(), P()), (P(), (P(), P())))(
            x, w1, w2, errs[0], errs[1])
        avg = np.asarray((y_q + y_q2) / 2)
        one_shot = float(np.max(np.abs(np.asarray(y_q - y_full))))
        ef_avg = float(np.max(np.abs(avg - np.asarray(y_full))))
        assert ef_avg < one_shot, (ef_avg, one_shot)

    def test_wire_bits_requires_axis(self):
        import jax.numpy as jnp

        from hcache_deepspeed_tpu.runtime.domino import domino_split_async
        with pytest.raises(ValueError, match="axis"):
            domino_split_async(lambda h: h, lambda t: t,
                               jnp.ones((4, 2)), wire_bits=8)


class TestPlanUnits:

    def test_depth_derivation(self):
        common = dict(max_live_parameters=10 ** 9, layer_params=1000,
                      outer_params=5000)
        assert derive_prefetch_depth(
            overlap_comm=True, prefetch_bucket_size=1, **common).depth == 1
        assert derive_prefetch_depth(
            overlap_comm=False, prefetch_bucket_size=10 ** 8,
            **common).depth == 0
        assert derive_prefetch_depth(
            overlap_comm=True, prefetch_bucket_size=0, **common).depth == 0
        # live-parameter contract vetoes depth 1 (but depth 0 still runs)
        assert derive_prefetch_depth(
            overlap_comm=True, prefetch_bucket_size=10 ** 8,
            max_live_parameters=6500, layer_params=1000,
            outer_params=5000).depth == 0

    def test_bucket_planning(self):
        buckets = plan_reduce_buckets([100, None, 300, 500, 200, None, 50],
                                      600)
        assert [b.leaf_indices for b in buckets] == [(0, 2), (3,), (4, 6)]
        assert [b.elements for b in buckets] == [400, 500, 250]
        # in-order packing: layout (and therefore arithmetic) is
        # deterministic
        assert plan_reduce_buckets([], 10) == []

    def test_validate_rejects_oversized_leaf(self):
        with pytest.raises(HDSConfigError, match="largest sharded leaf"):
            validate_overlap_config(reduce_bucket_elements=10,
                                    largest_leaf=100)
        validate_overlap_config(reduce_bucket_elements=100,
                                largest_leaf=100)  # boundary ok
