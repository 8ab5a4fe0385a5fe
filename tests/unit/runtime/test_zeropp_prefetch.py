"""Full bitwise-parity matrix for the software-pipelined layered
ZeRO-3 step: prefetched (``overlap_comm=True``) vs sequential
(``overlap_comm=False``) schedules must produce IDENTICAL losses and
parameters across 3 steps — fp32 and bf16, with and without qwZ / hpZ /
qgZ, gpt2 and llama. The tier-1 file
(``test_zero_overlap.py``) gates one representative config; this is the
nightly sweep.

Marked slow: each cell builds two engines (8-virtual-device compiles).
"""

import jax
import numpy as np
import pytest

import hcache_deepspeed_tpu as hds
from hcache_deepspeed_tpu.models.gpt2 import GPT2LMHeadModel, gpt2_tiny
from hcache_deepspeed_tpu.models.llama import LlamaForCausalLM, llama_tiny

pytestmark = pytest.mark.slow


def _batch(seed=3):
    rng = np.random.default_rng(seed)
    return {"input_ids": rng.integers(0, 256, (8, 32), dtype=np.int32)}


def _build(model_fn, overlap, bf16=False, **zero_extra):
    zero = {"stage": 3, "min_shard_size": 1, "overlap_comm": overlap}
    zero.update(zero_extra)
    cfg = {
        "train_batch_size": 8,
        "train_micro_batch_size_per_gpu": 1,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
        "zero_optimization": zero,
        "steps_per_print": 10 ** 9,
    }
    if bf16:
        cfg["bf16"] = {"enabled": True}
    engine, _, _, _ = hds.initialize(model=model_fn(), config=cfg,
                                     example_batch=_batch())
    return engine


def _assert_bitwise(model_fn, bf16=False, steps=3, **zero_extra):
    a = _build(model_fn, True, bf16=bf16, **zero_extra)
    b = _build(model_fn, False, bf16=bf16, **zero_extra)
    assert a.zero_overlap_plan["depth"] == 1, a.zero_overlap_plan
    assert b.zero_overlap_plan["depth"] == 0, b.zero_overlap_plan
    batch = _batch()
    la = [float(a.train_batch(batch=batch)) for _ in range(steps)]
    lb = [float(b.train_batch(batch=batch)) for _ in range(steps)]
    assert la == lb, (la, lb)
    for xa, xb in zip(jax.tree.leaves(a.state["params"]),
                      jax.tree.leaves(b.state["params"])):
        np.testing.assert_array_equal(np.asarray(xa), np.asarray(xb))


def _gpt2():
    return GPT2LMHeadModel(gpt2_tiny(n_layer=2, n_embd=64, n_head=4,
                                     use_flash=False))


def _llama():
    return LlamaForCausalLM(llama_tiny(use_flash=False))


class TestPrefetchBitwiseMatrix:

    def test_fp32_qwz(self, eight_devices):
        _assert_bitwise(_gpt2, zero_quantized_weights=True)

    def test_fp32_hpz(self, eight_devices):
        _assert_bitwise(_gpt2, zero_hpz_partition_size=2)

    def test_fp32_qwz_hpz(self, eight_devices):
        _assert_bitwise(_gpt2, zero_quantized_weights=True,
                        zero_hpz_partition_size=2)

    def test_fp32_qwz_qgz(self, eight_devices):
        _assert_bitwise(_gpt2, zero_quantized_weights=True,
                        zero_quantized_gradients=True)

    def test_bf16_qwz(self, eight_devices):
        _assert_bitwise(_gpt2, bf16=True, zero_quantized_weights=True)

    def test_bf16_hpz(self, eight_devices):
        _assert_bitwise(_gpt2, bf16=True, zero_hpz_partition_size=2)

    def test_llama_qwz(self, eight_devices):
        _assert_bitwise(_llama, zero_quantized_weights=True)


class TestPrefetchVsWholeTree:

    def test_prefetched_matches_whole_tree_trajectory(self, eight_devices):
        """The pipelined scan against the AD-based whole-tree gather:
        same per-leaf collectives, different program — trajectories
        agree to reassociation noise (the pre-existing layered-vs-whole
        contract, now with the pipeline on)."""
        a = _build(_gpt2, True, zero_quantized_weights=True)
        w = _build(_gpt2, True, zero_quantized_weights=True,
                   layered_gather=False)
        batch = _batch()
        la = [float(a.train_batch(batch=batch)) for _ in range(4)]
        lw = [float(w.train_batch(batch=batch)) for _ in range(4)]
        assert la[-1] < la[0]
        np.testing.assert_allclose(la, lw, rtol=1e-4)


class TestQuantizedWire:
    """The bucketed int8 reduce-scatter with error feedback and the
    fused qwZ matmul consumption: (a) depth-1 vs depth-0 stays BITWISE
    under quantization — the quantized wire changes the math vs
    full-width, never between the two schedules; (b) the error-feedback
    loss trajectory tracks the full-width run within tolerance over
    multiple steps (fp32 and bf16 — the acceptance gate)."""

    QRS = dict(zero_quantized_reduce_scatter=True,
               zero_reduce_scatter_error_feedback=True)

    def test_qrs_bitwise_depth_parity_fp32(self, eight_devices):
        _assert_bitwise(_gpt2, zero_quantized_weights=True, **self.QRS)

    def test_qrs_bitwise_depth_parity_bf16(self, eight_devices):
        _assert_bitwise(_gpt2, bf16=True, zero_quantized_weights=True,
                        **self.QRS)

    @pytest.mark.parametrize("bf16", [False, True],
                             ids=["fp32", "bf16"])
    def test_qrs_error_feedback_loss_trajectory(self, eight_devices,
                                                bf16):
        """Multi-step loss-trajectory parity gate: quantized wire +
        error feedback vs the full-width wire, same schedule."""
        q = _build(_gpt2, True, bf16=bf16, zero_quantized_weights=True,
                   **self.QRS)
        f = _build(_gpt2, True, bf16=bf16, zero_quantized_weights=True)
        batch = _batch()
        lq = [float(q.train_batch(batch=batch)) for _ in range(5)]
        lf = [float(f.train_batch(batch=batch)) for _ in range(5)]
        assert lq[-1] < lq[0]           # still training
        np.testing.assert_allclose(lq, lf, rtol=5e-2)

    def test_qrs_without_error_feedback_also_trains(self, eight_devices):
        """EF off is a legal (comparison) mode: quantization error is
        dropped, the trajectory drifts further but must stay sane."""
        q = _build(_gpt2, True, zero_quantized_weights=True,
                   zero_quantized_reduce_scatter=True)
        batch = _batch()
        lq = [float(q.train_batch(batch=batch)) for _ in range(4)]
        assert lq[-1] < lq[0]

    def test_qrs_int4_wire_trajectory(self, eight_devices):
        q = _build(_gpt2, True, zero_quantized_weights=True,
                   zero_quantized_reduce_scatter_bits=4, **self.QRS)
        f = _build(_gpt2, True, zero_quantized_weights=True)
        batch = _batch()
        lq = [float(q.train_batch(batch=batch)) for _ in range(4)]
        lf = [float(f.train_batch(batch=batch)) for _ in range(4)]
        assert lq[-1] < lq[0]
        np.testing.assert_allclose(lq, lf, rtol=1e-1)

    def test_fused_matmul_bitwise_depth_parity(self, eight_devices):
        _assert_bitwise(_gpt2, zero_quantized_weights=True,
                        zero_quantized_weights_fused_matmul=True)

    def test_fused_matmul_matches_dequant_path(self, eight_devices):
        """Fused (int8, scales) consumption vs dequant-then-matmul:
        same quantized weights, different consumption — losses agree
        within the kernel's documented tile tolerance."""
        fz = _build(_gpt2, True, zero_quantized_weights=True,
                    zero_quantized_weights_fused_matmul=True)
        dq = _build(_gpt2, True, zero_quantized_weights=True)
        batch = _batch()
        lfz = [float(fz.train_batch(batch=batch)) for _ in range(4)]
        ldq = [float(dq.train_batch(batch=batch)) for _ in range(4)]
        np.testing.assert_allclose(lfz, ldq, rtol=2e-2)

    def test_wire_error_state_persists_and_moves(self, eight_devices):
        """The residual state is engine state: allocated at build,
        updated every step, carried through the optimizer boundary."""
        q = _build(_gpt2, True, zero_quantized_weights=True, **self.QRS)
        assert q.state["wire_error"] is not None
        before = [np.asarray(r).copy()
                  for r in q.state["wire_error"]["block"]]
        batch = _batch()
        q.train_batch(batch=batch)
        after = [np.asarray(r) for r in q.state["wire_error"]["block"]]
        assert any(not np.array_equal(b, a)
                   for b, a in zip(before, after))
        assert all(np.isfinite(a).all() for a in after)


class TestGradAccumulation:

    def test_gas2_bitwise(self, eight_devices):
        """The fused gas>1 scan reuses the same micro — the pipeline
        must stay bitwise under gradient accumulation too."""
        def build(overlap):
            cfg = {
                "train_batch_size": 16,
                "train_micro_batch_size_per_gpu": 1,
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
                "zero_optimization": {"stage": 3, "min_shard_size": 1,
                                      "zero_quantized_weights": True,
                                      "overlap_comm": overlap},
                "steps_per_print": 10 ** 9,
            }
            engine, _, _, _ = hds.initialize(
                model=_gpt2(), config=cfg, example_batch=_batch())
            return engine

        rng = np.random.default_rng(5)
        batch = {"input_ids": rng.integers(0, 256, (16, 32),
                                           dtype=np.int32)}
        a, b = build(True), build(False)
        la = [float(a.train_batch(batch=batch)) for _ in range(2)]
        lb = [float(b.train_batch(batch=batch)) for _ in range(2)]
        assert la == lb
        for xa, xb in zip(jax.tree.leaves(a.state["params"]),
                          jax.tree.leaves(b.state["params"])):
            np.testing.assert_array_equal(np.asarray(xa), np.asarray(xb))
