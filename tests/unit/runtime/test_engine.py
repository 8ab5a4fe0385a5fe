"""End-to-end engine tests (reference analog: tests/unit/runtime/test_ds_initialize.py
+ zero/test_zero.py training-convergence checks, run on the virtual mesh)."""

import numpy as np
import pytest

import hcache_deepspeed_tpu as hds
from hcache_deepspeed_tpu.models.gpt2 import GPT2LMHeadModel, gpt2_tiny


def _data(batch, seq=16, vocab=256, seed=0):
    rng = np.random.default_rng(seed)
    return {"input_ids": rng.integers(0, vocab, (batch, seq), dtype=np.int32)}


def _base_config(**over):
    cfg = {
        "train_batch_size": 8,
        "train_micro_batch_size_per_gpu": 1,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
        "gradient_clipping": 1.0,
        "steps_per_print": 1000,
    }
    cfg.update(over)
    return cfg


def _make_engine(config, seed=0):
    model = GPT2LMHeadModel(gpt2_tiny())
    engine, _, _, _ = hds.initialize(
        model=model, config=config, example_batch=_data(1))
    return engine


class TestEngineTrains:
    def test_loss_decreases_fwd_bwd_step(self, eight_devices):
        engine = _make_engine(_base_config())
        losses = []
        for step in range(8):
            batch = _data(8, seed=step)
            loss = engine.forward(batch)
            engine.backward(loss)
            engine.step()
            losses.append(float(loss))
        assert losses[-1] < losses[0]
        assert engine.global_steps == 8

    def test_train_batch_fused(self, eight_devices):
        engine = _make_engine(_base_config(gradient_accumulation_steps=2,
                                           train_batch_size=16))
        losses = [float(engine.train_batch(batch=_data(16, seed=s)))
                  for s in range(6)]
        assert losses[-1] < losses[0]
        assert engine.global_steps == 6

    def test_gradient_accumulation_boundary(self, eight_devices):
        engine = _make_engine(_base_config(gradient_accumulation_steps=2,
                                           train_batch_size=16))
        batch = _data(8)
        loss = engine.forward(batch)
        engine.backward(loss)
        engine.step()  # not a boundary: no optimizer step
        assert engine.global_steps == 0
        loss = engine.forward(batch)
        engine.backward(loss)
        engine.step()
        assert engine.global_steps == 1


class TestZeroStages:
    """All stages must produce the same training trajectory — ZeRO is a
    memory layout, not an algorithm change (reference: test_zero.py checks
    model-parallel-invariant convergence)."""

    def _losses(self, stage, steps=4):
        from hcache_deepspeed_tpu.parallel import topology as topo_mod
        topo_mod.reset_topology()
        engine = _make_engine(_base_config(
            zero_optimization={"stage": stage, "min_shard_size": 1}))
        out = []
        for step in range(steps):
            loss = engine.train_batch(batch=_data(8, seed=step))
            out.append(float(loss))
        return out

    def test_stages_agree(self, eight_devices):
        ref = self._losses(0)
        for stage in (1, 2, 3):
            got = self._losses(stage)
            np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-5)

    def test_stage3_params_sharded(self, eight_devices):
        from hcache_deepspeed_tpu.parallel import topology as topo_mod
        topo_mod.reset_topology()
        engine = _make_engine(_base_config(
            zero_optimization={"stage": 3, "min_shard_size": 1}))
        import jax
        sharded = [
            leaf for leaf in jax.tree.leaves(engine.state["params"])
            if not leaf.sharding.is_fully_replicated
        ]
        assert sharded, "stage 3 must shard at least the big params"


class TestDataLoader:
    def test_train_batch_walks_dataset(self, eight_devices):
        """Regression: successive train_batch() calls must consume successive
        micro-batches, not restart the loader each call."""
        import hcache_deepspeed_tpu as hds
        from hcache_deepspeed_tpu.models.gpt2 import (GPT2LMHeadModel,
                                                      gpt2_tiny)
        rng = np.random.default_rng(0)
        dataset = {"input_ids": rng.integers(0, 256, (64, 16),
                                             dtype=np.int32)}
        model = GPT2LMHeadModel(gpt2_tiny())
        engine, _, loader, _ = hds.initialize(
            model=model, config=_base_config(), example_batch=_data(1),
            training_data=dataset)
        assert loader is not None

        seen = []
        orig = engine._shard_batch

        import jax

        def spy(batch, **kw):
            seen.append(np.asarray(jax.tree.leaves(batch)[0]).copy())
            return orig(batch, **kw)

        engine._shard_batch = spy
        engine.train_batch()
        engine.train_batch()
        assert len(seen) == 2
        assert not np.array_equal(seen[0], seen[1]), \
            "two train_batch calls saw identical data"


class TestPrecision:
    def test_bf16_trains(self, eight_devices):
        engine = _make_engine(_base_config(bf16={"enabled": True}))
        assert engine.state["master"] is not None
        batch = _data(8)  # fixed batch: memorisation must drive loss down
        losses = [float(engine.train_batch(batch=batch)) for _ in range(5)]
        assert losses[-1] < losses[0]

    def test_fp16_loss_scale_present(self, eight_devices):
        engine = _make_engine(_base_config(
            fp16={"enabled": True, "initial_scale_power": 8}))
        assert engine.get_loss_scale() == 2 ** 8
        loss = engine.train_batch(batch=_data(8))
        assert np.isfinite(float(loss))


class TestCheckpoint:
    def test_save_load_roundtrip(self, eight_devices, tmp_path):
        import jax
        engine = _make_engine(_base_config())
        for s in range(3):
            engine.train_batch(batch=_data(8, seed=s))
        engine.save_checkpoint(str(tmp_path), client_state={"note": "hi"})
        ref_params = jax.tree.map(np.asarray, engine.state["params"])

        from hcache_deepspeed_tpu.parallel import topology as topo_mod
        topo_mod.reset_topology()
        engine2 = _make_engine(_base_config())
        path, client = engine2.load_checkpoint(str(tmp_path))
        assert path is not None
        assert client == {"note": "hi"}
        assert engine2.global_steps == 3
        got = jax.tree.map(np.asarray, engine2.state["params"])
        jax.tree.map(np.testing.assert_allclose, got, ref_params)

    def test_train_after_restore(self, eight_devices, tmp_path):
        """Regression: scalar state leaves must stay mesh-replicated after
        orbax restore, or the next train step fails on device mismatch."""
        engine = _make_engine(_base_config())
        engine.train_batch(batch=_data(8))
        engine.save_checkpoint(str(tmp_path))
        from hcache_deepspeed_tpu.parallel import topology as topo_mod
        topo_mod.reset_topology()
        engine2 = _make_engine(_base_config())
        engine2.load_checkpoint(str(tmp_path))
        loss = engine2.train_batch(batch=_data(8, seed=1))
        assert np.isfinite(float(loss))

    def test_load_reshards_across_zero_stage(self, eight_devices, tmp_path):
        """Save at stage 0, load at stage 3 — the universal-checkpoint
        capability (reference: checkpoint/ds_to_universal.py)."""
        import jax
        engine = _make_engine(_base_config())
        engine.train_batch(batch=_data(8))
        engine.save_checkpoint(str(tmp_path))
        ref = jax.tree.map(np.asarray, engine.state["params"])

        from hcache_deepspeed_tpu.parallel import topology as topo_mod
        topo_mod.reset_topology()
        engine3 = _make_engine(_base_config(
            zero_optimization={"stage": 3, "min_shard_size": 1}))
        engine3.load_checkpoint(str(tmp_path))
        got = jax.tree.map(np.asarray, engine3.state["params"])
        jax.tree.map(np.testing.assert_allclose, got, ref)


class TestRematPolicy:
    """compile.remat_policy / activation_checkpointing.policy are live knobs:
    they wrap the loss in jax.checkpoint and measurably change the compiled
    step's temp memory (reference: runtime/activation_checkpointing/)."""

    SEQ = 128

    def _engine(self, **over):
        # wide enough that remat's activation savings dominate layout
        # noise in the compiled step's temp-buffer accounting
        model = GPT2LMHeadModel(gpt2_tiny(n_layer=6, n_embd=256,
                                          n_positions=self.SEQ,
                                          use_flash=False))
        engine, _, _, _ = hds.initialize(
            model=model, config=_base_config(**over),
            example_batch=_data(1, seq=self.SEQ))
        return engine

    def _micro_dots(self, engine):
        import jax
        batch = engine._shard_batch(
            {"input_ids": np.zeros((8, self.SEQ), np.int32)})
        lowered = engine._micro_fwd_bwd.lower(
            engine.state["params"], engine.state["grad_acc"],
            engine.state["loss_scale"], batch, jax.random.PRNGKey(0),
            True)
        return lowered.as_text().count("stablehlo.dot_general")

    def test_remat_recomputes_in_backward(self, eight_devices):
        """The structural signature of a live remat knob: full remat
        re-runs the forward's matmuls inside backward, so the lowered
        micro program carries strictly more dot ops. (Temp-byte deltas
        on the CPU backend are assignment noise — the TPU savings come
        from the same recompute structure.)"""
        plain = self._engine(train_batch_size=8)
        remat = self._engine(
            train_batch_size=8,
            compile={"remat_policy": "nothing_saveable"})
        assert self._micro_dots(remat) > self._micro_dots(plain)

    def test_remat_loss_matches(self, eight_devices):
        batch = _data(8)
        losses = {}
        for name, over in [("plain", {}),
                           ("remat", {"activation_checkpointing":
                                      {"policy": "dots_saveable"}})]:
            engine = self._engine(train_batch_size=8, **over)
            losses[name] = float(engine.train_batch(batch=batch))
        assert abs(losses["plain"] - losses["remat"]) < 1e-4

    def test_unknown_policy_rejected(self, eight_devices):
        from hcache_deepspeed_tpu.runtime.config import HDSConfigError
        with pytest.raises(HDSConfigError, match="remat policy"):
            self._engine(train_batch_size=8,
                         compile={"remat_policy": "no_such_policy"})


class TestGradNorm:
    def test_global_grad_norm_populated(self, eight_devices):
        engine = _make_engine(_base_config())
        assert engine.get_global_grad_norm() is None
        engine.train_batch(batch=_data(8))
        norm = engine.get_global_grad_norm()
        assert norm is not None and np.isfinite(norm) and norm > 0


class TestCompilationCache:
    def test_cache_reused_across_processes(self, tmp_path):
        # a cache directory placed through JAX_COMPILATION_CACHE_DIR is
        # left alone by hds.initialize: a first process writes
        # executables there, a SECOND process reuses them (measured as a
        # large drop in init+first-step wall time — in-process jit
        # caching cannot explain a cross-process speedup)
        import os
        import subprocess
        import sys

        cache = str(tmp_path / "xla_cache")
        child = f'''
import time, numpy as np
import hcache_deepspeed_tpu as hds
from hcache_deepspeed_tpu.models.gpt2 import GPT2LMHeadModel, gpt2_tiny
batch = {{"input_ids": np.zeros((8, 16), np.int32)}}
t0 = time.time()
engine, _, _, _ = hds.initialize(
    model=GPT2LMHeadModel(gpt2_tiny()), example_batch=batch,
    config={{"train_batch_size": 8,
            "optimizer": {{"type": "Adam", "params": {{"lr": 1e-3}}}},
            "steps_per_print": 10**9}})
float(engine.train_batch(batch=batch))
print("ELAPSED", time.time() - t0)
'''
        env = dict(os.environ,
                   PYTHONPATH=os.path.dirname(os.path.dirname(
                       os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))))),
                   JAX_PLATFORMS="cpu",
                   JAX_COMPILATION_CACHE_DIR=cache,
                   JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
                   XLA_FLAGS="--xla_force_host_platform_device_count=8")
        times = []
        for _ in range(2):
            out = subprocess.run([sys.executable, "-c", child], env=env,
                                 capture_output=True, text=True,
                                 timeout=400)
            assert out.returncode == 0, out.stderr[-2000:]
            times.append(float(out.stdout.split("ELAPSED")[1]))
        assert os.listdir(cache), "persistent cache dir stayed empty"
        assert times[1] < 0.7 * times[0], \
            f"no cross-process reuse: cold {times[0]:.1f}s, " \
            f"warm {times[1]:.1f}s"


class TestFlopsProfilerWiring:
    def test_profile_step_emits_report(self, eight_devices, tmp_path):
        out_file = tmp_path / "profile.txt"
        engine = _make_engine(_base_config(
            flops_profiler={"enabled": True, "profile_step": 1,
                            "output_file": str(out_file)}))
        for s in range(3):
            engine.train_batch(batch=_data(8, seed=s))
        text = out_file.read_text()
        assert "flops per step" in text and "achieved" in text
        # the per-device fused-step cost must be in the right ballpark:
        # >= 6*N*T/devices (weight flops alone) for the tiny model
        import re

        import jax
        m = re.search(r"flops per step:\s+([\d.]+) ([TGMK])", text)
        assert m, text
        val = float(m.group(1)) * {"T": 1e12, "G": 1e9, "M": 1e6,
                                   "K": 1e3}[m.group(2)]
        n_params = sum(x.size for x in
                       jax.tree.leaves(engine.state["params"]))
        floor = 6 * n_params * 8 * 16 / len(jax.devices()) / 3
        assert val > floor, (val, floor)
