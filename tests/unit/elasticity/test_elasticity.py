"""Reference analog: ``tests/unit/elasticity/test_elastic.py`` — batch/chip
compatibility arithmetic."""

import pytest

from hcache_deepspeed_tpu.autotuning import Autotuner
from hcache_deepspeed_tpu.elasticity import (ElasticityError,
                                             compute_elastic_config,
                                             get_compatible_gpus)

BASE = {
    "enabled": True,
    "max_train_batch_size": 10000,
    "micro_batch_sizes": [8, 12, 16, 17],
    "min_gpus": 32,
    "max_gpus": 1500,
}


class TestElasticity:

    def test_compatible_gpus(self):
        # batch 48, micros {8, 12}: replicas 6 or 4 -> w in {1..6}∪{1..4}
        out = get_compatible_gpus(48, [8, 12], min_gpus=1, max_gpus=64)
        assert out == [1, 2, 3, 4, 6]

    def test_granule(self):
        out = get_compatible_gpus(64, [8], min_gpus=1, max_gpus=64,
                                  granule=4)
        assert out == [4, 8]

    def test_compute_config(self):
        final_batch, valid, _ = compute_elastic_config(BASE)
        assert final_batch <= BASE["max_train_batch_size"]
        assert valid and all(BASE["min_gpus"] <= w <= BASE["max_gpus"]
                             for w in valid)
        # every valid world size actually factors the batch
        for w in valid[:5]:
            _, _, detail = compute_elastic_config(BASE, world_size=w)
            assert detail["micro_batch"] * detail["gas"] * w == final_batch

    def test_incompatible_world_size(self):
        final_batch, valid, _ = compute_elastic_config(BASE)
        bad = max(valid) + 1
        while bad in valid:
            bad += 1
        with pytest.raises(ElasticityError, match="not in the elastic"):
            compute_elastic_config(BASE, world_size=bad)

    def test_disabled(self):
        with pytest.raises(ElasticityError, match="not enabled"):
            compute_elastic_config({"enabled": False})


class TestElasticEndToEnd:

    @pytest.mark.slow
    def test_kill_shrink_relaunch_resume(self, tmp_path):
        """The full elastic flow with real subprocesses (reference:
        ``--elastic_training`` — DSElasticAgent membership change ->
        restart at the new world size, ``elastic_agent.py:32`` +
        ``launcher/runner.py:404``): the agent spawns 4 workers through
        ``launcher.launch``, worker 3 dies after the generation-0
        checkpoint, ``compute_elastic_config`` shrinks to the largest
        batch-compatible world <= 3 survivors (= 2), the group relaunches
        and worker 0 resumes from the universal checkpoint at dp=2 with
        loss continuity on a fixed probe batch."""
        import json
        import os
        import sys

        from hcache_deepspeed_tpu.elasticity.elastic_agent import \
            ElasticAgent

        worker = os.path.join(os.path.dirname(__file__),
                              "elastic_worker.py")
        run_dir = str(tmp_path)
        repo = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__)))))
        os.environ["HDS_ELASTIC_TEST_DIR"] = run_dir
        # the bootstrap execs the worker by PATH, so sys.path[0] is the
        # worker's dir — the repo root must come from PYTHONPATH.
        # Inherited entries are kept (deps may ride PYTHONPATH).
        prev_pp = os.environ.get("PYTHONPATH")
        kept = [p for p in (prev_pp or "").split(":") if p]
        os.environ["PYTHONPATH"] = ":".join([repo] + kept)
        try:
            def cmd_fn(world, restart, idx):
                return [sys.executable, "-m",
                        "hcache_deepspeed_tpu.launcher.launch",
                        worker, str(world), str(restart), str(idx)]

            # valid world sizes from the batch arithmetic: micro 2,
            # max_train_batch 8 -> {1, 2, 4}; 3 survivors shrink to 2
            agent = ElasticAgent(
                cmd_fn, world_size=4,
                elastic_config={"enabled": True,
                                "max_train_batch_size": 8,
                                "micro_batch_sizes": [2],
                                "min_gpus": 1, "max_gpus": 4},
                max_restarts=2, poll_interval=0.2, grace_period=1.0)
            final_world = agent.run()
        finally:
            os.environ.pop("HDS_ELASTIC_TEST_DIR", None)
            if prev_pp is None:
                os.environ.pop("PYTHONPATH", None)
            else:
                os.environ["PYTHONPATH"] = prev_pp
        assert final_world == 2

        with open(os.path.join(run_dir, "loss_pre.json")) as fh:
            pre = json.load(fh)
        with open(os.path.join(run_dir, "loss_post.json")) as fh:
            post = json.load(fh)
        assert pre["world"] == 4 and post["world"] == 2
        # step counter restored, and the probe loss carries across the
        # resize (same params, same batch -> same loss up to reshard
        # numerics)
        assert post["steps"] == pre["steps"]
        assert post["loss"] == pytest.approx(pre["loss"], rel=1e-3)
        # training continues downhill from the restored point: the
        # train-batch loss after the post-restore steps is below the
        # last pre-kill train loss on the SAME batch (a held-out probe
        # gives no 2-step guarantee; the train batch does)
        assert post["continued"][-1] < pre["train_last"]


class TestAutotuner:

    def test_picks_fastest_and_skips_failures(self):
        import time

        def run_fn(cand):
            if cand["micro_batch"] == 64:
                raise MemoryError("oom")  # surfaced at build time

            def step():
                time.sleep(0.001 if cand["micro_batch"] == 16 else 0.005)
            return step

        tuner = Autotuner(run_fn, micro_batch_sizes=[4, 16, 64],
                          warmup_steps=1, measure_steps=2)
        best = tuner.tune()
        assert best.config["micro_batch"] == 16
        failed = [r for r in tuner.results if not r.ok]
        assert len(failed) == 1 and failed[0].error == "MemoryError"
        assert "samples/s" in tuner.summary()

    def test_all_fail(self):
        def run_fn(cand):
            raise RuntimeError("nope")

        tuner = Autotuner(run_fn, micro_batch_sizes=[4])
        with pytest.raises(RuntimeError, match="no viable config"):
            tuner.tune()

    def test_extra_space_axes(self):
        """Arbitrary sweep axes (e.g. flash tiling) join the product
        and the winner carries them."""
        import time

        def run_fn(cand):
            def step():
                fast = cand["flash_block_q"] == 512 and \
                    cand["flash_block_k"] == 1024
                time.sleep(0.001 if fast else 0.004)
            return step

        tuner = Autotuner(
            run_fn, micro_batch_sizes=[8],
            extra_space={"flash_block_q": [256, 512],
                         "flash_block_k": [512, 1024]},
            warmup_steps=1, measure_steps=2)
        assert len(tuner.space) == 4
        best = tuner.tune()
        assert (best.config["flash_block_q"],
                best.config["flash_block_k"]) == (512, 1024)
        assert "flash_block_q" in tuner.summary()
