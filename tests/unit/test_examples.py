"""Every shipped example must run end-to-end (reference analog: the
DeepSpeedExamples CI smoke jobs). Each runs as its own subprocess on the
8-virtual-device CPU mesh — exactly the command its docstring documents —
so an internal API drift that breaks a user-facing example fails here
instead of in a user's terminal.
"""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
EXAMPLES = sorted(
    f for f in os.listdir(os.path.join(REPO, "examples"))
    if f.endswith(".py"))


def test_every_example_is_covered():
    """A new example file must be added to the runnable set below (or
    explicitly excluded with a reason)."""
    assert EXAMPLES == sorted(RUNNABLE), (
        "examples/ and RUNNABLE out of sync")


# example -> max seconds (CPU mesh; generous 3x headroom over measured)
RUNNABLE = {
    "autotune_train_config.py": 600,
    "compress_prune_export.py": 120,
    "long_context_ulysses.py": 300,
    "lora_finetune.py": 180,
    "moe_pipeline_3d.py": 300,
    "pretrain_indexed_gpt2.py": 180,
    "rlhf_raft_loop.py": 600,
    "serve_fused_decode.py": 180,
    "serve_hcache.py": 180,
    "serve_hf_checkpoint.py": 300,
    "train_zero3_llama.py": 300,
    "universal_checkpoint_reshape.py": 300,
}


@pytest.mark.slow
@pytest.mark.parametrize("name", sorted(RUNNABLE))
def test_example_runs(name):
    # inherited PYTHONPATH entries are kept (deps may ride it) — same
    # pattern as tests/unit/elasticity/test_elasticity.py
    kept = [p for p in os.environ.get("PYTHONPATH", "").split(":") if p]
    env = dict(os.environ,
               PYTHONPATH=":".join([REPO] + kept),
               JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "examples", name)],
        capture_output=True, text=True, timeout=RUNNABLE[name],
        cwd=REPO, env=env)
    assert out.returncode == 0, (
        f"{name} failed rc={out.returncode}\n--- stdout:\n"
        f"{out.stdout[-2000:]}\n--- stderr:\n{out.stderr[-2000:]}")
