"""Test harness.

Reference analog: ``tests/unit/common.py`` — there, multi-process
``torch.multiprocessing`` + file-store rendezvous simulates a cluster; here
the TPU-native analog is a *virtual 8-device CPU mesh* via
``--xla_force_host_platform_device_count`` (SURVEY.md §4): every sharding,
collective and ZeRO path executes exactly as it would across chips, inside
one process.

These env vars must be set before JAX initialises its backends, which is why
they live at conftest import time.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=8")
os.environ.setdefault("HDS_LOG_LEVEL", "warning")

import jax  # noqa: E402
import pytest  # noqa: E402

# jax may have been preloaded at interpreter startup (before this conftest
# ran), in which case the env vars above were read too late; force the
# platform through the live config instead. Backends are still lazy at
# collection time, so this takes effect.
jax.config.update("jax_platforms", "cpu")

# HARNESS RULE — one collective launch in flight at a time.
#
# XLA's CPU in-process collectives make every participating device thread
# block in a rendezvous (rendezvous.cc). Device programs run on a *shared*
# thread pool, so if a Python loop enqueues many launches without
# synchronizing, pool threads end up parked in different launches'
# rendezvous and the process dies with SIGABRT after the 40s termination
# timeout — taking all of pytest down (empirically deterministic on a
# 1-core host with 8 virtual devices; `jax_cpu_enable_async_dispatch=False`
# does NOT cover sharded computations and does not help).
#
# Any test loop that repeatedly calls a jitted function containing
# psum/all_gather/etc must therefore `jax.block_until_ready(...)` (or fetch
# a scalar) every iteration — which is also what the real engine train loop
# does by fetching the loss.


@pytest.fixture(autouse=True)
def _reset_singletons():
    yield
    from hcache_deepspeed_tpu.parallel import topology
    topology.reset_topology()


@pytest.fixture
def eight_devices():
    devs = jax.devices()
    if len(devs) < 8:
        pytest.skip("needs 8 virtual devices")
    return devs


# ------------------------------------------------------------------ #
# Test tiers (reference: tests/pytest.ini marker discipline).
#
# The 8-virtual-device engine compiles dominate suite wall clock
# (30-90 s per distinct engine/mesh program on this host), so every
# module that builds engines or lowers full train programs is
# auto-marked `slow`. The smoke tier
#
#     python -m pytest tests/ -m "not slow" -q        (< 5 min)
#
# keeps per-component unit coverage (schedule math, packing, config
# parsing, masks, importers, launcher command builders, kernels at
# tiny shapes) plus one true engine smoke (test_smoke_engine.py); the
# full suite is the nightly bar:
#
#     python -m pytest tests/ -q
# ------------------------------------------------------------------ #
_SLOW_PATH_PARTS = (
    "runtime/test_engine.py",
    "runtime/test_compression.py",
    "runtime/test_structured_compression.py",
    "runtime/test_multislice.py",
    "runtime/test_mics.py",
    "runtime/test_zeropp.py",
    "runtime/test_zeropp_layered.py",
    "runtime/test_offload.py",
    "runtime/test_hybrid_engine.py",
    "runtime/test_domino_hlo.py",
    "runtime/test_infinity.py",
    "runtime/test_data_pipeline.py",
    "runtime/test_sparse_domino_elastic.py",
    "runtime/test_indexed_dataset.py",
    "runtime/test_comm_dtype.py",
    "tests/unit/pipe/",
    "tests/unit/moe/",
    "tests/unit/sequence_parallelism/",
    "tests/unit/inference/",
    "tests/unit/models/",
    "checkpoint/test_universal.py",
    "checkpoint/test_moe_checkpoint.py",
    "tests/unit/test_bench_configs.py",
    "tests/unit/test_aux_subsystems.py",
    "tests/unit/test_auto_tp.py",
    "tests/integration/",
)


#: files under a slow directory that build no engine, or only the
#: two-layer toy one in seconds, and stay in the smoke tier
_TIER1_IN_SLOW_DIRS = (
    "inference/test_kv_pool_in_place.py",
    "inference/test_host_latents.py",
    "inference/test_latent_landing.py",
)


def pytest_collection_modifyitems(config, items):
    for item in items:
        path = str(item.fspath).replace("\\", "/")
        if any(part in path for part in _SLOW_PATH_PARTS) and not any(
                part in path for part in _TIER1_IN_SLOW_DIRS):
            item.add_marker(pytest.mark.slow)
