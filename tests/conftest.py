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
# Test tiers. Tier-1 is what the driver runs after every PR:
#
#     python -m pytest tests/ -q -m "not slow" -n 6 --dist loadfile
#
# under JAX_PLATFORMS=cpu; 1258 cases pass in 240 s on this
# host (PR 29). A file is ``slow`` (runs only without the ``-m``) if
# its slowest case takes over 60 s, if it kills its worker, or if a
# case of it fails or wavers; everything else belongs in tier-1, most
# of all the files that hold the two paths the benchmark measures
# (engine_v2 / restore / allocator for the serve cell, the GSPMD and
# ZeRO++ steps for the train cell; promoted in PR 29 after three runs
# each under the command above). The files below are still ``slow``.
# Beside each: cases, their summed seconds and the slowest case in one
# run of all of them on five workers (PR 29). Those with no reason
# given pass and are fast enough; they wait only for three timed runs
# and for room under the 600 s the whole command may take, and are the
# next to promote (ROADMAP D2 needs some 63 of them).
# A few other files mark single cases ``slow`` themselves.
# ------------------------------------------------------------------ #
_SLOW_FILES = (
    # crashes its worker: XLA:CPU aborts compiling the bf16 hpZ layered
    # step (TestPrefetchBitwiseMatrix::test_bf16_hpz, ROADMAP D11);
    # the file marks itself slow as well
    "runtime/test_zeropp_prefetch.py",               # 18: 124 s without it
    # fail on this tree and on its parents (ROADMAP D6)
    "inference/test_serve_bench.py",                 # 12: 62 s, 2 fail
    "tests/unit/test_aux_subsystems.py",             # 11: 0.1 s, 1 fails
    # pass; not yet timed three times
    "tests/integration/test_user_journey.py",        # 1: 22 s
    "checkpoint/test_moe_checkpoint.py",             # 2: 41 s, 32 s
    "inference/test_falcon_family.py",               # 5: 22 s, 12 s
    "inference/test_gpt2_family.py",                 # 8: 33 s, 11 s
    "inference/test_opt_family.py",                  # 5: 24 s, 11 s
    "inference/test_phi_family.py",                  # 6: 32 s, 17 s
    "inference/test_serving_fuzz.py",                # 1: 31 s
    "inference/test_tp_falcon_phi.py",               # 4: 9 s, 4 s
    "inference/test_tp_gpt2_opt.py",                 # 3: 9 s, 5 s
    "inference/test_tp_moe_inference.py",            # 3: 23 s, 17 s
    "inference/test_tp_quantized.py",                # 4: 32 s, 15 s
    "models/test_new_families_training.py",          # 4: 18 s, 6 s
    "pipe/test_1f1b.py",                             # 12: 82 s, 27 s
    "pipe/test_flat_to_pipeline.py",                 # 6: 40 s, 18 s
    "pipe/test_neox_scale_memory.py",                # 2: 13 s, 9 s
    "pipe/test_pipeline.py",                         # 11: 28 s, 16 s
    "pipe/test_schedule_trace.py",                   # 3: 0 s
    "runtime/test_compression.py",                   # 18: 41 s, 9 s
    "runtime/test_structured_compression.py",        # 25: 45 s, 14 s
    "runtime/test_multislice.py",                    # 3: 19 s, 7 s
    "runtime/test_mics.py",                          # 3: 13 s, 8 s
    "runtime/test_hybrid_engine.py",                 # 10: 31 s, 8 s
    "runtime/test_domino_hlo.py",                    # 3: 13 s, 8 s
    "runtime/test_infinity.py",                      # 7: 15 s, 6 s
    "runtime/test_data_pipeline.py",                 # 21: 16 s, 10 s
    "runtime/test_sparse_domino_elastic.py",         # 10: 6 s, 1 s
    "runtime/test_indexed_dataset.py",               # 10: 3 s
    "sequence_parallelism/test_ring_fpdt.py",        # 16: 31 s, 9 s
    "sequence_parallelism/test_ulysses.py",          # 14: 47 s, 7 s
    "tests/unit/test_bench_configs.py",              # 5: 9 s, 7 s
    "tests/unit/test_auto_tp.py",                    # 5: 5 s
)


def pytest_collection_modifyitems(config, items):
    for item in items:
        path = str(item.fspath).replace("\\", "/")
        if path.endswith(_SLOW_FILES):
            item.add_marker(pytest.mark.slow)
