"""bench.py's training path: one fixed configuration, measured in
process. The configuration must build and trace abstractly
(jax.eval_shape — no compile, no device) so a broken shape is caught
here and not on chip time, and the tiny CPU path must run the same code
end to end and name the device it ran on. (That the real configuration
refuses to run without a chip is pinned in test_chip_entry_points.py.)
"""

import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, _REPO)
import bench  # noqa: E402


@pytest.mark.parametrize("spec", [bench.CONFIG, bench.TINY_CONFIG],
                         ids=lambda s: s["name"])
def test_config_traces(spec):
    # bench.build_model is the SAME builder run_training measures with
    model, cfg = bench.build_model(spec)
    batch = {"input_ids": jax.ShapeDtypeStruct(
        (spec["batch"], spec["seq"]), np.int32)}

    def init_and_loss(rng, batch):
        variables = model.init(rng, batch, train=False)
        return model.apply(variables, batch, train=True,
                           rngs={"dropout": rng})

    out = jax.eval_shape(init_and_loss, jax.random.PRNGKey(0), batch)
    assert out.shape == ()


def test_the_one_configuration_is_the_350m_hd128_shape():
    assert bench.CONFIG["name"] == "350m-hd128-b8"
    _, cfg = bench.build_model(bench.CONFIG)
    assert (cfg.n_layer, cfg.n_embd, cfg.n_head) == (24, 1024, 8)
    assert cfg.vocab_size % 128 == 0


def test_training_path_starts_no_subprocess():
    """One process for the chip: the parent that probed and ran
    candidates in children is gone, and so are its knobs."""
    assert not hasattr(bench, "subprocess")
    assert not hasattr(bench, "threading")
    src = open(os.path.join(_REPO, "bench.py")).read()
    for gone in ("HDS_BENCH_CHILD", "HDS_BENCH_CAND_SECS",
                 "HDS_BENCH_PROBE_SECS", "HDS_BENCH_WATCHDOG_SECS",
                 "_record_last_measured", "CANDIDATES"):
        assert gone not in src, gone


def test_tiny_path_runs_in_process_and_names_its_device():
    env = dict(os.environ, HDS_BENCH_TINY="1", PYTHONPATH=_REPO,
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    out = subprocess.run([sys.executable, os.path.join(_REPO, "bench.py")],
                         env=env, capture_output=True, text=True,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    row = json.loads(out.stdout.strip().splitlines()[-1])
    assert row["device"] == {"platform": "cpu", "device_kind": "cpu",
                             "device_count": row["device"]["device_count"]}
    assert "SMOKE" in row["metric"] and row["value"] > 0
    # no device metric from a CPU run: the host has no published peak
    assert row["vs_baseline"] is None
    assert row["extra"]["mfu"] is None
    assert row["extra"]["peak_tflops"] is None
    assert row["extra"]["fallbacks"] == {}
