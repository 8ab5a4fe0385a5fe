"""Where the persistent compilation cache lives: one decision
(``utils/compile_cache.py``), taken by ``hds.initialize`` and by
``InferenceEngineV2`` and by nothing else. A directory placed from
outside through ``JAX_COMPILATION_CACHE_DIR`` is left alone; otherwise
the cache sits at ``<checkout>/.jax_cache``.
"""

import os
import re
import subprocess
import sys

import jax

from hcache_deepspeed_tpu.utils.compile_cache import (CACHE_DIR_ENV,
                                                      default_cache_dir,
                                                      ensure_compile_cache)

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def test_default_is_a_fixed_path_in_the_checkout(monkeypatch):
    monkeypatch.delenv(CACHE_DIR_ENV, raising=False)
    assert default_cache_dir() == os.path.join(_REPO, ".jax_cache")
    assert ensure_compile_cache() == default_cache_dir()
    assert jax.config.jax_compilation_cache_dir == default_cache_dir()
    # .gitignore lists it: what the program caches is never committed
    assert ".jax_cache/" in open(os.path.join(_REPO, ".gitignore")).read()


def test_a_directory_placed_from_outside_wins_and_nothing_is_set(
        monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(CACHE_DIR_ENV, "/placed/from/outside")
    assert ensure_compile_cache() == "/placed/from/outside"
    assert jax.config.jax_compilation_cache_dir == before


_CHILD = r"""
import numpy as np, jax
import hcache_deepspeed_tpu as hds
from hcache_deepspeed_tpu.inference.factory import build_hf_engine
from hcache_deepspeed_tpu.models.gpt2 import GPT2LMHeadModel, gpt2_tiny
from hcache_deepspeed_tpu.models.llama import LlamaForCausalLM, llama_tiny
print("START", jax.config.jax_compilation_cache_dir)
batch = {"input_ids": np.zeros((8, 16), np.int32)}
hds.initialize(model=GPT2LMHeadModel(gpt2_tiny()), example_batch=batch,
               config={"train_batch_size": 8, "steps_per_print": 10**9,
                       "optimizer": {"type": "Adam",
                                     "params": {"lr": 1e-3}}})
print("AFTER_INITIALIZE", jax.config.jax_compilation_cache_dir)
params = LlamaForCausalLM(llama_tiny()).init(
    jax.random.PRNGKey(0), {"input_ids": np.zeros((1, 8), np.int32)},
    train=False)["params"]
build_hf_engine({"model_type": "llama", "vocab_size": 256,
                 "hidden_size": 64, "intermediate_size": 128,
                 "num_hidden_layers": 2, "num_attention_heads": 4,
                 "num_key_value_heads": 2,
                 "max_position_embeddings": 128,
                 "torch_dtype": "float32"}, params)
print("AFTER_BUILD_ENGINE", jax.config.jax_compilation_cache_dir)
"""


def _child(tmp_path, **env_over):
    env = {k: v for k, v in os.environ.items() if k != CACHE_DIR_ENV}
    env.update(PYTHONPATH=_REPO, JAX_PLATFORMS="cpu", **env_over)
    out = subprocess.run([sys.executable, "-c", _CHILD], env=env,
                         cwd=str(tmp_path), capture_output=True,
                         text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    return dict(re.findall(r"^(START|AFTER_\w+) (.*)$", out.stdout, re.M))


def test_both_entry_points_leave_a_placed_directory_alone(tmp_path):
    placed = str(tmp_path / "placed")
    seen = _child(tmp_path, **{CACHE_DIR_ENV: placed})
    assert seen == {"START": placed, "AFTER_INITIALIZE": placed,
                    "AFTER_BUILD_ENGINE": placed}


def test_both_entry_points_use_the_checkout_path_when_unset(tmp_path):
    seen = _child(tmp_path)
    assert seen["START"] == "None"
    assert seen["AFTER_INITIALIZE"] == os.path.join(_REPO, ".jax_cache")
    assert seen["AFTER_BUILD_ENGINE"] == os.path.join(_REPO, ".jax_cache")


def test_no_other_site_sets_the_cache_directory():
    """The helper is the only code that touches the option, and its two
    callers are the only ones that call it."""
    setters, callers = [], []
    for root in ("hcache_deepspeed_tpu", "bin", "examples"):
        for dirpath, _, names in os.walk(os.path.join(_REPO, root)):
            for name in names:
                path = os.path.join(dirpath, name)
                if "__pycache__" in path or name.endswith(".pyc"):
                    continue
                try:
                    text = open(path, encoding="utf-8").read()
                except UnicodeDecodeError:
                    continue
                rel = os.path.relpath(path, _REPO)
                if re.search(r"update\(\s*[\"']jax_compilation_cache_dir",
                             text):
                    setters.append(rel)
                if "ensure_compile_cache()" in text:
                    callers.append(rel)
    for name in ("bench.py", "chip_smoke.py", "__graft_entry__.py"):
        text = open(os.path.join(_REPO, name)).read()
        assert "jax_compilation_cache_dir" not in text, name
    assert setters == ["hcache_deepspeed_tpu/utils/compile_cache.py"]
    assert sorted(callers) == [
        "hcache_deepspeed_tpu/__init__.py",
        "hcache_deepspeed_tpu/inference/engine_v2.py",
        "hcache_deepspeed_tpu/utils/compile_cache.py"]
