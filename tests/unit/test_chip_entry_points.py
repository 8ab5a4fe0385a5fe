"""The entry points that exist to measure the chip.

``chip_smoke.py`` is the standing proof that both main paths start on
the TPU; its phases run here at ``llama_tiny`` size, chosen by an
explicit argument and never fallen back to. Without a chip it, and every
other measuring entry point, exits non-zero and says why — none of them
measures the CPU under a device metric's name, and none prints a result.
"""

import json
import os
import shutil
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, _REPO)
import chip_smoke  # noqa: E402


def test_smoke_phases_pass_at_tiny_size(eight_devices):
    size = chip_smoke.TINY
    one_chip = chip_smoke.serve_phase(size)
    assert len(one_chip["context"]) > size.prefill_chunk
    trained = chip_smoke.train_phase(size, eight_devices[:1])
    assert trained["losses"][-1] < trained["losses"][0]
    # the four-chip phases, on virtual devices (llama_tiny has two KV
    # heads, so its tensor phase runs two-way)
    chip_smoke.zero3_phase(size, eight_devices[:4], trained["losses"])
    chip_smoke.tensor_phase(size, eight_devices[:2], one_chip)


def test_hybrid_smoke_phase_passes_at_tiny_size():
    """One period of the hybrid trunk at toy width: the phase's checks
    (finite logits through both pools, latents of the full layer only,
    nothing pool-sized copied, slot and blocks given back) hold on the
    CPU's program too; the stacked-layer check is the chip's."""
    chip_smoke.hybrid_phase(chip_smoke.TINY_HYBRID_PERIOD, block_size=8,
                            prefill_chunk=16)
    published = chip_smoke.OLMO_HYBRID_PERIOD
    assert (published["hidden_size"], published["intermediate_size"],
            published["linear_key_head_dim"],
            published["linear_value_head_dim"]) == (3840, 11008, 96, 192)


def test_latent_smoke_phase_passes_at_tiny_size():
    """The latent-attention pair (a dense layer and a sparse one) at toy
    width: the phase's checks (cache rows to the host, evict and restore
    through the latent pool against the uninterrupted logits, nothing
    pool-sized copied, blocks given back) hold on the CPU's program."""
    chip_smoke.latent_phase(chip_smoke.TINY_LATENT_PAIR, block_size=8,
                            prefill_chunk=16, logit_tol=1e-5)
    published = chip_smoke.GLM_LATENT_PAIR
    assert (published["hidden_size"], published["kv_lora_rank"],
            published["qk_rope_head_dim"], published["n_routed_experts"],
            published["vocab_size"]) == (2048, 512, 64, 64, 154880)


def test_window_smoke_phase_passes_at_tiny_size():
    """The windowed period (three window layers and a global one, a
    share of the experts held) at toy width: the phase's checks (K/V
    rows to the host, window blocks back while the sequence lives, evict
    and restore through both pools against the uninterrupted logits,
    every block given back) hold on the CPU's program."""
    chip_smoke.window_phase(chip_smoke.TINY_WINDOW_PERIOD, block_size=8,
                            prefill_chunk=16, logit_tol=1e-5)
    published = chip_smoke.COMMAND_A_PERIOD
    assert (published["hidden_size"], published["num_attention_heads"],
            published["num_key_value_heads"], published["head_dim"],
            published["sliding_window"], published["num_experts"],
            published["intermediate_size"]) == \
        (4096, 128, 8, 128, 4096, 128, 4096)


def test_smoke_sizes_keep_the_full_mistral_7b_width():
    hf = chip_smoke.MISTRAL_7B.hf_config
    assert (hf["hidden_size"], hf["intermediate_size"],
            hf["num_attention_heads"], hf["num_key_value_heads"],
            hf["vocab_size"]) == (4096, 14336, 32, 8, 32000)
    assert hf["hidden_size"] // hf["num_attention_heads"] == 128
    # every context stays under the published 4096-token sliding window
    assert chip_smoke.MISTRAL_7B.max_context < 4096
    # one prompt is longer than a prefill dispatch, so chunking runs
    assert any(r[1] > chip_smoke.MISTRAL_7B.prefill_chunk
               for r in chip_smoke.MISTRAL_7B.requests)


def _run(argv, env=None, cwd=_REPO, **env_over):
    env = dict(os.environ if env is None else env, **env_over)
    return subprocess.run([sys.executable] + argv, env=env, cwd=cwd,
                          capture_output=True, text=True, timeout=300)


def _no_result_line(stdout):
    for line in stdout.splitlines():
        if line.startswith("{"):
            assert not json.loads(line).get("ok"), line


@pytest.mark.parametrize("var, value", [("JAX_PLATFORMS", "cpu"),
                                        ("HDS_PLATFORM", "cpu"),
                                        ("HDS_DISABLE_PALLAS", "1")])
def test_a_variable_that_hides_the_chip_is_refused_by_name(monkeypatch,
                                                           var, value):
    for name in ("JAX_PLATFORMS", "HDS_PLATFORM", "HDS_DISABLE_PALLAS"):
        monkeypatch.delenv(name, raising=False)
    monkeypatch.setenv(var, value)
    with pytest.raises(chip_smoke.SmokeFailure, match=var):
        chip_smoke.require_chip()


@pytest.mark.parametrize("hidden", [True, False],
                         ids=["JAX_PLATFORMS=cpu", "nothing hides it"])
def test_chip_smoke_without_a_chip_exits_nonzero(hidden):
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "HDS_PLATFORM",
                        "HDS_DISABLE_PALLAS")}
    if hidden:
        env["JAX_PLATFORMS"] = "cpu"
    # otherwise JAX looks for a chip itself and finds only the CPU
    out = _run(["chip_smoke.py"], env=env)
    assert out.returncode != 0
    assert "chip_smoke: FAILED" in out.stderr
    _no_result_line(out.stdout)


def test_chip_smoke_alone_in_a_directory_exits_nonzero(tmp_path):
    shutil.copy(os.path.join(_REPO, "chip_smoke.py"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    out = _run(["chip_smoke.py"], env=env, cwd=str(tmp_path))
    assert out.returncode != 0
    assert "checkout of the repository" in out.stderr
    assert out.stdout.strip() == ""


@pytest.mark.parametrize("argv", [
    ["bench.py"],
    ["bin/hds_serve_bench"],
    ["bin/hds_decode_diag", "--model", "tiny"],
    ["bin/chip_paged_vet.py"],
], ids=lambda a: a[0])
def test_measuring_entry_point_off_chip_exits_nonzero(argv):
    out = _run(argv, JAX_PLATFORMS="cpu", PYTHONPATH=_REPO)
    assert out.returncode != 0
    said = out.stdout + out.stderr
    assert "cpu" in said and ("TPU" in said or "tpu" in said), said[-500:]
    for line in out.stdout.splitlines():
        if line.startswith("{"):
            row = json.loads(line)
            # bench.py's one line: an error payload that names the device
            assert row["value"] == 0.0 and row["error"]
            assert row["device"]["platform"] == "cpu"
