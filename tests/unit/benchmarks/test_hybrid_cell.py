"""The files of the cell ``olmoh-serve-long-prompt``: its configuration,
traffic and metric files through ``contract.py``, the gated-delta counts
against hand arithmetic, the ``roofline_counts`` reducer on a made-up
trace, and the ``serve_hybrid`` runner at tiny size on the CPU (the
command itself refuses to measure there).

``BENCHMARK.json`` declares the configuration and the cell (PR 31).
``test_contract_files.py::test_config_files_name_their_cuts`` asserts
Mistral-7B's widths of every declared configuration and so fails on
this one by its being another model; only a ``benchmark`` PR may edit
that file (CHANGES.md, PR 31). ``test_every_configuration_names_its_cuts``
here is what that test becomes with the widths keyed by ``source``."""

import json
import os
import time

import numpy as np
import pytest

from benchmarks import contract, flops_recurrent, layer_metrics
from benchmarks.compile_meter import CompileMeter
from benchmarks.generators import paced
from benchmarks.reducers import roofline_counts, trace_share
from benchmarks.runners import serve, serve_hybrid
from benchmarks.runners.common import Context
from benchmarks.trace import xplane
from benchmarks.trace.xplane import Op, Trace

CELL = {"name": "olmoh-serve-long-prompt",
        "config": "olmo-hybrid-7b-serve-l8", "traffic": "long-prompt-8k",
        "chips": 1}
ENTRY = {"name": "olmo-hybrid-7b-serve-l8",
         "source": "https://huggingface.co/allenai/Olmo-Hybrid-7B/blob/"
                   "main/config.json",
         "file": "benchmarks/configs/olmo-hybrid-7b-serve-l8.json",
         "reduced": ["num_hidden_layers", "layer_types",
                     "max_position_embeddings"]}
BENCH = contract.load_benchmark()
TINY = os.path.join(os.path.dirname(__file__), "tiny")
NEW_METRICS = ("kernel_share.gated_delta", "gated_delta_roofline",
               "state_pool_copy_share", "state_slots_mean",
               "latent_bytes_per_token")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def _config():
    return contract.load_config(BENCH, CELL["config"])


def test_benchmark_declares_the_configuration_and_the_cell():
    entry = BENCH["configs"][-1]
    assert {k: entry[k] for k in ENTRY} == ENTRY
    cell = contract.find_cell(BENCH, CELL["name"])
    assert {k: cell[k] for k in CELL} == CELL
    assert cell is BENCH["workloads"][-1]
    reports = {m["name"] for m in BENCH["end_to_end"]
               if CELL["name"] in m.get("workloads", [CELL["name"]])}
    assert reports == {"ttft_p90_s", "itl_mean_s", "serve_tok_s", "setup_s"}
    mine = [m["name"] for m in BENCH["per_layer"]
            if CELL["name"] in m["workloads"]]
    assert mine == list(NEW_METRICS) == \
        [m["name"] for m in BENCH["per_layer"][-len(NEW_METRICS):]]


# the published widths of each source a configuration may name: what
# test_contract_files.py::test_config_files_name_their_cuts holds every
# configuration to, once keyed by source (an unknown source fails)
WIDTHS = {
    "https://huggingface.co/mistralai/Mistral-7B-v0.1/blob/main/config.json":
        {"hidden_size": 4096, "intermediate_size": 14336,
         "num_attention_heads": 32, "num_key_value_heads": 8,
         "vocab_size": 32000},
    ENTRY["source"]:
        {"hidden_size": 3840, "intermediate_size": 11008,
         "num_attention_heads": 30, "num_key_value_heads": 30,
         "vocab_size": 100352},
}


@pytest.mark.parametrize("entry", BENCH["configs"],
                         ids=[c["name"] for c in BENCH["configs"]])
def test_every_configuration_names_its_cuts(entry):
    config = contract.load_config(BENCH, entry["name"])
    assert config["source"] == entry["source"]
    assert sorted(config["reduced"]) == sorted(entry["reduced"])
    for key, value in WIDTHS[entry["source"]].items():
        assert config[key] == value, key
    assert "assumed" in config and "stands_for" in config


def test_configuration_keeps_every_published_width():
    config = _config()
    # what test_config_files_name_their_cuts asks of every configuration
    assert config["source"] == ENTRY["source"]
    assert sorted(config["reduced"]) == sorted(ENTRY["reduced"])
    assert "assumed" in config and "stands_for" in config
    assert config["name"] == ENTRY["name"]
    published = {
        "hidden_size": 3840, "intermediate_size": 11008,
        "vocab_size": 100352, "num_attention_heads": 30,
        "num_key_value_heads": 30, "linear_num_key_heads": 30,
        "linear_num_value_heads": 30, "linear_key_head_dim": 96,
        "linear_value_head_dim": 192, "linear_conv_kernel_dim": 4,
        "linear_allow_neg_eigval": True, "rms_norm_eps": 1e-6,
        "tie_word_embeddings": False, "attention_bias": False,
        "rope_parameters": {"rope_theta": None}}
    for key, value in published.items():
        assert config[key] == value, key
    assert config["layer_types"] == (["linear_attention"] * 3
                                     + ["full_attention"]) * 2
    assert config["num_hidden_layers"] == len(config["layer_types"]) == 8
    assert {"block_wrapper", "rope_theta", "linear_mixer_gate_and_norm",
            "state_dtype"} <= set(config["assumed"])
    assert config["runner"] == "serve_hybrid" and config["chips"] == 1


@pytest.mark.skipif(not os.path.exists(CATALOG),
                    reason="the model catalog is not on this machine")
def test_configuration_differs_from_the_catalog_only_where_it_says():
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Olmo-Hybrid-7B")
    config = _config()
    assert config["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key not in config["reduced"]:
            assert config[key] == value, key
    assert config["layer_types"] == row["config"]["layer_types"][:8]


def test_standing_memory_is_over_half_the_chip():
    config = _config()
    dep = config["deployment"]
    h, ffn, vocab = (config[k] for k in ("hidden_size", "intermediate_size",
                                         "vocab_size"))
    hk = config["linear_num_key_heads"] * config["linear_key_head_dim"]
    hv = config["linear_num_value_heads"] * config["linear_value_head_dim"]
    mlp = 3 * h * ffn
    linear = 2 * h * hk + 3 * h * hv + 2 * h * 30 + 4 * (2 * hk + hv) + mlp
    full = 4 * h * h + mlp
    assert round(linear / 1e6, 1) == 215.6 and round(full / 1e6, 1) == 185.8
    weights = 2 * (6 * linear + 2 * full + 2 * h * vocab)
    kv = dep["num_blocks"] * dep["block_size"] * 2 * 2 * h * 2
    state = dep["max_tracked_sequences"] * 6 * (
        30 * 96 * 192 * 4 + 3 * (2 * hk + hv) * 2)
    assert round(weights / 1e9, 2) == 4.87 and round(kv / 1e9, 2) == 3.02
    assert round(state / 1e9, 2) == 0.88
    assert weights + kv + state > 8e9           # the driver's floor: 4 GB


def test_traffic_is_the_mix_the_issue_gives():
    traffic = contract.load_traffic(CELL["traffic"])
    assert traffic["kind"] == "paced"
    assert traffic["prompt_tokens"] == {
        "dist": "lognormal", "median": 3072, "sigma": 0.6, "min": 1024,
        "max": 7680}
    assert traffic["output_tokens"] == {
        "dist": "lognormal", "median": 128, "sigma": 0.6, "min": 32,
        "max": 384}
    assert (traffic["block"], traffic["jitter"], traffic["shuffle"],
            traffic["prefills_together"], traffic["priority"]) == \
        (25, 0.1, 5, 3, 0)
    assert traffic["rate"] >= 2.0
    # over 100 requests in the window, every prompt 2 to 15 slices
    arrivals = paced.schedule(traffic, 2 ** 31 + 5, BENCH["run_seconds"],
                              100352, 8192)
    in_window = [a for a in arrivals if a.in_window]
    assert len(in_window) == \
        int(traffic["rate"] * BENCH["run_seconds"]) >= 100
    assert all(2 <= -(-len(a.prompt) // 512) <= 15 for a in in_window)
    assert any(len(a.prompt) + a.max_new_tokens > 4096 + 1
               for a in in_window[:len(in_window) // 2])
    # and the warm-up reaches every dispatch shape it can make
    dep = _config()["deployment"]
    prefill, decode = serve.warm_plan(traffic, dep)
    assert decode == [5, 9, 17, 33]
    assert all(lanes * length <= 768 for lanes, length in prefill)


@pytest.mark.parametrize("name", NEW_METRICS)
def test_new_metric_files_name_their_kind_and_their_cell_alone(name):
    spec = contract.load_metric_specs()[name]
    assert spec["cells"] == {"runner": "serve_hybrid"}
    # no cell the benchmark had gains or loses a metric
    for cell in BENCH["workloads"]:
        kind = contract.load_config(BENCH, cell["config"])["runner"]
        assert contract.metric_applies(spec, cell, kind) == \
            (cell["name"] == CELL["name"])
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert spec["moves"] in e2e and spec["better"] in ("lower", "higher")
    assert CELL["name"] in e2e[spec["moves"]]["workloads"]
    contract.load_kind("reducers", spec["reads"])


def test_correct_holds_the_state_pool_to_the_configurations_dtype():
    assert _config()["assumed"]["state_dtype"].split()[0] == \
        serve_hybrid.STATE_DTYPE == "float32"


def test_step_counts_are_the_hand_arithmetic():
    got = flops_recurrent.gated_delta_step_counts(
        lanes=3, n_head=30, d_k=96, d_v=192, itemsize=4)
    state = 96 * 192
    assert got["flops"] == 3 * 30 * 7 * state
    assert got["bytes"] == 3 * 30 * (2 * state * 4 + (96 + 96 + 192 + 192) * 4)
    # 6 linear layers of one lane: the 27 MB of state traffic a lane
    assert round(6 * 30 * 2 * state * 4 / 1e6, 1) == 26.5


def test_chunk_counts_are_the_hand_arithmetic():
    kw = dict(n_head=2, d_k=8, d_v=16, chunk=4, itemsize=4)
    one = flops_recurrent.gated_delta_chunk_counts(t_lens=[4], **kw)
    # one chunk of 4: 6 pairs below the diagonal, 10 on and below it
    per_head = 2 * 8 * 16 + 2 * 16 * 16 + 6 * 4 * 8 * 16 + 8 * 16
    assert one["flops"] == 2 * per_head
    assert one["bytes"] == 2 * (4 * (8 + 8 + 16 + 16) * 4 + 2 * 8 * 16 * 4)
    # 6 tokens are a chunk of 4 and one of 2; two lanes add up; the
    # state is read and written once a lane, not once a chunk
    tail = 2 * 8 * (1 + 3) + 2 * 16 * (1 + 3) + 6 * 2 * 8 * 16 + 8 * 16
    both = flops_recurrent.gated_delta_chunk_counts(t_lens=[6, 4], **kw)
    assert both["flops"] == 2 * (per_head + tail) + 2 * per_head
    assert both["bytes"] == 2 * ((6 + 4) * 48 * 4 + 2 * 2 * 8 * 16 * 4)
    # pads do no work: a lane of no real token counts its state alone
    none = flops_recurrent.gated_delta_chunk_counts(t_lens=[0], **kw)
    assert none["flops"] == 0 and none["bytes"] == 2 * 2 * 8 * 16 * 4
    # a 512-token slice at the published widths: 2.3 GFLOP a layer
    real = flops_recurrent.gated_delta_chunk_counts(
        t_lens=[512], n_head=30, d_k=96, d_v=192, chunk=64, itemsize=4)
    assert 2.2e9 < real["flops"] < 2.4e9


def _op(text, start, end):
    return Op(text, xplane.label_of(text), start, end)


def _made_up_trace():
    meta = 'kernel_metadata={"hds_kernel":"gated_delta_%s"}'
    ops = [
        _op("%hds_gated_delta_chunk.1 = (f32[1,30,512,192]) custom-call(q),"
            " " + meta % "chunk", 1.0, 1.4),
        _op("%hds_gated_delta_step.2 = (f32[8,30,1,192]) custom-call(q),"
            " " + meta % "step", 1.4, 1.5),
        _op("%copy.9 = f32[6,65,30,96,192]{4,3,2,1,0} copy(p)", 1.5, 1.7),
        _op("%fusion.3 = bf16[512,3840]{1,0} fusion(y)", 1.7, 2.0)]
    xplane.set_own_times(ops)
    return xplane.reduce(Trace(chips={0: ops}, t_min=1.0, t_max=2.0))


def test_roofline_counts_reads_the_new_counts_module():
    spec = contract.load_metric_specs()["gated_delta_roofline"]
    chunk = dict(t_lens=[512], n_head=30, d_k=96, d_v=192, chunk=64,
                 itemsize=2)
    step = dict(lanes=8, n_head=30, d_k=96, d_v=192, itemsize=2)
    ev = {"trace": _made_up_trace(), "device_kind": "TPU v5 lite",
          "gated_chunk_calls": [chunk], "gated_step_calls": [step]}

    def least(counts):
        return max(counts["flops"] / 197e12, counts["bytes"] / 819e9)

    want = least(flops_recurrent.gated_delta_chunk_counts(**chunk)) + \
        least(flops_recurrent.gated_delta_step_counts(**step))
    assert roofline_counts.read(spec, ev) == pytest.approx(
        100 * want / 0.5)
    # a kernel the stretch never ran is left out with its calls
    only = dict(ev, gated_step_calls=[])
    assert roofline_counts.read(spec, only) == pytest.approx(
        100 * least(flops_recurrent.gated_delta_chunk_counts(**chunk)) / 0.4)
    # nothing to read: no trace, or no kernel in it
    assert roofline_counts.read(spec, {"device_kind": "TPU v5 lite"}) is None
    assert roofline_counts.read(
        spec, dict(ev, gated_chunk_calls=[], gated_step_calls=[])) is None
    with pytest.raises(ValueError):
        roofline_counts.read(dict(spec, counts_module="os.path"), ev)


def test_the_other_new_metrics_on_the_made_up_trace():
    specs = contract.load_metric_specs()
    ev = {"trace": _made_up_trace(), "device_kind": "TPU v5 lite",
          "placeholders": {"state_pool": "65_30_96_192_"},
          "series": {"state_slots": [10, 12, 14]},
          "counters": {"latent_bytes_per_token": 15360.0}}
    assert trace_share.read(specs["kernel_share.gated_delta"], ev) == \
        pytest.approx(50.0)
    assert trace_share.read(specs["state_pool_copy_share"], ev) == \
        pytest.approx(20.0)
    got = layer_metrics.compute(CELL, "serve_hybrid", ev)
    assert got["state_slots_mean"]["value"] == 12.0
    assert got["latent_bytes_per_token"] == {"value": 15360.0,
                                             "unit": "B/token"}
    # on a program with no state pool the placeholder is not given and
    # the metric leaves the line
    assert trace_share.read(specs["state_pool_copy_share"],
                            dict(ev, placeholders={})) is None


def test_kernel_calls_count_full_and_linear_layers_apart():
    from hcache_deepspeed_tpu.inference.factory import MODEL_FAMILIES
    config = _config()
    cfg = MODEL_FAMILIES["olmo_hybrid"](serve.hf_config(config))
    steps = [{"decode_ctx": [3000, 4100], "slices": [(512, 1024), (1, 700)]},
             {"decode_ctx": [], "slices": [(200, 200)]}]
    calls = serve_hybrid.kernel_calls(steps, cfg)
    # per step and full layer: the decode dispatch (the one-token slice
    # of a sequence with a context rides it) and one call a slice
    assert len(calls["paged_calls"]) == 2 * (1 + 1) + 2 * 1
    assert calls["paged_calls"][0]["context_lens"] == [3000, 4100, 700]
    assert len(calls["gated_step_calls"]) == 6
    assert calls["gated_step_calls"][0]["lanes"] == 3
    assert [c["t_lens"] for c in calls["gated_chunk_calls"]] == \
        [[512]] * 6 + [[200]] * 6
    assert calls["gated_chunk_calls"][0]["chunk"] == 64
    # q, k, v, o at the activations' 2 bytes, as the paged kernel's
    assert calls["gated_chunk_calls"][0]["itemsize"] == \
        calls["gated_step_calls"][0]["itemsize"] == \
        calls["paged_calls"][0]["itemsize"] == 2


def test_decay_leaves_spread_the_heads_and_repeat():
    import jax.numpy as jnp
    a = serve_hybrid.decay_leaves(2 ** 31 + 7, 2, 30, jnp.bfloat16)
    b = serve_hybrid.decay_leaves(2 ** 31 + 7, 2, 30, jnp.bfloat16)
    other = serve_hybrid.decay_leaves(2 ** 31 + 7, 3, 30, jnp.bfloat16)
    np.testing.assert_array_equal(a["dt_bias"], b["dt_bias"])
    assert not np.array_equal(a["dt_bias"], other["dt_bias"])
    factor = np.exp(-np.log1p(np.exp(np.asarray(a["dt_bias"], np.float32))))
    assert 0.0 < factor.min() < 0.3 and 0.7 < factor.max() < 1.0
    assert not np.asarray(a["A_log"], np.float32).any()


# ------------------------------------------------------------------ #
# the runner at tiny size
# ------------------------------------------------------------------ #
def _load(name):
    with open(os.path.join(TINY, name)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    ctx = Context(cell={"name": "tiny-hybrid", "chips": 1},
                  config=_load("tiny-hybrid.json"),
                  traffic=_load("tiny-long.json"), seed=2 ** 31 + 11,
                  seconds=4.0, trace=False, t_start=time.monotonic(),
                  root=str(tmp_path_factory.mktemp("serve_hybrid")),
                  meter=CompileMeter())
    return ctx, serve_hybrid.run(ctx)


def test_hybrid_run_is_correct_and_counts_every_due_request(served):
    ctx, result = served
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] == int(ctx.traffic["rate"] * ctx.seconds)
    assert set(result["metrics"]) == {"ttft_p90_s", "itl_mean_s",
                                      "serve_tok_s", "setup_s"}
    assert all(m["value"] > 0 for m in result["metrics"].values())
    for phase in ("weights", "engine", "warm", "ramp", "setup_s"):
        assert phase in ctx.phases


def test_check_compares_a_short_and_a_long_sequence():
    class Req:
        def __init__(self, uid, n_prompt, n_out):
            self.uid, self.prompt = uid, [0] * n_prompt
            self.tokens_out = [0] * n_out

    rows = [{"req": Req(0, 3000, 100)}, {"req": Req(1, 1100, 40)},
            {"req": Req(2, 5000, 50)}, {"req": Req(3, 4000, 98)},
            {"req": Req(4, 1024, 32)}]
    picked = serve_hybrid.pick_compared(rows, {0, 1, 2, 3}, 4096)
    assert picked["short"].uid == 1             # uid 4 kept no row
    assert picked["long"].uid == 3              # 4000 + 97 passed 4096
    assert "long" not in serve_hybrid.pick_compared(rows, {0, 1}, 4096)
