"""The files of the cell ``sdar-serve-block-denoise`` (PR 42): its
configuration against the catalog row, its bytes by hand, its traffic,
the counts of ``flops_moe.py`` against hand arithmetic, the new metrics
on a made-up trace, and the ``serve_diffusion`` runner at tiny size on
the CPU (the command itself refuses to measure there). Entries of
``BENCHMARK.json`` are held by name, never by position."""

import json
import os
import time

import jax
import numpy as np
import pytest

from benchmarks import contract, flops_moe, layer_metrics, weights
from benchmarks.compile_meter import CompileMeter
from benchmarks.generators import paced
from benchmarks.runners import serve_diffusion
from benchmarks.runners.common import Context
from benchmarks.trace import xplane
from benchmarks.trace.xplane import Op, Trace

CELL = {"name": "sdar-serve-block-denoise",
        "config": "sdar-30b-a3b-serve-1chip",
        "traffic": "diffusion-chat-256", "chips": 1}
SOURCE = ("https://huggingface.co/JetLM/SDAR-30B-A3B-Chat/blob/main/"
          "config.json")
BENCH = contract.load_benchmark()
TINY = os.path.join(os.path.dirname(__file__), "tiny")
NEW_METRICS = {"tokens_per_forward", "block_lanes_mean",
               "fetch_bytes_per_token", "moe_share",
               "expert_gemm_roofline", "expert_load_imbalance",
               "paged_block_roofline"}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def _config():
    return contract.load_config(BENCH, CELL["config"])


def _by_name(entries):
    return {e["name"]: e for e in entries}


def test_benchmark_declares_the_configuration_and_the_cell():
    entry = _by_name(BENCH["configs"])[CELL["config"]]
    assert entry["source"] == SOURCE
    assert entry["file"] == f"benchmarks/configs/{CELL['config']}.json"
    assert sorted(entry["reduced"]) == ["max_position_embeddings",
                                        "num_hidden_layers"]
    cell = contract.find_cell(BENCH, CELL["name"])
    assert {k: cell[k] for k in CELL} == CELL and len(cell["why"]) <= 200
    reports = {m["name"] for m in BENCH["end_to_end"]
               if CELL["name"] in m.get("workloads", [CELL["name"]])}
    assert reports == {"ttft_p90_s", "itl_mean_s", "serve_tok_s", "setup_s"}
    mine = {m["name"] for m in BENCH["per_layer"]
            if CELL["name"] in m["workloads"]}
    assert mine == NEW_METRICS
    e2e = _by_name(BENCH["end_to_end"])
    for name in NEW_METRICS:
        entry = _by_name(BENCH["per_layer"])[name]
        assert entry["workloads"] == [CELL["name"]]
        assert CELL["name"] in e2e[entry["moves"]]["workloads"]
    # no share of a roofline or of a peak that another cell's metric
    # reads is claimed to be read here
    assert os.path.getsize(os.path.join(contract.ROOT,
                                        "BENCHMARK.json")) < 64 * 1024


@pytest.mark.skipif(not os.path.exists(CATALOG),
                    reason="the model catalog is not on this machine")
def test_configuration_differs_from_the_catalog_only_where_it_says():
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "SDAR-30B-A3B-Chat")
    config = _config()
    assert config["source"] == row["source_url"] == SOURCE
    assert sorted(config["reduced"]) == ["max_position_embeddings",
                                         "num_hidden_layers"]
    for key, value in row["config"].items():
        if key not in config["reduced"]:
            assert config[key] == value, key
    assert (config["num_hidden_layers"],
            config["max_position_embeddings"]) == (6, 2304)


def test_configuration_keeps_every_published_width():
    config = _config()
    published = {
        "hidden_size": 2048, "head_dim": 128, "num_attention_heads": 32,
        "num_key_value_heads": 4, "moe_intermediate_size": 768,
        "intermediate_size": 6144, "num_experts": 128,
        "num_experts_per_tok": 8, "norm_topk_prob": True,
        "vocab_size": 151936, "tie_word_embeddings": False,
        "rope_theta": 1000000, "rms_norm_eps": 1e-6,
        "decoder_sparse_step": 1, "mlp_only_layers": [],
        "attention_bias": False}
    for key, value in published.items():
        assert config[key] == value, key
    assert config["diffusion_block_length"] == 4
    assert config["mask_token_id"] == 151669 < config["vocab_size"]
    assert {"diffusion_block_length", "schedule", "mask_token_id",
            "qk_norm", "weights", "latent_capture"} <= \
        set(config["assumed"])
    assert "pipeline" in config["stands_for"]
    assert config["runner"] == "serve_diffusion" and config["chips"] == 1
    from hcache_deepspeed_tpu.inference.factory import MODEL_FAMILIES
    from benchmarks.runners.serve import hf_config
    cfg = MODEL_FAMILIES["sdar_moe"](hf_config(config))
    assert (cfg.head_dim, cfg.n_head * cfg.head_dim) == (128, 4096)
    assert (cfg.num_experts, cfg.top_k, cfg.intermediate_size) == \
        (128, 8, 768)
    assert cfg.qk_norm and cfg.diffusion_block_length == 4


def test_bytes_by_hand():
    config = _config()
    dep = config["deployment"]
    h, v = config["hidden_size"], config["vocab_size"]
    experts = 128 * 3 * h * config["moe_intermediate_size"]
    attention = 2 * h * 4096 + 2 * h * 512
    assert experts == 603_979_776 and attention == 18_874_368
    layer = 2 * (experts + attention + 2 * 128 + 2 * h) + 4 * h * 128
    assert round(layer / 1e9, 3) == 1.247
    vocab = 2 * 2 * v * h
    assert round(vocab / 1e9, 3) == 1.245
    weights_gb = (config["num_hidden_layers"] * layer + vocab + 2 * h) / 1e9
    assert round(weights_gb, 2) == 8.73
    kv_token = 2 * config["num_hidden_layers"] * 4 * 128 * 2
    assert kv_token == 12_288
    assert 2 * kv_token == config["num_hidden_layers"] * h * 2 == 24_576
    pool_gb = dep["num_blocks"] * dep["block_size"] * kv_token / 1e9
    assert dep["num_blocks"] * dep["block_size"] == 262_144
    assert round(pool_gb, 2) == 3.22
    assert 11.9 < weights_gb + pool_gb < 12.0       # of the chip's 16 GB
    assert dep["max_context"] == 2048 + 256 == \
        config["max_position_embeddings"]
    # a forward: one 512-token slice beside 128 lanes of 4 positions
    assert dep["max_ragged_batch_size"] == 512 + 128 * 4
    assert dep["max_tracked_sequences"] == 128
    assert dep["prefill_chunk"] % config["diffusion_block_length"] == 0


def test_traffic_is_the_mix_the_issue_gives():
    traffic = contract.load_traffic(CELL["traffic"])
    assert traffic["kind"] == "paced"
    assert traffic["prompt_tokens"] == {
        "dist": "lognormal", "median": 512, "sigma": 0.6, "min": 64,
        "max": 2048}
    assert traffic["output_tokens"] == {"dist": "uniform", "min": 256,
                                        "max": 256}
    assert (traffic["block"], traffic["jitter"]) == (25, 0.1)
    assert "rate_found" in traffic and "ramp_found" in traffic
    arrivals = paced.schedule(traffic, 2 ** 31 + 5, 50.0, 151669, 2304)
    window = [a for a in arrivals if a.in_window]
    assert len(window) == int(traffic["rate"] * 50.0) > 100
    assert {a.max_new_tokens for a in arrivals} == {256}
    assert max(max(a.prompt) for a in window[:50]) < 151669
    lengths = sorted(len(a.prompt) for a in window[:25])
    # every block of 25 holds the same lengths, one of them past the
    # 1,536 tokens the check's long request needs
    assert lengths == sorted(len(a.prompt) for a in window[25:50])
    assert lengths[0] >= 64 and 1536 < lengths[-1] <= 2048
    half = traffic["ramp_s"] + 25.0
    probed = serve_diffusion.pick_probed(arrivals, half)
    assert len(arrivals[probed["long"]].prompt) == lengths[-1]
    assert len(arrivals[probed["short"]].prompt) == lengths[0]
    # the warm-up reaches every slice bucket that the 25 lengths' whole
    # blocks reach (whole chunks and each prompt's tail) and every bucket
    # of lanes
    prefill, lanes = serve_diffusion.warm_plan(
        traffic, _config()["deployment"], 4)
    assert {n % 4 for _, n in prefill} == {0}
    assert {serve_diffusion._bucket(n, 8) for _, n in prefill} == \
        {serve_diffusion._bucket(piece, 8) for n in lengths
         for piece in (min(n // 4 * 4, 512), n // 4 * 4 % 512) if piece}
    assert lanes == [5, 9, 17, 33, 65]


@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_new_metric_files_name_their_kind_and_their_cell_alone(name):
    spec = contract.load_metric_specs()[name]
    assert spec["cells"] == {"runner": "serve_diffusion"}
    assert contract.metric_applies(spec, CELL, "serve_diffusion")
    assert not contract.metric_applies(spec, CELL, "serve")
    entry = _by_name(BENCH["per_layer"])[name]
    for key in ("unit", "better", "layer", "moves", "source"):
        assert spec[key] == entry[key], key
    contract.load_kind("reducers", spec["reads"])
    if name.endswith("_roofline"):
        assert spec["unit"] == "%" and spec["counts_module"] == "flops_moe"
        for kernel in spec["kernels"]:
            assert callable(getattr(flops_moe, kernel["counts"]))
            # no quantifier in braces: the pattern goes through format_map
            assert layer_metrics.fill(kernel["pattern"], {}) == \
                kernel["pattern"]


def test_expert_counts_are_the_hand_arithmetic():
    # 128 lanes x 4 positions x 8 picks through experts of 2048 x 768,
    # 120 of the 128 touched
    got = flops_moe.expert_ffn_counts(rows=4096, touched=120, hidden=2048,
                                      width=768, itemsize=2)
    assert got["flops"] == 3 * 2 * 4096 * 2048 * 768 == 38_654_705_664
    assert got["bytes"] == (120 * 3 * 2048 * 768
                            + 3 * 4096 * (2048 + 768)) * 2
    # the weights are 94% of the bytes: the product is bound by reading
    # each touched expert once
    assert 120 * 3 * 2048 * 768 * 2 / got["bytes"] > 0.94
    assert flops_moe.touched_experts(0, 128) == 0
    assert flops_moe.touched_experts(64, 128) == pytest.approx(
        128 * (1 - (127 / 128) ** 64))
    assert 127.9 < flops_moe.touched_experts(4096, 128) <= 128


def test_paged_block_counts_are_the_hand_arithmetic():
    shape = dict(n_head=32, n_kv_head=4, head_dim=128, itemsize=2, block=4)
    # one lane of a block over 1,000 cached tokens and itself: every row
    # sees all 1,004
    got = flops_moe.paged_block_counts([1004], [4], **shape)
    assert got["flops"] == 4 * 32 * 128 * 4 * 1004
    assert got["bytes"] == 2 * 1004 * 4 * 128 * 2 + 2 * 4 * 32 * 128 * 2
    # a first slice of 512: row t sees (t // 4 + 1) * 4 columns
    seen = sum((t // 4 + 1) * 4 for t in range(512))
    got = flops_moe.paged_block_counts([512], [512], **shape)
    assert got["flops"] == 4 * 32 * 128 * seen
    # a second slice behind it sees the first whole
    got = flops_moe.paged_block_counts([1024], [512], **shape)
    assert got["flops"] == 4 * 32 * 128 * (seen + 512 * 512)
    two = flops_moe.paged_block_counts([1004, 504], [4, 4], **shape)
    assert two["flops"] == 4 * 32 * 128 * 4 * (1004 + 504)


def _made_up_trace():
    gemm = ('%gmm.3 = bf16[4096,768]{1,0} custom-call(...), '
            'custom_call_target="tpu_custom_call", frontend_attributes='
            '{hds_kernel="expert_gemm",hds_layer="expert_ffn",'
            'kernel_metadata={}}')
    sort = ('%fusion.9 = s32[4096]{0} fusion(...), kind=kLoop, '
            'frontend_attributes={hds_layer="expert_ffn"}')
    paged = ('%hds_paged_attention.2 = bf16[128,4,32,128]{3,2,1,0} '
             'custom-call(...), custom_call_target="tpu_custom_call", '
             'frontend_attributes={kernel_metadata={"hds_kernel":'
             '"paged_attention"}}')
    head = "%fusion.1 = f32[512,151936]{1,0} fusion(...)"
    ops = []
    at = 0.0
    for text, seconds in ((gemm, 0.004), (sort, 0.001), (paged, 0.002),
                          (head, 0.003)):
        ops.append(Op(text, xplane.label_of(text), at, at + seconds))
        at += seconds
    trace = Trace(chips={0: ops}, t_min=0.0, t_max=0.0125)
    for chip_ops in trace.chips.values():
        xplane.set_own_times(chip_ops)
    return xplane.reduce(trace)


def test_the_new_metrics_on_a_made_up_trace():
    reduction = _made_up_trace()
    evidence = {
        "trace": reduction, "device_kind": "TPU v5e",
        "series": {"block_lanes": [96, 100, 104]},
        "counters": {"tokens_per_forward": 4 / 3,
                     "fetch_bytes_per_token": 131.5,
                     "expert_load_imbalance": 1.25},
        "expert_gemm_calls": [dict(rows=4096, touched=128, hidden=2048,
                                   width=768, itemsize=2)],
        "paged_block_calls": [dict(context_lens=[1004] * 100,
                                   q_lens=[4] * 100, n_head=32,
                                   n_kv_head=4, head_dim=128, itemsize=2,
                                   block=4)]}
    got = layer_metrics.compute(CELL, "serve_diffusion", evidence)
    assert set(got) == NEW_METRICS
    assert got["block_lanes_mean"]["value"] == 100
    assert got["tokens_per_forward"]["value"] == pytest.approx(1.3333, 1e-3)
    assert got["fetch_bytes_per_token"] == {"value": 131.5,
                                            "unit": "B/token"}
    assert got["expert_load_imbalance"]["value"] == 1.25
    # the expert layer's two operations of the four: 5 of 10 ms busy
    assert got["moe_share"]["value"] == pytest.approx(50.0)
    least = (128 * 3 * 2048 * 768 + 3 * 4096 * 2816) * 2 / 819e9
    assert got["expert_gemm_roofline"]["value"] == pytest.approx(
        100 * least / 0.004)
    least = 100 * (2 * 1004 * 4 * 128 * 2 + 2 * 4 * 32 * 128 * 2) / 819e9
    assert got["paged_block_roofline"]["value"] == pytest.approx(
        100 * least / 0.002)
    assert all(m["value"] <= 100 for name, m in got.items()
               if name.endswith("_roofline"))
    # a program without the attributes (the parent's): nothing to read,
    # nothing raised, the metrics leave the line
    bare = dict(evidence, trace=xplane.reduce(Trace(
        chips={0: [Op("%fusion.1 = f32[8]{0} fusion()", "fusion_f32_8_",
                      0.0, 0.001, 0.001)]}, t_min=0.0, t_max=0.002)))
    got = layer_metrics.compute(CELL, "serve_diffusion", bare)
    assert "moe_share" in got and got["moe_share"]["value"] == 0
    assert "expert_gemm_roofline" not in got
    assert "paged_block_roofline" not in got


def test_kernel_calls_count_blocks_and_slices():
    from hcache_deepspeed_tpu.models.sdar_moe import sdar_moe_tiny
    cfg = sdar_moe_tiny()               # 2 layers, 8 experts top-2
    steps = [{"block_ctx": [24, 40, 12], "slices": [(16, 16), (8, 24)],
              "touched": 13},
             {"block_ctx": [], "slices": [], "touched": 0}]
    calls = serve_diffusion.kernel_calls(steps, cfg)
    paged = calls["paged_block_calls"]
    assert len(paged) == (1 + 2) * 2            # a call a layer
    assert paged[0]["context_lens"] == [24, 40, 12]
    assert paged[0]["q_lens"] == [4, 4, 4] and paged[0]["block"] == 4
    assert paged[2]["context_lens"] == [16] and paged[2]["q_lens"] == [16]
    gemm = calls["expert_gemm_calls"]
    assert gemm[0]["rows"] == 3 * 4 * 2 * 2 and gemm[0]["touched"] == 13
    assert gemm[1]["rows"] == 16 * 2 * 2
    assert gemm[1]["touched"] == pytest.approx(
        2 * flops_moe.touched_experts(32, 8))
    assert (gemm[0]["hidden"], gemm[0]["width"]) == (64, 32)


# ------------------------------------------------------------------ #
# the runner at tiny size
# ------------------------------------------------------------------ #
def _load(name):
    with open(os.path.join(TINY, name)) as f:
        return json.load(f)


def test_stacked_layers_hold_what_the_seeded_tree_holds():
    from hcache_deepspeed_tpu.inference.factory import MODEL_FAMILIES
    from hcache_deepspeed_tpu.models.sdar_moe import SdarMoeForCausalLM
    from benchmarks.runners.serve import hf_config
    cfg = MODEL_FAMILIES["sdar_moe"](hf_config(_load("tiny-diffusion.json")))
    shapes = weights.param_shapes(
        SdarMoeForCausalLM(cfg), {"input_ids": np.zeros((1, 8), np.int32)})
    seed = 2 ** 31 + 7
    tree = weights.seeded_tree(shapes, seed, "bfloat16")
    stacked = serve_diffusion.stacked_layers(shapes, seed, "bfloat16", 2)
    for i in range(2):
        want = jax.tree.leaves(tree[f"layers_{i}"])
        got = jax.tree.leaves(jax.tree.map(lambda x: x[i], stacked))
        assert len(want) == len(got) == 12
        for a, b in zip(want, got):
            assert a.dtype == b.dtype and a.shape == b.shape
            np.testing.assert_array_equal(np.asarray(a, np.float32),
                                          np.asarray(b, np.float32))


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    ctx = Context(cell={"name": "tiny-diffusion", "chips": 1},
                  config=_load("tiny-diffusion.json"),
                  traffic=_load("tiny-blocks.json"), seed=2 ** 31 + 11,
                  seconds=4.0, trace=False, t_start=time.monotonic(),
                  root=str(tmp_path_factory.mktemp("serve_diffusion")),
                  meter=CompileMeter())
    kept = []

    def check(*args):               # what the check was handed, kept for
        kept.append(args)           # the controls below
        return serve_diffusion.check_passes(*args)

    return ctx, serve_diffusion.run(ctx, check=check), kept[0]


def test_diffusion_run_is_correct_and_counts_every_due_request(served):
    ctx, result, _ = served
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] == int(ctx.traffic["rate"] * ctx.seconds)
    assert set(result["metrics"]) == {"ttft_p90_s", "itl_mean_s",
                                      "serve_tok_s", "setup_s"}
    assert all(m["value"] > 0 for m in result["metrics"].values())
    for phase in ("weights", "engine", "warm", "ramp", "setup_s"):
        assert phase in ctx.phases


def test_the_check_compares_two_whole_blocks_a_probed_request(served):
    _, _, (ctx, built, rows, probed) = served
    assert {"short", "long"} <= set(probed)
    ok, details = serve_diffusion.check_passes(ctx, built, rows, probed)
    assert ok and not details["broken_chains"]
    later = serve_diffusion.LATER_BLOCK
    for kind, k in probed.items():
        req = rows[k]["req"]
        # its first block and one behind eight that this run committed,
        # every pass of each, in order
        assert [p.ordinal for p in req.probes] == sorted(
            p.ordinal for p in req.probes)
        first, last = req.probes[0], req.probes[-1]
        assert first.ordinal == 0 and last.ordinal >= later
        assert len(last.context) >= len(first.context) + 4 * later
        assert details[f"{kind}.0"]["masked"][-1] == 0      # the commit
    assert details["rows"] == 4 * sum(len(rows[k]["req"].probes)
                                      for k in probed.values())
    # a served token that is not the commit pass's breaks the chain
    req = rows[probed["short"]]["req"]
    req.tokens_out[1] += 1
    try:
        ok, details = serve_diffusion.check_passes(ctx, built, rows, probed)
    finally:
        req.tokens_out[1] -= 1
    assert not ok and details["broken_chains"] == ["short.0.tokens"]


@pytest.mark.parametrize("control", sorted(serve_diffusion.CONTROLS))
def test_every_control_comes_out_not_correct(served, control):
    _, _, (ctx, built, rows, probed) = served
    ok, details = serve_diffusion.check_passes(ctx, built, rows, probed,
                                               control=control)
    assert not ok and details["largest"] > serve_diffusion.LOGIT_TOL
