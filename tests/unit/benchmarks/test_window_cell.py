"""The files of the cell ``cmdaplus-serve-mixed-length`` (PR 56): its
configuration against the catalog row, its bytes by hand, its traffic,
the counts of ``flops_window.py`` against hand arithmetic, the ten new
metrics on a made-up trace, and the ``serve_window`` runner at tiny size
on the CPU (the command itself refuses to measure there). Entries of
``BENCHMARK.json`` are held by name, never by position."""

import json
import math
import os
import time

import numpy as np
import pytest

from benchmarks import contract, flops, flops_moe, flops_window, layer_metrics
from benchmarks.compile_meter import CompileMeter
from benchmarks.generators import paced
from benchmarks.reference import cohere2_moe as reference
from benchmarks.runners import serve_window
from benchmarks.runners.common import Context
from benchmarks.trace import xplane
from benchmarks.trace.xplane import Op, Trace

CELL = {"name": "cmdaplus-serve-mixed-length",
        "config": "command-a-plus-serve-ep8",
        "traffic": "mixed-length-24k", "chips": 1}
SOURCE = ("https://huggingface.co/CohereLabs/command-a-plus-05-2026/blob/"
          "main/config.json")
REDUCED = ["layer_types", "max_position_embeddings", "num_experts",
           "num_hidden_layers", "vocab_size"]
BENCH = contract.load_benchmark()
TINY = os.path.join(os.path.dirname(__file__), "tiny")
NEW_METRICS = {
    "window_attn_roofline": "itl_mean_s",
    "global_attn_roofline": "ttft_p90_s",
    "kernel_share.window_attention": "itl_mean_s",
    "held_expert_gemm_roofline": "itl_mean_s",
    "expert_layer_share": "itl_mean_s",
    "picks_held_share": "serve_tok_s",
    "window_blocks_freed": "serve_tok_s",
    "pool_peak_share.window": "serve_tok_s",
    "pool_peak_share.global": "serve_tok_s",
    "kv_pools_copy_share": "itl_mean_s"}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def _config():
    return contract.load_config(BENCH, CELL["config"])


def _by_name(entries):
    return {e["name"]: e for e in entries}


def _load(name):
    with open(os.path.join(TINY, name)) as f:
        return json.load(f)


def test_benchmark_declares_the_configuration_and_the_cell():
    entry = _by_name(BENCH["configs"])[CELL["config"]]
    assert entry["source"] == SOURCE
    assert entry["file"] == "benchmarks/configs/command-a-plus-serve-ep8.json"
    assert sorted(entry["reduced"]) == REDUCED
    cell = _by_name(BENCH["workloads"])[CELL["name"]]
    assert {k: cell[k] for k in CELL} == CELL
    assert len(cell["why"]) <= 200
    # at the ends of their lists, and in the three serve lists
    assert BENCH["configs"][-1]["name"] == CELL["config"]
    assert BENCH["workloads"][-1]["name"] == CELL["name"]
    e2e = _by_name(BENCH["end_to_end"])
    for name in ("ttft_p90_s", "itl_mean_s", "serve_tok_s"):
        assert e2e[name]["workloads"][-1] == CELL["name"]
    assert CELL["name"] not in e2e["train_tok_s_chip"]["workloads"]
    per_layer = _by_name(BENCH["per_layer"])
    assert [m["name"] for m in BENCH["per_layer"][-10:]] == \
        list(NEW_METRICS)
    for name, moves in NEW_METRICS.items():
        assert per_layer[name]["workloads"] == [CELL["name"]]
        assert per_layer[name]["moves"] == moves
    # no accepted metric was given the new cell
    for m in BENCH["per_layer"][:-10]:
        assert CELL["name"] not in m["workloads"]
    config = _config()
    assert config["runner"] == "serve_window" and config["chips"] == 1
    assert sorted(config["reduced"]) == REDUCED


@pytest.mark.skipif(not os.path.exists(CATALOG),
                    reason="the catalog is not on this machine")
def test_configuration_differs_from_the_catalog_only_where_it_says():
    with open(CATALOG) as f:
        rows = [json.loads(line) for line in f]
    (row,) = [r for r in rows if r["source_url"] == SOURCE]
    config = _config()
    differs = sorted(k for k, v in row["config"].items()
                     if config.get(k, "missing") != v)
    assert differs == ["layer_types", "max_position_embeddings",
                       "num_hidden_layers", "vocab_size"]
    # the router's width stays the published one; the cut is what is held
    assert config["num_experts"] == row["config"]["num_experts"] == 128
    assert config["experts_held"] == [0, 16]
    assert config["layer_types"] == row["config"]["layer_types"][:4]
    assert config["num_hidden_layers"] == 4 == row["config"]["layer_switch"]


def test_configuration_keeps_every_published_width():
    config = _config()
    for key, value in {
            "hidden_size": 4096, "num_attention_heads": 128,
            "num_key_value_heads": 8, "head_dim": 128,
            "intermediate_size": 4096, "num_experts": 128,
            "num_experts_per_tok": 8, "num_shared_experts": 4,
            "sliding_window": 4096, "rope_theta": 50000,
            "layer_norm_eps": 1e-5, "logit_scale": 1,
            "tie_word_embeddings": True, "use_parallel_block": True,
            "expert_selection_fn": "sigmoid",
            "shared_expert_combination_strategy": "average",
            "first_k_dense_replace": 0}.items():
        assert config[key] == value, key
    # inside the floors: a whole period of at least four layers, at
    # least eight experts, at least an eighth of the vocabulary
    assert config["num_hidden_layers"] % 4 == 0
    assert config["experts_held"][1] >= 8
    assert config["vocab_size"] * 8 >= 262144
    for key in ("assumed", "stands_for", "deployment"):
        assert key in config
    for key in ("shared_experts", "expert_width", "layer_norm", "window",
                "router", "rotary_pairing", "weights", "cache",
                "latent_capture", "modality"):
        assert key in config["assumed"], key
    from hcache_deepspeed_tpu.inference.factory import MODEL_FAMILIES
    cfg = MODEL_FAMILIES["cohere2_moe"](serve_window.hf_config(config))
    assert cfg.held == (0, 16) and cfg.num_experts == 128
    assert cfg.period == ("sliding_attention",) * 3 + ("full_attention",)


def test_bytes_by_hand():
    """The arithmetic of the file's ``bytes_by_hand``, from the shapes
    the program builds."""
    import jax
    from hcache_deepspeed_tpu.inference.factory import MODEL_FAMILIES
    from hcache_deepspeed_tpu.models.cohere2_moe import param_shapes
    config = _config()
    dep = config["deployment"]
    cfg = MODEL_FAMILIES["cohere2_moe"](serve_window.hf_config(config))
    shapes = param_shapes(cfg)
    sizes = {k: sum(int(np.prod(x.shape)) for x in jax.tree.leaves(v))
             for k, v in shapes.items()}
    layer = sizes["layers_0"]
    experts = 16 * 3 * 4096 * 4096
    assert experts == 805_306_368
    assert layer - experts == 142_606_336 + 201_326_592 + 524_288 + 4096
    assert round(layer / 1e6, 1) == 1149.8
    assert sizes["embed_tokens"] == 32768 * 4096 == 134_217_728
    weights_gb = (4 * layer + sizes["embed_tokens"]) * 2 / 1e9
    assert round(weights_gb, 2) == 9.47
    token_layer = 2 * 8 * 128 * 2
    assert token_layer == 4096
    global_gb = dep["num_blocks"] * dep["block_size"] * token_layer / 1e9
    window_gb = dep["num_window_blocks"] * dep["block_size"] * 3 * \
        token_layer / 1e9
    assert (round(global_gb, 2), round(window_gb, 2)) == (1.61, 1.84)
    per_seq = (4096 + dep["prefill_chunk"]) // dep["block_size"] + 1
    assert per_seq == 73
    assert dep["num_window_blocks"] == dep["max_tracked_sequences"] * 73
    assert 12.0 < weights_gb + global_gb + window_gb < 14.0
    # the window layers keeping every block would not fit
    assert round(3 * global_gb, 2) == 4.83
    # one layer's routed experts whole: no chip holds them
    assert 128 * 3 * 4096 * 4096 * 2 / 1e9 > 12.8


def test_traffic_is_the_mix_the_issue_gives():
    traffic = contract.load_traffic(CELL["traffic"])
    assert traffic["kind"] == "paced" and traffic["name"] == CELL["traffic"]
    assert traffic["prompt_tokens"] == {
        "dist": "lognormal", "median": 6144, "sigma": 0.45, "min": 1024,
        "max": 24576}      # sigma 0.45: ISSUE 56 (k)'s remedy, taken
    assert traffic["output_tokens"] == {
        "dist": "lognormal", "median": 192, "sigma": 0.5, "min": 32,
        "max": 512}
    assert (traffic["block"], traffic["jitter"], traffic["shuffle"],
            traffic["priority"]) == (25, 0.1, 5, 0)
    lengths = paced.quantile_lengths(traffic["prompt_tokens"], 25)
    under = [n for n in lengths if n < 4096]
    assert (len(under), under[0], under[-1]) == (5, 2438, 4070)
    assert (lengths[-1], lengths[-3]) == (15482, 10937)
    assert round(sum(lengths) / 25) == 6760
    # what ISSUE 56 (k) reckoned, at the sigma of 0.6 it starts from
    wider = paced.quantile_lengths(
        dict(traffic["prompt_tokens"], sigma=0.6), 25)
    assert (sum(n < 4096 for n in wider), wider[-1], wider[-3],
            round(sum(wider) / 25)) == (6, 21067, 13255, 7275)
    assert lengths[-1] + 512 <= _config()["deployment"]["max_context"]
    # 0.8 of the highest rate of the chip sweep with no failed request
    # (1.75/s, at sigma 0.45 as at 0.6), to two figures: no rate with whole blocks of 25 in the
    # window lies within 0.7-0.85 of it; 50 requests or more a window
    assert traffic["rate"] == round(0.8 * 1.75, 1)
    assert traffic["rate"] * BENCH["run_seconds"] >= 50
    assert not any(0.7 <= n * 25 / BENCH["run_seconds"] / 1.75 <= 0.85
                   for n in range(1, 6))
    outputs = paced.quantile_lengths(traffic["output_tokens"], 25)
    assert min(outputs) > serve_window.LATER_TOKEN
    # 1.2 x 512 output tokens at most x the time between tokens at
    # this rate (0.019341 s: PERF.md section 6), rounded up
    assert traffic["ramp_s"] == math.ceil(1.2 * 512 * 0.019341) == 12


@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_new_metric_files_name_their_kind_and_their_cell_alone(name):
    spec = contract.load_metric_specs()[name]
    assert spec["cells"] == {"runner": "serve_window"}
    assert contract.metric_applies(spec, CELL, "serve_window")
    for cell in BENCH["workloads"]:
        if cell["name"] != CELL["name"]:
            runner = contract.load_config(BENCH, cell["config"])["runner"]
            assert not contract.metric_applies(spec, cell, runner)
    if name.endswith("_roofline"):
        assert spec["unit"] == "%" and spec["reads"] == "roofline_counts"
        assert spec["counts_module"] in ("flops_window", "flops")


def test_window_counts_are_the_hand_arithmetic():
    head = dict(n_head=128, n_kv_head=8, head_dim=128, itemsize=2,
                window=4096)
    # a decode lane at 20,000: the window's 4,096 keys, not the context
    one = flops_window.window_attention_counts([20000], [1], **head)
    assert one["flops"] == 4 * 128 * 128 * 4096
    assert one["bytes"] == 2 * 4096 * 8 * 128 * 2 + 2 * 128 * 128 * 2
    # inside the window it is the causal count
    short = flops_window.window_attention_counts([3000], [1], **head)
    causal = flops.paged_attention_counts([3000], [1], 128, 8, 128, 2)
    assert short == causal
    # a 512-token slice ending at 8,192: every row sees 4,096 keys, and
    # the slice reads the 4,096 + 511 positions its rows see
    slice_ = flops_window.window_attention_counts([8192], [512], **head)
    assert slice_["flops"] == 4 * 128 * 128 * 512 * 4096
    assert slice_["bytes"] == 2 * 4607 * 8 * 128 * 2 + 2 * 512 * 128 * 128 * 2
    # a slice that crosses the window's edge: rows at 3,840-4,351
    assert flops_window.window_pairs(4352, 512, 4096) == \
        sum(min(p + 1, 4096) for p in range(3840, 4352))
    # a first slice is the causal triangle
    assert flops_window.window_pairs(512, 512, 4096) == 512 * 513 / 2
    # the global layer at the same slice does 2.5 times the work at 16k
    full = flops.paged_attention_counts([16384], [512], 128, 8, 128, 2)
    windowed = flops_window.window_attention_counts([16384], [512], **head)
    assert 3.8 < full["flops"] / windowed["flops"] < 4.0
    # the held experts: the accepted count over the rows that fell here
    assert flops_window.held_expert_counts(520, 16, 4096, 4096, 2) == \
        flops_moe.expert_ffn_counts(520, 16, 4096, 4096, 2)


def _made_up_trace(extra=()):
    """A traced stretch of six operations (25 ms busy) and ``extra``
    ones behind them."""
    window = ('%hds_window_attention.2 = bf16[1,8,8192,128]{3,2,1,0} '
              'custom-call(...), custom_call_target="tpu_custom_call", '
              'frontend_attributes={hds_kv_layer_view="bf16[8,2336,64,128]",'
              'kernel_metadata={"hds_kernel":"window_attention"}}')
    causal = ('%hds_paged_attention.3 = bf16[1,8,8192,128]{3,2,1,0} '
              'custom-call(...), custom_call_target="tpu_custom_call", '
              'frontend_attributes={hds_kv_layer_view="bf16[8,6144,64,128]",'
              'kernel_metadata={"hds_kernel":"paged_attention"}}')
    gemm = ('%gmm.3 = bf16[4224,4096]{1,0} custom-call(...), '
            'custom_call_target="tpu_custom_call", frontend_attributes='
            '{hds_kernel="expert_gemm",hds_layer="expert_ffn"}')
    shared = ('%fusion.9 = bf16[520,16384]{1,0} fusion(...), kind=kOutput, '
              'frontend_attributes={hds_layer="expert_ffn"}')
    qkv = "%fusion.4 = bf16[520,16384]{1,0} fusion(...), kind=kOutput"
    head = "%fusion.1 = f32[9,32768]{1,0} fusion(...)"
    ops, at = [], 0.0
    for text, seconds in ((window, 0.003), (causal, 0.004), (gemm, 0.010),
                          (shared, 0.006), (qkv, 0.001), (head, 0.001)) \
            + tuple(extra):
        ops.append(Op(text, xplane.label_of(text), at, at + seconds))
        at += seconds
    trace = Trace(chips={0: ops}, t_min=0.0, t_max=at + 0.005)
    for chip_ops in trace.chips.values():
        xplane.set_own_times(chip_ops)
    return xplane.reduce(trace)


def test_the_ten_new_metrics_on_a_made_up_trace():
    head = dict(n_head=128, n_kv_head=8, head_dim=128, itemsize=2)
    window_call = dict(head, window=4096, context_lens=[16384],
                       q_lens=[512])
    global_call = dict(head, context_lens=[16384], q_lens=[512])
    held_call = dict(hidden=4096, width=4096, itemsize=2, rows=520 * 4,
                     touched=64)
    evidence = {
        "trace": _made_up_trace(), "device_kind": "TPU v5e",
        "counters": {"picks_held_share": 12.4, "window_blocks_freed": 5400,
                     "pool_peak_share.window": 41.0,
                     "pool_peak_share.global": 57.5},
        "window_calls": [window_call], "global_calls": [global_call],
        "held_expert_calls": [held_call],
        "placeholders": {"g_pool": "393216_128_", "w_pool": "149504_128_"}}
    got = layer_metrics.compute(CELL, "serve_window", evidence)
    assert set(got) == set(NEW_METRICS)
    assert got["picks_held_share"] == {"value": 12.4, "unit": "%"}
    assert got["window_blocks_freed"] == {"value": 5400.0, "unit": "blocks"}
    assert got["pool_peak_share.window"]["value"] == 41.0
    assert got["pool_peak_share.global"]["value"] == 57.5
    # the window kernel 3 of 25 ms busy, the expert layer's two 16
    assert got["kernel_share.window_attention"]["value"] == \
        pytest.approx(12.0)
    assert got["expert_layer_share"]["value"] == pytest.approx(64.0)
    assert got["kv_pools_copy_share"]["value"] == 0.0
    for name, counts, seconds in (
            ("window_attn_roofline",
             flops_window.window_attention_counts(**window_call), 0.003),
            ("global_attn_roofline",
             flops.paged_attention_counts(**global_call), 0.004),
            ("held_expert_gemm_roofline",
             flops_window.held_expert_counts(**held_call), 0.010)):
        least = max(counts["flops"] / 197e12, counts["bytes"] / 819e9)
        assert got[name]["value"] == pytest.approx(100 * least / seconds)
        assert 0 < got[name]["value"] <= 100, name
    # a copy of either pool's extent is found by its label
    for dims in ("1,8,393216,128", "3,8,149504,128", "8,149504,128"):
        again = layer_metrics.compute(CELL, "serve_window", dict(
            evidence, trace=_made_up_trace(extra=((
                f"%copy.7 = bf16[{dims}]{{3,2,1,0}} copy(...)", 0.001),))))
        assert again["kv_pools_copy_share"]["value"] == pytest.approx(
            100 * 0.001 / 0.026), dims
    # a program without the attributes (the parent's): nothing to read,
    # nothing raised, the rooflines leave the line
    bare = dict(evidence, trace=xplane.reduce(Trace(
        chips={0: [Op("%fusion.1 = f32[8]{0} fusion()", "fusion_f32_8_",
                      0.0, 0.001, 0.001)]}, t_min=0.0, t_max=0.002)))
    got = layer_metrics.compute(CELL, "serve_window", bare)
    assert not any(name.endswith("_roofline") for name in got)
    assert got["kernel_share.window_attention"]["value"] == 0


def test_kernel_calls_count_lanes_slices_and_held_rows():
    from hcache_deepspeed_tpu.models.cohere2_moe import cohere2_moe_tiny
    cfg = cohere2_moe_tiny(n_layer=4, experts_held=(2, 4))
    # what five forwards counted on the device: rows on held experts,
    # held experts touched; the first two are the warm-up's
    held_log = np.array([[9, 9], [9, 9], [11, 7], [60, 16], [52, 15]])
    steps = [{"decode_ctx": [40, 70], "slices": [(16, 48), (1, 33)],
              "forwards": (2, 4)},
             {"decode_ctx": [], "slices": [(16, 16)], "forwards": (4, 5)}]
    calls = serve_window.kernel_calls(steps, cfg, held_log)
    # a step: one decode dispatch (the one-token slice of a sequence
    # with a context rides it) and one call a slice; three window layers
    # and one global
    assert len(calls["window_calls"]) == 3 * 3
    assert len(calls["global_calls"]) == 3
    assert calls["window_calls"][0]["context_lens"] == [40, 70, 33]
    assert calls["window_calls"][0]["window"] == cfg.sliding_window
    assert calls["global_calls"][1] == dict(
        n_head=8, n_kv_head=2, head_dim=16, itemsize=2, context_lens=[48],
        q_lens=[16])
    # the grouped products: a call a forward of the step, by that
    # forward's own counts
    ffn = dict(hidden=cfg.hidden_size, width=cfg.intermediate_size,
               itemsize=2)
    assert calls["held_expert_calls"] == [
        dict(ffn, rows=11, touched=7), dict(ffn, rows=60, touched=16),
        dict(ffn, rows=52, touched=15)]
    # a step of decode lanes and one slice in the chunk's bucket is one
    # forward: its products run once, over the rows of both, while each
    # lane group keeps its kernel call
    fused = serve_window.kernel_calls(
        [{"decode_ctx": [40, 70], "slices": [(400, 912)],
          "forwards": (3, 4)}], cfg, held_log)
    assert len(fused["window_calls"]) == 2 * 3
    assert fused["held_expert_calls"] == [dict(ffn, rows=60, touched=16)]


@pytest.fixture(scope="module", autouse=True)
def _small_reference_blocks():
    """The reference's steps at the size of these contexts (a few
    hundred tokens): it pads a context to whole blocks of queries, 1,024
    at the benchmark's sizes."""
    saved = reference._Q_BLOCK
    reference._Q_BLOCK = 256
    yield
    reference._Q_BLOCK = saved


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    ctx = Context(cell={"name": "tiny-window", "chips": 1},
                  config=_load("tiny-window.json"),
                  traffic=_load("tiny-long-doc.json"), seed=2 ** 31 + 11,
                  seconds=4.0, trace=False, t_start=time.monotonic(),
                  root=str(tmp_path_factory.mktemp("serve_window")),
                  meter=CompileMeter())
    kept = []

    def check(*args):               # what the check was handed, kept for
        kept.append(args)           # the controls below
        return serve_window.check_rows(*args)

    return ctx, serve_window.run(ctx, check=check), kept[0]


def test_window_run_is_correct_and_counts_every_due_request(served):
    ctx, result, _ = served
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] == int(ctx.traffic["rate"] * ctx.seconds)
    assert set(result["metrics"]) == {"ttft_p90_s", "itl_mean_s",
                                      "serve_tok_s", "setup_s"}
    assert all(m["value"] > 0 for m in result["metrics"].values())
    for phase in ("weights", "engine", "warm", "ramp", "setup_s"):
        assert phase in ctx.phases


def test_the_check_compares_two_rows_of_eight_requests(served):
    _, _, (ctx, built, rows, probed) = served
    assert len(probed) == serve_window.PROBED and \
        {"short", "long"} <= set(probed)
    ok, details = serve_window.check_rows(ctx, built, rows, probed)
    assert ok and details["rows"] == 2 * serve_window.PROBED
    assert details["largest"] < 1e-4            # float32 both sides
    # the short one never passes the window, the long one does
    window = ctx.config["sliding_window"]
    assert details["short"]["context_tokens"] < window < \
        details["long"]["context_tokens"] == 172 + 32
    engine = built["engine"]
    pools = engine.kv_pool_stats()
    assert pools["window"]["released"] > 0
    assert pools["window"]["in_use"] == pools["global"]["in_use"] == 1
    moe = engine.moe_stats()
    assert 0 < moe["picks_held"] < moe["picks"].sum()
    # every step names its forwards, and every forward left its counts
    steps = built["steps"].steps
    assert steps[-1]["forwards"][1] == moe["dispatches"] == \
        len(moe["held_log"])
    assert all(a["forwards"][1] == b["forwards"][0] <= b["forwards"][1]
               for a, b in zip(steps, steps[1:]))
    assert moe["held_log"][:, 0].sum() == moe["picks_held"]
    # each compared row came with what the four layers' routers read
    long = rows[probed["long"]]["req"]
    kept = built["tokens"].rows[long.uid]
    assert set(kept) == {0, serve_window.LATER_TOKEN}
    assert all(read.shape == (4, 64) for _, read in kept.values())
    # a row that is another request's fails
    other = rows[probed["short"]]["req"].uid
    swapped = dict(built["tokens"].rows)
    swapped[long.uid], swapped[other] = swapped[other], swapped[long.uid]
    built["tokens"].rows, saved = swapped, built["tokens"].rows
    try:
        ok, details = serve_window.check_rows(ctx, built, rows, probed)
    finally:
        built["tokens"].rows = saved
    assert not ok and details["largest"] > serve_window.LOGIT_TOL


@pytest.mark.parametrize("control", sorted(serve_window.CONTROLS))
def test_every_control_comes_out_not_correct(served, control):
    _, _, (ctx, built, rows, probed) = served
    # the shortest and the longest request's four rows tell every
    # control apart (the whole eight: the test above, and the chip's
    # ``tools/window_controls.py``)
    probed = {kind: probed[kind] for kind in ("short", "long")}
    # the configuration's own window and share, cut as the control cuts
    wrong = dict(serve_window.CONTROLS[control])
    if wrong.get("sliding_window"):
        wrong["sliding_window"] = ctx.config["sliding_window"] // 2
    if "experts_held" in wrong:
        wrong["experts_held"] = [0, 4]
    serve_window.CONTROLS[control], saved = wrong, \
        serve_window.CONTROLS[control]
    try:
        ok, details = serve_window.check_rows(ctx, built, rows, probed,
                                              control=control)
    finally:
        serve_window.CONTROLS[control] = saved
    assert not ok and details["largest"] > serve_window.LOGIT_TOL
