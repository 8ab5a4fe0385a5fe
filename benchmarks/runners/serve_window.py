"""``serve_window``: one paced-traffic cell through the serving main path
for a sparse trunk with window and global attention layers
(``model_type`` ``cohere2_moe``: Command A+, two block pools with two
block lifetimes, a parallel block, one chip's 16 of 128 sigmoid-routed
experts beside four averaged shared ones).

The same road as ``runners/serve.py`` and ``runners/serve_latent.py``
and everything of them that does not ask for their trunks
(``warm_engine``, ``offer``, ``window_numbers``, the step log, the
probed token callback, ``pick_probed``, ``stacked_layers``):
``MODEL_FAMILIES[...]`` -> ``build_hf_engine`` -> ``ServingServer`` in
thread mode, an open-loop generator on this process's main thread. Its
own:

* ``build``: the window layers and the global layer are made on the
  device a layer at a time into donated buffers, stacked by kind, and
  handed to the engine stacked: 9.47 GB of weights cannot be held twice.
* the check (``check_rows``): for eight requests (the shortest prompt,
  which never passes the window; the mix's longest, whose context
  passes 15k at the sigma of 0.45 the mix runs at; six between) the
  last prompt row (prefill through 5-31 slices, both pools, window
  blocks freed on the way) and a decode row
  32 tokens later, as the timed path made them, each against
  ``reference/cohere2_moe.py``'s full forward of the same tokens from
  the same bf16 leaves and the same 16 experts, the compared row routed
  from what the served routers read. ``tools/window_controls.py`` runs
  the same check against the reference computed wrong in twelve ways,
  each of which has to fail.
* the evidence: the two kernels' calls a pool, the held experts' rows
  and the held experts touched as each forward counted them, the pools'
  peaks and the blocks freed behind windows, and beside them
  what the ``serve`` kind and the expert layer's accepted metrics
  report.

Files of this cell (PR 56): ``configs/command-a-plus-serve-ep8.json``,
``traffic/mixed-length-24k.json``, this runner,
``reference/cohere2_moe.py``, ``flops_window.py``,
``tools/window_controls.py`` and ten metric files
(``window_attn_roofline``, ``global_attn_roofline``,
``kernel_share.window_attention``, ``held_expert_gemm_roofline``,
``expert_layer_share``, ``picks_held_share``, ``window_blocks_freed``,
``pool_peak_share.window``, ``pool_peak_share.global``,
``kv_pools_copy_share``). Its traced line also holds the metrics of the
files that name their cells by the kind ``serve`` and the expert
layer's (``moe_share``, ``expert_gemm_roofline``), undeclared, as the
other three serve runners' do.
"""

import functools
import gc
import time

import numpy as np

from .. import contract, layer_metrics, weights
from ..reference import cohere2_moe as reference
from ..stats import mean, percentile
from ..trace import xplane
from .common import TracedStretch, device_line, fallback_count
from .serve import (TRACE_S, StepLog, hf_config, offer, warm_engine,
                    window_numbers)
from .serve_latent import (GRACE_S, LATER_TOKEN, PROBE_SHARE, PROBED,
                           ProbedTokens, pick_probed, stacked_layers)

#: The served row is reached in bf16 weights and activations through
#: 5-42 prompt slices and decode steps over both pools; the reference in
#: float32 at "highest" precision in one pass with a dense mask, its
#: compared row routed from what the served routers read. Set between
#: two readings (my chip runs, PR 56; PERF.md section 4): the largest
#: row of the change, 0.0068-0.0136 in all but one of some fifty-five
#: runs and 0.0202 in that one (sixteen rows a run, medians
#: 0.0052-0.0063; rates 1.0-2.0/s, seeds over 2**31; the fourteen runs
#: since the reference draws every leaf from the seed 0.0068-0.0136;
#: the 0.0202 a short request's decode row, whose 32 generated
#: positions before it route by the reference's own stream), and the
#: served path against the reference
#: with its residual stream rounded after every layer to float8_e4m3,
#: the nearest precision below the bf16 the configuration states: 0.0530
#: and 0.0487 on two seeds (medians 0.035 and 0.037), which has to
#: fail. 0.032 is 1.58 times the first and 0.66 of the second; the
#: weakest of the other controls, a softmax router, reads 0.107.
LOGIT_TOL = 0.032
#: the reference computed wrong, one mechanism each
#: (``tools/window_controls.py``)
CONTROLS = {
    "window_dropped": {"sliding_window": None},
    "window_of_2048": {"sliding_window": 2048},
    "rotary_on_the_global_layer": {"rope_on_global": True},
    "half_split_on_unpermuted_columns": {"rope_pairing": "half_split"},
    "sequential_block": {"use_parallel_block": False},
    "softmax_router": {"expert_selection_fn": "softmax"},
    "weights_not_renormalised": {"norm_topk_prob": False},
    "shared_experts_summed": {
        "shared_expert_combination_strategy": "sum"},
    "shared_experts_dropped": {"num_shared_experts": 0},
    "wrong_experts_held": {"experts_held": [16, 16]},
    "dropped_eighth_pick": {"num_experts_per_tok": 7},
    "float8_e4m3_stream": {"stream_dtype": "float8_e4m3fn"},
}


class WindowStepLog(StepLog):
    """``StepLog`` that also keeps, at every step, the window blocks
    given back so far (``engine.kv_pool_stats()``) and which of the
    engine's forwards the step ran (``forwards``: from, to; rows of
    ``engine.moe_stats()["held_log"]``)."""

    def __init__(self, chunk, engine):
        super().__init__(chunk)
        self.engine = engine

    def on_step(self, report, scheduler):
        super().on_step(report, scheduler)
        before = self.steps[-2]["forwards"][1] if len(self.steps) > 1 \
            else 0
        self.steps[-1].update(
            released=self.engine.state.window_blocks_released,
            forwards=(before, self.engine.model.moe_dispatches))


def layer_tree(shapes, seed, dtype, i):
    """Layer ``i``'s subtree as the engine holds it: the reference's
    ``layer_params(i)``."""
    name = f"layers_{i}"
    return weights.seeded_tree(shapes, seed, dtype, only=(name,))[name]


def build(ctx):
    """Weights, engine and server for ``ctx.config``."""
    import jax

    from hcache_deepspeed_tpu.inference import RaggedInferenceEngineConfig
    from hcache_deepspeed_tpu.inference.factory import (MODEL_FAMILIES,
                                                        build_hf_engine)
    from hcache_deepspeed_tpu.models.cohere2_moe import param_shapes
    from hcache_deepspeed_tpu.serving import ServerConfig, ServingServer

    hf = hf_config(ctx.config)
    dep = ctx.config["deployment"]
    model_config = MODEL_FAMILIES[hf["model_type"]](hf)
    with ctx.phase("weights"):
        shapes = param_shapes(model_config)
        params = weights.seeded_tree(
            shapes, ctx.seed, hf["torch_dtype"],
            only=("embed_tokens", "norm"))
        for kind, name in (("sliding_attention", "window_layers"),
                           ("full_attention", "global_layers")):
            params[name] = stacked_layers(
                shapes, ctx.seed, hf["torch_dtype"],
                [i for i, k in enumerate(model_config.layer_types)
                 if k == kind])
        jax.block_until_ready(params)
    with ctx.phase("engine"):
        engine = build_hf_engine(hf, params, RaggedInferenceEngineConfig(
            state_manager={
                "max_tracked_sequences": dep["max_tracked_sequences"],
                "max_ragged_sequence_count":
                    dep["max_ragged_sequence_count"],
                "max_ragged_batch_size": dep["max_ragged_batch_size"],
                "max_context": dep["max_context"],
                "prefill_chunk": dep["prefill_chunk"]},
            kv_cache={"block_size": dep["block_size"],
                      "num_blocks": dep["num_blocks"],
                      "num_window_blocks": dep["num_window_blocks"],
                      "cache_dtype": hf["torch_dtype"]}))
        del params              # the engine holds the stacked leaves
        gc.collect()
    tokens = ProbedTokens(engine)
    steps = WindowStepLog(dep["prefill_chunk"], engine)
    server = ServingServer(
        engine, sample_fn=tokens, metrics=steps,
        config=ServerConfig(prefill_chunk=dep["prefill_chunk"]))
    return {"engine": engine, "server": server, "tokens": tokens,
            "steps": steps, "shapes": shapes, "hf": hf,
            "model_config": model_config, "vocab": model_config.vocab_size}


def check_rows(ctx, built, rows, probed, control=None):
    """The probed requests' two rows against the reference: the last
    prompt row and the row ``LATER_TOKEN`` output tokens later, each
    within ``LOGIT_TOL`` of the reference's full forward of the same
    tokens, the compared rows routed from what the served routers read.
    ``control``: a key of :data:`CONTROLS`, the reference computed
    wrong. Returns ``(ok, details)``."""
    hf, shapes, tokens = built["hf"], built["shapes"], built["tokens"]
    arch = {**hf, **(CONTROLS[control] if control else {})}
    # every leaf drawn again from the seed: nothing of the engine's
    # tree, so a fault in how it loads, slices or ties one shows
    outer = weights.seeded_tree(shapes, ctx.seed, hf["torch_dtype"],
                                only=("embed_tokens", "norm"))
    layer = functools.partial(layer_tree, shapes, ctx.seed,
                              hf["torch_dtype"])
    details, gaps, longest, shortest = {}, [], 0, None
    for kind, k in sorted(probed.items()):
        req = rows[k]["req"] if k < len(rows) else None
        kept = tokens.rows.get(req.uid, {}) if req is not None else {}
        if set(kept) != {0, LATER_TOKEN} or \
                any(read is None for _, read in kept.values()):
            return False, {"reason": f"the {kind} request has not both of "
                           f"its rows to compare (has {sorted(kept)})"}
        n = len(req.prompt)
        seq = list(req.prompt) + list(req.tokens_out[:LATER_TOKEN])
        at = [n - 1, n - 1 + LATER_TOKEN]
        ref = np.asarray(reference.logits(
            seq, arch, outer, layer, at,
            route_from={p: kept[j][1]
                        for p, j in zip(at, (0, LATER_TOKEN))}))
        pair = [reference.logit_gap(kept[j][0], ref[i])
                for i, j in enumerate((0, LATER_TOKEN))]
        gaps += pair
        longest = max(longest, len(seq))
        shortest = len(seq) if shortest is None else min(shortest, len(seq))
        details[kind] = {"context_tokens": len(seq),
                         "row_gaps": [round(g, 5) for g in pair]}
    details["rows"] = len(gaps)
    details["largest"] = round(max(gaps, default=float("nan")), 5)
    details["median"] = round(float(np.median(gaps)), 5) if gaps else None
    gen = contract.load_kind("generators", ctx.traffic["kind"])
    floor = max(gen.quantile_lengths(ctx.traffic["prompt_tokens"],
                                     int(ctx.traffic["block"])))
    if len(probed) < PROBED or longest < floor + LATER_TOKEN or \
            shortest >= hf["sliding_window"]:
        return False, {"reason": f"{len(probed)} requests compared, "
                       f"contexts of {shortest} to {longest} tokens: not "
                       f"{PROBED} with the mix's longest prompt ({floor}) "
                       f"and one inside the window among them", **details}
    ok = bool(np.isfinite(gaps).all()) and max(gaps) <= LOGIT_TOL
    return ok, details


def kernel_calls(steps, cfg, held_log):
    """The kernels' calls in ``steps`` as keyword arguments of the
    counting functions. Attention, from the step log: per step one
    decode dispatch over the lanes and one call a prompt slice (a
    one-token slice of a sequence with a context rides the decode
    dispatch), the window kernel once a window layer and the causal one
    once a global layer, a call a lane group. The grouped products run
    once a forward over all its rows, however many lane groups it
    carries: a call a forward of the step (``forwards``), its rows
    those that fell on held experts and its experts the held ones those
    rows touched, layer by layer, as that forward counted them on the
    device (``held_log``: ``engine.moe_stats()``)."""
    kinds = list(cfg.layer_types)
    n_window = kinds.count("sliding_attention")
    n_global = len(kinds) - n_window
    head = dict(n_head=cfg.n_head, n_kv_head=cfg.n_kv_head,
                head_dim=cfg.head_dim, itemsize=2)
    ffn = dict(hidden=cfg.hidden_size, width=cfg.intermediate_size,
               itemsize=2)
    calls = {"window_calls": [], "global_calls": [],
             "held_expert_calls": []}

    def attention(contexts, qs):
        calls["window_calls"] += [dict(
            head, window=cfg.sliding_window, context_lens=contexts,
            q_lens=qs)] * n_window
        calls["global_calls"] += [dict(
            head, context_lens=contexts, q_lens=qs)] * n_global

    for step in steps:
        contexts = list(step["decode_ctx"]) + \
            [end for q, end in step["slices"] if q == 1 and end > 1]
        if contexts:
            attention(contexts, [1] * len(contexts))
        for q, end in step["slices"]:
            if not (q == 1 and end > 1):
                attention([end], [q])
        calls["held_expert_calls"] += [
            dict(ffn, rows=int(rows), touched=int(touched))
            for rows, touched in held_log[slice(*step["forwards"])]]
    return calls


def run(ctx, check=check_rows):
    import jax

    from hcache_deepspeed_tpu.telemetry.tracer import get_tracer

    dep = ctx.config["deployment"]
    fallbacks_before = fallback_count()     # this run's, not the process's
    built = build(ctx)
    engine, server = built["engine"], built["server"]
    with ctx.phase("warm"):
        warmed = warm_engine(engine, ctx.traffic, dep, built["vocab"])
    # the peaks are the window's, not the warm-up's (which fills every
    # tracked sequence's lane)
    for alloc in (engine.state.allocator, engine.state.window_allocator):
        alloc.peak_in_use = alloc.num_blocks - alloc.free_blocks
    gen = contract.load_kind("generators", ctx.traffic["kind"])
    arrivals = gen.schedule(ctx.traffic, ctx.seed, ctx.seconds,
                            built["vocab"], dep["max_context"])
    probed = pick_probed(arrivals, ctx.traffic["ramp_s"] +
                         PROBE_SHARE * ctx.seconds)
    # uids are handed out in submit order, from 0: arrival k is uid k
    built["tokens"].probed = set(probed.values())
    engine.router_probe_uids = set(probed.values())
    setup_compiles = ctx.meter.take()
    if ctx.trace:
        get_tracer().configure(enabled=True)

    server.start()
    t0 = time.monotonic()
    t_open = t0 + ctx.traffic["ramp_s"]
    t_close = t_open + ctx.seconds
    stretch = None
    if ctx.trace:
        stretch = TracedStretch(ctx.root, ctx.cell["name"])
        stretch.run(t_open + 1.0, t_open + 1.0 + min(TRACE_S,
                                                     ctx.seconds - 1.0))
    ctx.phases["ramp"] = round(ctx.traffic["ramp_s"], 3)
    ctx.phases["setup_s"] = t_open - ctx.t_start
    try:
        rows = offer(server, arrivals, t0)
        time.sleep(max(0.0, t_close - time.monotonic()))
        time.sleep(GRACE_S)
        t_grace = time.monotonic()
        for row in rows:
            if not row["req"].finished:
                server.cancel(row["req"].uid)
    finally:
        server.stop(drain=True, timeout=60.0)
    if server.error is not None:
        raise server.error
    if stretch is not None:
        stretch.join()

    nums = window_numbers(rows, built["tokens"].stamps, t_open, t_close,
                          t_grace)
    pools = engine.kv_pool_stats()
    # a block of either pool that is still out, beside its scratch block
    leaked = {name: pool["in_use"] - 1 for name, pool in pools.items()}
    faults = server.scheduler.fault_summary()
    in_window = [s for s in built["steps"].steps
                 if t_open <= s["t"] < t_close]
    compiles = {"ramp": ctx.meter.between(t0, t_open),
                "window": ctx.meter.between(t_open, t_close)}
    fallbacks = fallback_count() - fallbacks_before
    latents = engine.latent_stats()
    moe = engine.moe_stats()
    dispatched = engine.dispatch_stats()
    served_peak = device_line(jax.devices(), ctx.cell["chips"])[
        "memory_peak_bytes"]        # before the check's reference
    # the pools and the served weights are the check's room: nothing
    # reads them after the window, the reference draws every leaf again
    # from the seed, and the process's peak stays the served one
    engine.cache.replace(None, None)
    engine.cache.replace_window(None, None)
    engine.model.params = None
    gc.collect()
    rows_ok, row_details = check(ctx, built, rows, probed)
    # beside what ``runners/serve.py`` asks: the window ran the kernels
    # (a fallback would be timed as the cell), built no program, and
    # left no block of either pool out
    correct = bool(rows_ok and not any(leaked.values()) and
                   faults["total_faults"] == 0 and nums["failed"] == 0 and
                   nums["attempted"] == sum(1 for a in arrivals
                                            if a.in_window) and
                   fallbacks == 0 and compiles["window"] == 0)
    freed = in_window[-1]["released"] - in_window[0]["released"] \
        if len(in_window) > 1 else 0
    print(f"check: rows {row_details}, limit {LOGIT_TOL}, leaked_blocks "
          f"{leaked}, faults {faults['total_faults']}, fallbacks "
          f"{fallbacks}, saved state {latents['saved_state']}, programs "
          f"built or fetched {compiles}, preempted "
          f"{sum(s['preempted'] for s in in_window)}, restores "
          f"{engine.restore_stats['restores']}, pools {pools}, window "
          f"blocks freed in window {freed}, picks held "
          f"{moe['picks_held']} of {int(moe['picks'].sum())}, held "
          f"experts touched {moe['touched']} in {moe['dispatches']} "
          f"forwards, dispatches {dispatched}, peak bytes "
          f"before the check {served_peak}, steps in "
          f"window {len(in_window)}, longest "
          f"""{max((b['t'] - a['t'] for a, b in
                    zip(in_window, in_window[1:])), default=0.0):.3f} s""",
          flush=True)

    devices = jax.devices()
    result = {"correct": correct, "attempted": nums["attempted"],
              "failed": nums["failed"],
              "device": device_line(devices, ctx.cell["chips"])}
    end_to_end = {
        "ttft_p90_s": (percentile(nums["ttft"], 90), "s"),
        "itl_mean_s": (mean(nums["gaps"]), "s"),
        "serve_tok_s": (nums["tokens_in_window"] / ctx.seconds, "tokens/s"),
        "setup_s": (ctx.phases["setup_s"], "s")}
    ctx.phases.update(warmed, programs=setup_compiles["programs"],
                      cache_hits=setup_compiles["cache_hits"],
                      compile_or_fetch_s=setup_compiles["seconds"])
    if not ctx.trace:
        result["metrics"] = {k: {"value": v, "unit": u}
                             for k, (v, u) in end_to_end.items()
                             if v is not None}
        return result

    traced = [s for s in built["steps"].steps
              if stretch.t_begin <= s["t"] < stretch.t_end]
    reduction = xplane.reduce_file(stretch.path)
    cfg = built["model_config"]
    block = dep["block_size"]
    g_slots = dep["num_blocks"] * block
    w_slots = pools["window"]["blocks"] * block
    picks_all = int(moe["picks"].sum())
    held_share = moe["picks_held"] / picks_all if picks_all else 0.0
    evidence = {
        "series": {
            "gen_late_s": nums["late"], "submit_wait_s": nums["submit_wait"],
            "itl_s": nums["gaps"], "ttft_s": nums["ttft"],
            "queue_wait_s": [r["req"].queue_wait() for r in rows
                             if t_open <= r["due"] < t_close and
                             r["req"].queue_wait() is not None],
            "decode_lanes": [s["lanes"] for s in in_window if s["lanes"]]},
        "counters": {
            "preemptions": sum(s["preempted"] for s in in_window),
            "compiles_in_window": compiles["window"],
            "restores": engine.restore_stats["restores"],
            "restore_mb": engine.restore_stats["bytes_shipped"] / 1e6,
            "fallbacks": fallbacks,
            "picks_held_share": 100.0 * held_share,
            "window_blocks_freed": freed,
            "pool_peak_share.window": 100.0 * pools["window"]["peak_in_use"]
            / pools["window"]["blocks"],
            "pool_peak_share.global": 100.0 * pools["global"]["peak_in_use"]
            / pools["global"]["blocks"]},
        "memory": {"peak_bytes": result["device"]["memory_peak_bytes"]},
        "trace": reduction,
        "device_kind": devices[0].device_kind,
        "arch": built["hf"],
        "placeholders": {
            # the accepted ``serve`` kind's readers see the global pool
            "kv_pool": f"{g_slots}_{cfg.head_dim}_",
            "kv_blocks": f"{cfg.n_kv_head},{dep['num_blocks']},{block},"
                         f"{cfg.head_dim}",
            "g_pool": f"{g_slots}_{cfg.head_dim}_",
            "w_pool": f"{w_slots}_{cfg.head_dim}_"},
    }
    evidence.update(kernel_calls(traced, cfg, moe["held_log"]))
    evidence["paged_calls"] = evidence["global_calls"]
    evidence["expert_gemm_calls"] = evidence["held_expert_calls"]
    # the serve cells' metrics (files that name their cells by the kind
    # "serve"), the expert layer's and this kind's own
    accepted_moe = {name: value for name, value in layer_metrics.compute(
        ctx.cell, "serve_diffusion", evidence).items()
        if name in ("moe_share", "expert_gemm_roofline")}
    result["metrics"] = {
        **layer_metrics.compute(ctx.cell, "serve", evidence), **accepted_moe,
        **layer_metrics.compute(ctx.cell, ctx.config["runner"], evidence)}
    result["device"].update(busy_s=reduction.busy_s,
                            window_s=reduction.window_s)
    result["breakdown"] = reduction.breakdown()
    return result
