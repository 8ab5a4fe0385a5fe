"""``serve_latent``: one paced-traffic cell through the serving main path
for a latent-attention sparse trunk (``model_type`` ``glm4_moe_lite``:
GLM-4.7-Flash, a pool of compressed KV rows, 64 experts top-4 with a
shared one behind a leading dense layer).

The same road as ``runners/serve.py`` and everything of it that does not
ask for a llama trunk (``warm_engine``, ``offer``, ``window_numbers``,
the step log): ``MODEL_FAMILIES[...]`` -> ``build_hf_engine`` ->
``ServingServer`` in thread mode, an open-loop generator on this
process's main thread. Its own:

* ``build``: the two stacks (the leading dense layer, the sparse
  layers) are made on the device a layer at a time into donated buffers
  (``stacked_layers``: the keys and values of ``weights.seeded_tree``),
  each sparse layer's selection bias seeded small and not zero, and
  handed to the engine stacked: 7.79 GB of weights cannot be held twice.
* the token callback: a stamp a token and, for the probed requests, the
  logits row behind their first token and behind their 33rd with what
  the sparse layers' routers read for that row
  (``engine.router_inputs``).
* the check (``check_rows``): for eight requests (the shortest prompt,
  the mix's longest, whose context passes 18k tokens, six between) the
  last prompt row (prefill through 8-37 slices and the pool) and a decode
  row 32 tokens later (decode through the pool), as the timed path made
  them, each against ``reference/glm4_moe_lite.py``'s full forward of
  the same tokens from the same bf16 leaves in the published
  (up-projected) form, the compared row routed from what the served
  routers read. ``tools/latent_controls.py`` runs the same check
  against the reference computed wrong in nine ways, each of which has
  to fail.
* the evidence: the latent kernel's calls, the rows walked a step, the
  saved state's bytes a token, and beside them what the expert layer
  and the ``serve`` kind report.

Files of this cell (PR 44): ``configs/glm-4.7-flash-serve-1chip.json``,
``traffic/long-doc-32k.json``, this runner,
``reference/glm4_moe_lite.py``, ``flops_mla.py``,
``tools/latent_controls.py`` and six metric files
(``latent_attn_roofline``, ``kernel_share.latent_attention``,
``latent_attn_share``, ``latent_pool_copy_share``,
``saved_state_bytes_per_token``, ``latent_mb_read_per_step``). Its
traced line also holds the metrics of the files that name their cells by
the kind ``serve`` and the expert layer's (``moe_share``,
``expert_gemm_roofline``), undeclared, as ``serve_hybrid``'s and
``serve_diffusion``'s do.
"""

import functools
import gc
import time
import zlib

import numpy as np
from hcache_deepspeed_tpu.models.glm4_moe_lite import correction_bias

from .. import contract, flops_moe, layer_metrics, weights
from ..reference import glm4_moe_lite as reference
from ..stats import mean, percentile
from ..trace import xplane
from .common import TracedStretch, device_line, fallback_count
from .serve import (TRACE_S, StepLog, hf_config, offer, warm_engine,
                    window_numbers)

#: The served row is reached in bf16 weights and activations through
#: 8-45 prompt slices (8-37 since the mix's sigma is 0.4) and decode steps over the pool in the absorbed
#: form; the reference in float32 at "highest" precision in one pass in
#: the published form, its compared row routed from what the served
#: routers read. Set from two readings (my chip runs, PR 44; PERF.md
#: section 4): the largest row of the change, 0.0211 to 0.0257 over
#: forty-eight runs (sixteen rows a run, medians 0.018-0.021; rates
#: 0.7-1.75/s, seeds over 2**31), and the served path against the
#: reference with its residual stream rounded after every layer to
#: float8_e4m3, the nearest precision below the bf16 the configuration
#: states: 0.1022 and 0.1063 on two seeds (medians 0.093, every row
#: over the limit), which has to fail. 0.04 is 1.56 times the first and
#: 0.39 of the second; the weakest of the other controls, the selection
#: bias added to the weights, reads 0.077-0.079.
LOGIT_TOL = 0.04
#: seconds after the window for late first tokens before a request due
#: inside it counts as failed. ``runners/serve.py``'s 2 s would fail
#: every long prompt due in the window's last seconds whatever the
#: system does: a 32k prompt is 63 slices, 2.3 s of programs alone, and
#: below the knee the 90th percentile of the time to the first token is
#: 3-4 s (my chip runs, PR 44). Twice that.
GRACE_S = 8.0
#: requests the check compares (the longest of them the mix's longest
#: prompt) and the output token behind which the later row lies
PROBED = 8
LATER_TOKEN = 32
#: of the window, the part whose arrivals may be probed: their 33rd
#: token falls inside the run
PROBE_SHARE = 0.7
#: the reference computed wrong, one mechanism each
#: (``tools/latent_controls.py``)
CONTROLS = {
    "r_without_rotary": {"rope_r": False},
    "c_before_its_norm": {"norm_c": False},
    "scale_sqrt_192": {"softmax_scale_dim": 192},
    "softmax_router": {"scoring_func": "softmax"},
    "bias_in_the_weights": {"bias_in_weights": True},
    "no_scaling_factor": {"routed_scaling_factor": 1.0},
    "no_shared_expert": {"n_shared_experts": 0},
    "dropped_fourth_pick": {"num_experts_per_tok": 3},
    "float8_e4m3_stream": {"stream_dtype": "float8_e4m3fn"},
}


class ProbedTokens:
    """The server's token callback: greedy sampling, a stamp a token on
    the generator's clock, and for the probed requests the logits row
    behind their first and their ``LATER_TOKEN``-th later token with the
    sparse routers' inputs of that row."""

    def __init__(self, engine):
        self.engine = engine
        self.stamps = {}        # uid -> [t of each output token]
        self.probed = set()     # uids
        self.rows = {}          # uid -> {token index: (row, router_in)}

    def __call__(self, req, row):
        t = time.monotonic()
        token = int(np.argmax(row))
        self.stamps.setdefault(req.uid, []).append(t)
        at = len(req.tokens_out)
        if req.uid in self.probed and at in (0, LATER_TOKEN):
            self.rows.setdefault(req.uid, {})[at] = (
                np.array(row, np.float32),
                self.engine.router_inputs(req.uid))
        return token


class LatentStepLog(StepLog):
    """``StepLog`` that also keeps the blocks walked so far
    (``engine.paged_walk_stats()``) at every step."""

    def __init__(self, chunk, engine):
        super().__init__(chunk)
        self.engine = engine

    def on_step(self, report, scheduler):
        super().on_step(report, scheduler)
        self.steps[-1]["walked"] = \
            self.engine.paged_walk_stats()["blocks_walked"]


def stacked_layers(shapes, seed, dtype, layers):
    """The ``layers_<i>`` subtrees of ``shapes`` for ``i`` in ``layers``
    (all of one kind) as one tree of stacked leaves ``[len(layers),
    ...]`` on the device, leaf ``[j]`` holding exactly what
    ``weights.seeded_tree`` gives ``layers_<layers[j]>`` there (and the
    seeded selection bias, ``correction_bias``). A layer at a time into a
    donated buffer: the most beside the result is one layer's float32
    draw (``runners/serve_diffusion.py stacked_layers``, for a stack
    that does not start at layer 0)."""
    import jax
    import jax.numpy as jnp
    dtype = jnp.dtype(dtype)
    word = np.uint32(int(seed) & 0xFFFFFFFF)

    @functools.partial(jax.jit, donate_argnums=0, static_argnums=4)
    def put(buf, j, fold, seed_word, fan_in):
        root = jax.random.fold_in(jax.random.PRNGKey(0), seed_word)
        layer = jax.random.normal(jax.random.fold_in(root, fold),
                                  buf.shape[1:], jnp.float32) \
            * np.float32(1.0 / np.sqrt(fan_in))
        return jax.lax.dynamic_update_index_in_dim(
            buf, layer.astype(buf.dtype), j, 0)

    def leaf(path, like):
        names = weights._path_names(path)
        if names[-1] == "e_score_correction_bias":
            return jnp.asarray(np.stack([
                correction_bias(seed, i, like.shape[0]) for i in layers]))
        if like.ndim < 2:               # a norm's scale: ones, as _draw
            return jnp.ones((len(layers),) + like.shape, dtype)
        buf = jnp.zeros((len(layers),) + like.shape, dtype)
        for j, i in enumerate(layers):
            name = "/".join((f"layers_{i}",) + names)
            buf = put(buf, np.int32(j),
                      np.uint32(zlib.crc32(name.encode()) & 0x7FFFFFFF),
                      word, like.shape[-2])
        return buf

    return jax.tree_util.tree_map_with_path(
        leaf, shapes[f"layers_{layers[0]}"])


def layer_tree(shapes, seed, dtype, i):
    """Layer ``i``'s subtree as the engine holds it: the reference's
    ``layer_params(i)``."""
    import jax.numpy as jnp
    name = f"layers_{i}"
    tree = weights.seeded_tree(shapes, seed, dtype, only=(name,))[name]
    gate = tree["mlp"].get("gate")
    if gate is not None:
        gate["e_score_correction_bias"] = jnp.asarray(correction_bias(
            seed, i, gate["e_score_correction_bias"].shape[0]))
    return tree


def build(ctx):
    """Weights, engine and server for ``ctx.config``."""
    import jax

    from hcache_deepspeed_tpu.inference import RaggedInferenceEngineConfig
    from hcache_deepspeed_tpu.inference.factory import (MODEL_FAMILIES,
                                                        build_hf_engine)
    from hcache_deepspeed_tpu.models.glm4_moe_lite import param_shapes
    from hcache_deepspeed_tpu.serving import ServerConfig, ServingServer

    hf = hf_config(ctx.config)
    dep = ctx.config["deployment"]
    model_config = MODEL_FAMILIES[hf["model_type"]](hf)
    n_dense = model_config.first_k_dense_replace
    with ctx.phase("weights"):
        shapes = param_shapes(model_config)
        params = weights.seeded_tree(
            shapes, ctx.seed, hf["torch_dtype"],
            only=("embed_tokens", "norm", "lm_head"))
        params["lead_layers"] = stacked_layers(
            shapes, ctx.seed, hf["torch_dtype"], list(range(n_dense)))
        params["layers"] = stacked_layers(
            shapes, ctx.seed, hf["torch_dtype"],
            list(range(n_dense, model_config.n_layer)))
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
                      "cache_dtype": hf["torch_dtype"]}))
        del params              # the engine holds the stacked leaves
        gc.collect()
    tokens = ProbedTokens(engine)
    steps = LatentStepLog(dep["prefill_chunk"], engine)
    server = ServingServer(
        engine, sample_fn=tokens, metrics=steps,
        config=ServerConfig(prefill_chunk=dep["prefill_chunk"]))
    return {"engine": engine, "server": server, "tokens": tokens,
            "steps": steps, "shapes": shapes, "hf": hf,
            "model_config": model_config, "vocab": model_config.vocab_size}


def pick_probed(arrivals, until_s):
    """Which arrivals the check compares: of those due in the window
    before ``until_s`` (seconds after the start of the ramp), the
    shortest prompt, the longest, and others evenly spaced between them
    by length, ``PROBED`` in all. ``{kind: index into arrivals}``."""
    early = sorted((len(a.prompt), k) for k, a in enumerate(arrivals)
                   if a.in_window and a.due_s < until_s and
                   a.max_new_tokens > LATER_TOKEN)
    if not early:
        return {}
    probed = {"short": early[0][1], "long": early[-1][1]}
    rest = [k for _, k in early[1:-1]]
    for j in range(min(PROBED - 2, len(rest))):
        probed[f"other{j}"] = rest[(2 * j + 1) * len(rest) //
                                   (2 * (PROBED - 2))]
    return probed


def check_rows(ctx, built, rows, probed, control=None):
    """The probed requests' two rows against the reference: the last
    prompt row and the row ``LATER_TOKEN`` output tokens later, each
    within ``LOGIT_TOL`` of the reference's full forward of the same
    tokens, the compared rows routed from what the served routers read.
    ``control``: a key of :data:`CONTROLS`, the reference computed
    wrong. Returns ``(ok, details)``."""
    hf, shapes, tokens = built["hf"], built["shapes"], built["tokens"]
    dtype = hf["torch_dtype"]
    arch = {**hf, **(CONTROLS[control] if control else {})}
    # the engine's own embedding, final norm and head: the same seeded
    # values, and a second copy of the vocabulary (1.3 GB) would not fit
    # beside the engine
    served = built["engine"].model.params
    outer = {"embed_tokens": {"embedding": served["embed"]},
             "norm": {"weight": served["norm"]},
             "lm_head": {"kernel": served["lm_head"]}}
    layer = functools.partial(layer_tree, shapes, ctx.seed, dtype)
    details, gaps, longest = {}, [], 0
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
        details[kind] = {"context_tokens": len(seq),
                         "row_gaps": [round(g, 5) for g in pair]}
    details["rows"] = len(gaps)
    details["largest"] = round(max(gaps, default=float("nan")), 5)
    details["median"] = round(float(np.median(gaps)), 5) if gaps else None
    gen = contract.load_kind("generators", ctx.traffic["kind"])
    floor = max(gen.quantile_lengths(ctx.traffic["prompt_tokens"],
                                     int(ctx.traffic["block"])))
    if len(probed) < PROBED or longest < floor + LATER_TOKEN:
        return False, {"reason": f"{len(probed)} requests compared, the "
                       f"longest context {longest} tokens: not {PROBED} "
                       f"with the mix's longest prompt ({floor}) among "
                       f"them", **details}
    ok = bool(np.isfinite(gaps).all()) and max(gaps) <= LOGIT_TOL
    return ok, details


def kernel_calls(steps, cfg):
    """The kernels' calls in ``steps`` as keyword arguments of the
    counting functions: per step one decode dispatch over the lanes and
    one call a prompt slice (a one-token slice of a sequence with a
    context rides the decode dispatch), the latent kernel once a layer,
    the grouped products once a sparse layer with the experts the rows
    touch when they fall evenly."""
    L = cfg.n_layer
    sparse = L - cfg.first_k_dense_replace
    mla = dict(n_head=cfg.n_head, c_width=cfg.kv_lora_rank,
               r_width=cfg.qk_rope_head_dim, itemsize=2)
    ffn = dict(hidden=cfg.hidden_size, width=cfg.intermediate_size,
               itemsize=2)
    calls = {"latent_calls": [], "expert_gemm_calls": []}

    def experts(positions):
        rows = positions * cfg.top_k
        calls["expert_gemm_calls"].append(dict(
            ffn, rows=rows * sparse, touched=sparse *
            flops_moe.touched_experts(rows, cfg.num_experts)))

    for step in steps:
        contexts = list(step["decode_ctx"]) + \
            [end for q, end in step["slices"] if q == 1 and end > 1]
        if contexts:
            calls["latent_calls"] += [dict(
                mla, context_lens=contexts, q_lens=[1] * len(contexts))] * L
            experts(len(contexts))
        for q, end in step["slices"]:
            if q == 1 and end > 1:
                continue
            calls["latent_calls"] += [dict(
                mla, context_lens=[end], q_lens=[q])] * L
            experts(q)
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
    leaked = engine.state.allocator.num_blocks - 1 - engine.free_blocks
    faults = server.scheduler.fault_summary()
    in_window = [s for s in built["steps"].steps
                 if t_open <= s["t"] < t_close]
    compiles = {"ramp": ctx.meter.between(t0, t_open),
                "window": ctx.meter.between(t_open, t_close)}
    fallbacks = fallback_count() - fallbacks_before
    latents = engine.latent_stats()
    # the pools are the check's room: nothing reads them after the
    # window, and the reference of a 32k context needs a few GB
    engine.cache.replace(None, None)
    gc.collect()
    rows_ok, row_details = check(ctx, built, rows, probed)
    # beside what ``runners/serve.py`` asks: the window ran the kernels
    # (a fallback would be timed as the cell) and built no program
    correct = bool(rows_ok and leaked == 0 and
                   faults["total_faults"] == 0 and nums["failed"] == 0 and
                   nums["attempted"] == sum(1 for a in arrivals
                                            if a.in_window) and
                   fallbacks == 0 and compiles["window"] == 0)
    print(f"check: rows {row_details}, limit {LOGIT_TOL}, leaked_blocks "
          f"{leaked}, faults {faults['total_faults']}, fallbacks "
          f"{fallbacks}, saved state {latents['saved_state']}, programs "
          f"built or fetched {compiles}, preempted "
          f"{sum(s['preempted'] for s in in_window)}, restores "
          f"{engine.restore_stats['restores']}, steps in window "
          f"{len(in_window)}, longest "
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
    slots = dep["num_blocks"] * dep["block_size"]
    c_width, r_width = cfg.cache_row_widths
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
            "fallbacks": fallbacks},
        "memory": {"peak_bytes": result["device"]["memory_peak_bytes"]},
        "trace": reduction,
        "device_kind": devices[0].device_kind,
        "arch": built["hf"],
        "placeholders": {
            "kv_pool": f"{slots}_{c_width}_",
            "kv_blocks": f"1,{dep['num_blocks']},{dep['block_size']},"
                         f"{c_width}",
            "c_pool": f"{slots}_{c_width}_",
            "r_pool": f"{slots}_{r_width}_"},
    }
    evidence.update(kernel_calls(traced, cfg))
    if latents.get("captured_tokens"):
        evidence["counters"]["saved_state_bytes_per_token"] = \
            latents["captured_bytes"] / latents["captured_tokens"]
    if len(in_window) > 1:
        walked = in_window[-1]["walked"] - in_window[0]["walked"]
        evidence["counters"]["latent_mb_read_per_step"] = (
            walked * dep["block_size"] * (c_width + r_width) * 2
            * cfg.n_layer / 1e6 / (len(in_window) - 1))
    # the serve cells' metrics (files that name their cells by the kind
    # "serve"), the expert layer's and this kind's own
    moe = {name: value for name, value in layer_metrics.compute(
        ctx.cell, "serve_diffusion", evidence).items()
        if name in ("moe_share", "expert_gemm_roofline")}
    result["metrics"] = {
        **layer_metrics.compute(ctx.cell, "serve", evidence), **moe,
        **layer_metrics.compute(ctx.cell, ctx.config["runner"], evidence)}
    result["device"].update(busy_s=reduction.busy_s,
                            window_s=reduction.window_s)
    result["breakdown"] = reduction.breakdown()
    return result
