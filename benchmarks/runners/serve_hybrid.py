"""``serve_hybrid``: one paced-traffic cell through the serving main path
for a trunk with recurrent layers (``model_type`` ``olmo_hybrid``).

The same road as ``runners/serve.py`` and everything of it that does not
ask for a llama trunk (``warm_engine``, ``offer``, ``window_numbers``,
the token callback, the step log): ``MODEL_FAMILIES[...]`` ->
``build_hf_engine`` -> ``ServingServer`` in thread mode, an open-loop
generator on this process's main thread. Its own: ``build`` (shapes from
``OlmoHybridForCausalLM``, decay parameters drawn so that the heads'
decays spread over (0, 1)), the check (two finished sequences against
``reference/olmo_hybrid.py``, one of them past 4096 tokens of context)
and the evidence (the paged kernel runs in the full layers only; the
gated-delta kernels' calls; state slots; latent bytes a token). Its
traced line holds the metrics of the files that name their cells by the
kind ``serve`` and those of its own kind: it asks ``layer_metrics`` for
both.
"""

import gc
import time

import numpy as np

from .. import contract, layer_metrics, weights
from ..reference import olmo_hybrid as reference
from ..stats import mean, percentile
from ..trace import xplane
from .common import TracedStretch, device_line, fallback_count
from .serve import (GRACE_S, TRACE_S, StepLog, _bucket, _Tokens, hf_config,
                    offer, warm_engine, window_numbers)

#: As ``runners/serve.py LOGIT_TOL``: the engine reaches the compared
#: row in bf16 weights and activations through eight or more prompt
#: slices and decode steps over both pools, the reference in float32 at
#: "highest" precision in one pass, token by token. Set from two
#: readings (my chip runs, PR 31; PERF.md section 4): the largest gap of
#: the change over its seeds, 0.0415 (one row of some fifty; the others
#: 0.0196 to 0.0279: bf16 rounds every matmul's result, and this block
#: has more of them a layer than the llama block, whose cell reads
#: 0.013-0.017), and the reference against itself with its residual
#: stream rounded after every layer to float8_e4m3, the nearest
#: precision below the bf16 the configuration states: 0.101 and 0.111,
#: which has to fail. 0.07 is 1.7 times the first and 0.7 of the
#: second. A dropped layer reads 0.96-1.17.
#:
#: What no limit on a logit row can hold is the precision that is this
#: configuration's own, the float32 of the recurrent state: kept in
#: bfloat16 between tokens it moves the row by 0.011-0.012 (same runs),
#: under the bf16 noise of the activations around it. So ``correct``
#: holds the state pool to ``STATE_DTYPE`` by name.
LOGIT_TOL = 0.07
#: ``assumed.state_dtype`` of the configuration: what the state pool
#: keeps between tokens, and part of ``correct``
STATE_DTYPE = "float32"
#: the long compared sequence has a context of more than this many
#: prompt slices (4096 tokens at the cell's 512-token chunk)
LONG_SLICES = 8
#: the heads' decay factors at a zero ``a`` projection
DECAY_SPREAD = (0.02, 0.98)


class HybridStepLog(StepLog):
    """``StepLog`` that also keeps ``StepReport.state_slots``."""

    def on_step(self, report, scheduler):
        super().on_step(report, scheduler)
        self.steps[-1]["state_slots"] = report.state_slots


def decay_leaves(seed, layer, n_head, dtype):
    """``A_log`` and ``dt_bias`` of linear layer ``layer``: ``A_log`` 0
    and ``dt_bias`` such that ``exp(-softplus(dt_bias))``, a head's decay
    factor where its ``a`` projection is 0, is uniform over
    ``DECAY_SPREAD``, seeded by ``(seed, layer)``: the heads forget at
    rates from a few tokens to a few dozen, as trained decays do, where
    the ones ``weights.py`` gives every vector would make all heads
    forget within a token."""
    import jax.numpy as jnp
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(layer), 31])
    factor = rng.uniform(*DECAY_SPREAD, size=n_head)
    dt_bias = np.log(np.expm1(-np.log(factor)))
    return {"A_log": jnp.zeros((n_head,), dtype),
            "dt_bias": jnp.asarray(dt_bias, jnp.float32).astype(dtype)}


def with_decays(layer_tree, seed, layer, dtype):
    """``layer_tree`` (one ``layers_<i>`` subtree) with the seeded decay
    parameters, if it is a linear layer."""
    if "linear_attn" not in layer_tree:
        return layer_tree
    la = dict(layer_tree["linear_attn"])
    la.update(decay_leaves(seed, layer, la["A_log"].shape[0], dtype))
    return dict(layer_tree, linear_attn=la)


def build(ctx):
    """Weights, engine and server for ``ctx.config``."""
    import jax

    from hcache_deepspeed_tpu.inference import RaggedInferenceEngineConfig
    from hcache_deepspeed_tpu.inference.factory import (MODEL_FAMILIES,
                                                        build_hf_engine)
    from hcache_deepspeed_tpu.models.olmo_hybrid import \
        OlmoHybridForCausalLM
    from hcache_deepspeed_tpu.serving import ServerConfig, ServingServer

    hf = hf_config(ctx.config)
    dep = ctx.config["deployment"]
    model_config = MODEL_FAMILIES[hf["model_type"]](hf)
    with ctx.phase("weights"):
        shapes = weights.param_shapes(
            OlmoHybridForCausalLM(model_config),
            {"input_ids": np.zeros((1, 128), np.int32)})
        params = weights.seeded_tree(shapes, ctx.seed, hf["torch_dtype"])
        for i in range(model_config.n_layer):
            params[f"layers_{i}"] = with_decays(
                params[f"layers_{i}"], ctx.seed, i, hf["torch_dtype"])
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
        del params              # the engine holds its own stacked copy
        gc.collect()
    tokens = _Tokens()
    steps = HybridStepLog(dep["prefill_chunk"])
    server = ServingServer(
        engine, sample_fn=tokens, metrics=steps,
        config=ServerConfig(prefill_chunk=dep["prefill_chunk"]))
    return {"engine": engine, "server": server, "tokens": tokens,
            "steps": steps, "shapes": shapes, "hf": hf,
            "model_config": model_config, "vocab": model_config.vocab_size}


def pick_compared(rows, kept, long_context):
    """The two finished sequences the check compares: the one with the
    shortest context and the shortest of those whose context passed
    ``long_context`` (prompt plus all but the last output token: what
    the engine had cached when it made the kept row)."""
    done = [(len(row["req"].prompt) + len(row["req"].tokens_out) - 1,
             row["req"]) for row in rows if row["req"].uid in kept]
    done.sort(key=lambda item: (item[0], item[1].uid))
    picked = {}
    if done:
        picked["short"] = done[0][1]
    longer = [req for n, req in done if n > long_context]
    if longer:
        picked["long"] = longer[0]
    return picked


def check_logits(ctx, built, rows):
    """Next-token logits of two finished sequences against the plain
    reference at the published widths: the shortest, and one whose
    context passed 4096 tokens (eight or more prompt slices, then decode
    through both pools). Returns ``(ok, details)``."""
    tokens, hf, shapes = built["tokens"], built["hf"], built["shapes"]
    dtype = hf["torch_dtype"]
    long_context = LONG_SLICES * ctx.config["deployment"]["prefill_chunk"]
    picked = pick_compared(rows, tokens.rows, long_context)
    if set(picked) != {"short", "long"} or \
            picked["short"] is picked["long"]:
        return False, {"reason": "no finished short sequence and one "
                                 f"past {long_context} tokens to compare "
                                 f"(have {sorted(picked)})"}
    outer = weights.seeded_tree(
        shapes, ctx.seed, dtype, only=("embed_tokens", "norm", "lm_head"))

    def layer(i):
        name = f"layers_{i}"
        return with_decays(
            weights.seeded_tree(shapes, ctx.seed, dtype,
                                only=(name,))[name], ctx.seed, i, dtype)

    details, ok = {}, True
    for kind, req in picked.items():
        context = list(req.prompt) + list(req.tokens_out[:-1])
        ids = np.zeros(_bucket(len(context), 256), np.int32)
        ids[:len(context)] = context
        ref = reference.next_token_logits(ids, len(context), hf, outer,
                                          layer)
        gap = reference.logit_gap(tokens.rows[req.uid], ref)
        details[kind] = {"context_tokens": len(context),
                         "logit_gap": round(gap, 5)}
        ok = ok and bool(np.isfinite(gap)) and gap <= LOGIT_TOL
    return ok, details


def kernel_calls(steps, model_config):
    """The kernels' calls in ``steps`` as keyword arguments of the
    counting functions: per step one decode dispatch over the lanes and
    one call a prompt slice (a one-token slice of a sequence with a
    context rides the decode dispatch), the paged kernel once a full
    layer, the gated-delta kernels once a linear layer."""
    from hcache_deepspeed_tpu.ops.gated_delta import CHUNK
    n_full = model_config.layer_types.count("full_attention")
    n_lin = model_config.n_layer - n_full
    paged = dict(n_head=model_config.n_head,
                 n_kv_head=model_config.n_kv_head,
                 head_dim=model_config.head_dim, itemsize=2)
    rule = dict(n_head=model_config.linear_num_value_heads,
                d_k=model_config.linear_key_head_dim,
                d_v=model_config.linear_value_head_dim, itemsize=2)
    calls = {"paged_calls": [], "gated_chunk_calls": [],
             "gated_step_calls": []}
    for step in steps:
        contexts = list(step["decode_ctx"]) + \
            [end for q, end in step["slices"] if q == 1 and end > 1]
        slices = [(q, end) for q, end in step["slices"]
                  if not (q == 1 and end > 1)]
        if contexts:
            calls["paged_calls"] += [dict(
                paged, context_lens=contexts,
                q_lens=[1] * len(contexts))] * n_full
            calls["gated_step_calls"] += [dict(
                rule, lanes=len(contexts))] * n_lin
        for q, end in slices:
            calls["paged_calls"] += [dict(
                paged, context_lens=[end], q_lens=[q])] * n_full
            calls["gated_chunk_calls"] += [dict(
                rule, t_lens=[q], chunk=CHUNK)] * n_lin
    return calls


def run(ctx):
    import jax

    from hcache_deepspeed_tpu.telemetry.tracer import get_tracer

    dep = ctx.config["deployment"]
    built = build(ctx)
    engine, server = built["engine"], built["server"]
    with ctx.phase("warm"):
        warmed = warm_engine(engine, ctx.traffic, dep, built["vocab"])
    gen = contract.load_kind("generators", ctx.traffic["kind"])
    arrivals = gen.schedule(ctx.traffic, ctx.seed, ctx.seconds,
                            built["vocab"], dep["max_context"])
    half = ctx.traffic["ramp_s"] + ctx.seconds / 2.0
    setup_compiles = ctx.meter.take()
    if ctx.trace:
        get_tracer().configure(enabled=True)

    # uids are handed out in submit order, from 0: arrival k is uid k
    built["tokens"].keep_row_of = {
        k for k, a in enumerate(arrivals)
        if a.in_window and a.due_s < half}
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
    leaked_slots = engine.state.state_slots_in_use
    faults = server.scheduler.fault_summary()
    logits_ok, logit_details = check_logits(ctx, built, rows)
    in_window = [s for s in built["steps"].steps
                 if t_open <= s["t"] < t_close]
    compiles = {"ramp": ctx.meter.between(t0, t_open),
                "window": ctx.meter.between(t_open, t_close)}
    fallbacks = fallback_count()
    state_dtype = str(engine.cache.state.dtype)
    # beside what ``runners/serve.py`` asks: the window ran the kernels
    # (a slice that fell back to the token-by-token recurrence would be
    # timed as the cell), built no program, and the state pool keeps the
    # precision the configuration states
    correct = bool(logits_ok and leaked == 0 and leaked_slots == 0 and
                   faults["total_faults"] == 0 and
                   nums["attempted"] == sum(1 for a in arrivals
                                            if a.in_window) and
                   fallbacks == 0 and compiles["window"] == 0 and
                   state_dtype == STATE_DTYPE)
    latents = engine.latent_stats()
    print(f"check: logits {logit_details}, leaked_blocks {leaked}, "
          f"leaked_state_slots {leaked_slots}, faults "
          f"{faults['total_faults']}, fallbacks {fallbacks}, state pool "
          f"{state_dtype}, programs built or fetched {compiles}, "
          f"preempted {sum(s['preempted'] for s in in_window)}, steps in "
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
    state = engine.cache.state.shape          # [L_lin, S + 1, H, dk, dv]
    evidence = {
        "series": {
            "gen_late_s": nums["late"], "submit_wait_s": nums["submit_wait"],
            "itl_s": nums["gaps"], "ttft_s": nums["ttft"],
            "queue_wait_s": [r["req"].queue_wait() for r in rows
                             if t_open <= r["due"] < t_close and
                             r["req"].queue_wait() is not None],
            "decode_lanes": [s["lanes"] for s in in_window if s["lanes"]],
            "state_slots": [s["state_slots"] for s in in_window]},
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
            "kv_pool": f"{dep['num_blocks'] * dep['block_size']}_"
                       f"{cfg.head_dim}_",
            "kv_blocks": f"{cfg.n_kv_head},{dep['num_blocks']},"
                         f"{dep['block_size']},{cfg.head_dim}",
            "state_pool": "_".join(str(d) for d in state[1:]) + "_"},
    }
    evidence.update(kernel_calls(traced, cfg))
    if latents.get("captured_tokens"):
        evidence["counters"]["latent_bytes_per_token"] = \
            latents["captured_bytes"] / latents["captured_tokens"]
    # the serve cells' metrics (files that name their cells by the kind
    # "serve") and this kind's own
    result["metrics"] = {
        **layer_metrics.compute(ctx.cell, "serve", evidence),
        **layer_metrics.compute(ctx.cell, ctx.config["runner"], evidence)}
    result["device"].update(busy_s=reduction.busy_s,
                            window_s=reduction.window_s)
    result["breakdown"] = reduction.breakdown()
    return result
