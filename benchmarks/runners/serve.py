"""``serve``: one paced-traffic cell through the serving main path.

``MODEL_FAMILIES[...]`` -> ``build_hf_engine`` -> ``ServingServer`` in
thread mode -> ``serving/scheduler.py``, driven by an open-loop load
generator on this process's main thread. Set-up: seeded weights on the
device, the engine, every dispatch shape the traffic can reach (worked
out from the traffic file, run once each through ``engine.put``), then
the ramp. The window is timed on the generator's clock; tokens are
stamped at the server's token callback (``sample_fn``). The check runs
after the window.
"""

import gc
import time

import numpy as np
from hcache_deepspeed_tpu.serving.metrics import ServingMetrics
from hcache_deepspeed_tpu.serving.request import RequestState

from .. import contract, layer_metrics, weights
from ..reference import llama as reference
from ..stats import mean, percentile
from ..trace import xplane
from .common import TracedStretch, device_line, fallback_count

#: the reference and the engine both reach the compared row in bf16
#: weights, the engine also in bf16 activations through chunked prefill
#: and decode steps over the paged cache, the reference in float32 at
#: "highest" precision in one pass. bf16 keeps 8 significant bits
#: (2^-8 = 0.0039) and the error grows by a few of those per layer, so
#: the rows differ by about a percent of their scale at 8 layers
#: (chip_smoke.py measured 0.007 between two bf16 paths, PR 21). 0.03
#: leaves room for that and fails an engine that computed in a lower
#: precision or dropped a layer's contribution (each layer moves the
#: logits by far more than 3%).
LOGIT_TOL = 0.03
#: seconds after the window for late first tokens before a request due
#: inside it counts as failed
GRACE_S = 2.0
#: seconds of the window a ``--trace 1`` run profiles
TRACE_S = 6.0
_WARM_UID = 1 << 30


def _bucket(n, minimum):
    b = minimum
    while b < n:
        b *= 2
    return b


def warm_plan(traffic, deployment):
    """The dispatch shapes this cell's traffic can reach, from the
    traffic file and the engine's limits: ``(prefill, decode)`` where
    ``prefill`` is ``[(lanes, slice_len)]`` (one put of ``lanes``
    prompts of ``slice_len`` tokens reaches the program of that bucket)
    and ``decode`` the lane counts that reach each decode bucket."""
    gen = contract.load_kind("generators", traffic["kind"])
    chunk = deployment["prefill_chunk"]
    budget = deployment["max_ragged_batch_size"]
    together = int(traffic.get("prefills_together", 3))
    by_bucket = {}
    for n in gen.prefill_slices(traffic, chunk):
        by_bucket.setdefault(_bucket(n, 8), []).append(n)
    prefill = []
    for _, lens in sorted(by_bucket.items()):
        smallest = min(lens)
        most = max(1, min(together, budget // smallest))
        for lanes in sorted({_bucket(k, 1) for k in range(1, most + 1)}):
            # the fewest prompts that still land in this lane bucket
            prefill.append((lanes // 2 + 1 if lanes > 1 else 1, smallest))
    decode, lanes = [], 8
    while lanes <= _bucket(deployment["max_tracked_sequences"], 8):
        decode.append(lanes // 2 + 1)
        lanes *= 2
    return prefill, decode


def warm_engine(engine, traffic, deployment, vocab):
    """Run every shape of :func:`warm_plan` once through ``engine.put``,
    then free what it allocated."""
    prefill, decode = warm_plan(traffic, deployment)
    rng = np.random.default_rng(0)
    live = []

    def admit(count, length):
        uids = [_WARM_UID + len(live) + i for i in range(count)]
        engine.put(uids, [rng.integers(0, vocab, length) for _ in uids])
        live.extend(uids)

    try:
        for count, length in prefill:
            admit(count, length)
        count, length = max(prefill)             # cheapest way to add lanes
        while len(live) < min(max(decode),
                              deployment["max_tracked_sequences"]):
            admit(count, length)
        for lanes in decode:
            if lanes <= len(live):
                engine.put(live[:lanes], [[1]] * lanes)
    finally:
        for uid in live:
            engine.flush(uid)
    return {"prefill_shapes": len(prefill), "decode_shapes": len(decode)}


class _Tokens:
    """The server's token callback: greedy sampling, a stamp per token
    on the generator's clock, and the logits row behind the last token
    of the requests the check may compare."""

    def __init__(self):
        self.stamps = {}        # uid -> [t of each output token]
        self.keep_row_of = set()
        self.rows = {}          # uid -> logits row behind the last token

    def __call__(self, req, row):
        t = time.monotonic()
        token = int(np.argmax(row))
        self.stamps.setdefault(req.uid, []).append(t)
        if req.uid in self.keep_row_of and \
                len(req.tokens_out) + 1 == req.max_new_tokens:
            self.rows[req.uid] = np.array(row, np.float32)
        return token


class StepLog(ServingMetrics):
    """``ServingMetrics`` that also keeps what each scheduler step
    dispatched: the decode lanes with their contexts and the prompt
    slices with their lengths and end positions (for the paged kernel's
    operation and byte counts). Read after the dispatch, so a request
    that finished in the step is not in it."""

    def __init__(self, chunk):
        super().__init__()
        self.chunk = chunk
        self.steps = []

    def on_step(self, report, scheduler):
        super().on_step(report, scheduler)
        decode_ctx, slices = [], []
        for req in scheduler.running.values():
            fresh = req.first_token_at == report.t
            if req.state == RequestState.DECODE and not fresh:
                decode_ctx.append(req.cached_tokens)
                continue
            end = len(req.prompt) if fresh else req.prefill_pos
            if end:
                slices.append(
                    (end - (end - 1) // self.chunk * self.chunk, end))
        self.steps.append({
            "t": report.t, "lanes": report.decode_lanes,
            "prefill_tokens": report.prefill_tokens,
            "preempted": len(report.preempted),
            "decode_ctx": decode_ctx, "slices": slices})


#: keys of a configuration file that are the benchmark's own; every
#: other key is the model's published ``config.json``
_OWN_KEYS = ("name", "source", "runner", "chips", "reduced", "assumed",
             "stands_for", "deployment")


def hf_config(config):
    return {k: v for k, v in config.items() if k not in _OWN_KEYS}


def build(ctx):
    """Weights, engine and server for ``ctx.config``; returns what the
    window and the check need."""
    import jax

    from hcache_deepspeed_tpu.inference import RaggedInferenceEngineConfig
    from hcache_deepspeed_tpu.inference.factory import (MODEL_FAMILIES,
                                                        build_hf_engine)
    from hcache_deepspeed_tpu.models.llama import LlamaForCausalLM
    from hcache_deepspeed_tpu.serving import ServerConfig, ServingServer

    hf = hf_config(ctx.config)
    dep = ctx.config["deployment"]
    model_config = MODEL_FAMILIES[hf["model_type"]](hf)
    with ctx.phase("weights"):
        shapes = weights.param_shapes(
            LlamaForCausalLM(model_config),
            {"input_ids": np.zeros((1, 128), np.int32)})
        params = weights.seeded_tree(shapes, ctx.seed, hf["torch_dtype"])
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
    steps = StepLog(dep["prefill_chunk"])
    server = ServingServer(
        engine, sample_fn=tokens, metrics=steps,
        config=ServerConfig(prefill_chunk=dep["prefill_chunk"]))
    return {"engine": engine, "server": server, "tokens": tokens,
            "steps": steps, "shapes": shapes, "hf": hf,
            "vocab": model_config.vocab_size}


def offer(server, arrivals, t0):
    """The open loop: submit each arrival when it is due, whatever the
    server is doing. Returns one row per arrival."""
    rows = []
    for a in arrivals:
        due = t0 + a.due_s
        wait = due - time.monotonic()
        if wait > 0:
            time.sleep(wait)
        sent = time.monotonic()
        req = server.submit(prompt=list(a.prompt),
                            max_new_tokens=a.max_new_tokens,
                            priority=a.priority)
        rows.append({"arrival": a, "due": due, "sent": sent,
                     "submitted": time.monotonic(), "req": req})
    return rows


def window_numbers(rows, stamps, t_open, t_close, t_grace):
    """The end-to-end numbers of the window and the series the per-layer
    metrics read, from the generator's rows and the token stamps."""
    ttft, late, submit_wait, gaps = [], [], [], []
    attempted = failed = 0
    for row in rows:
        due = row["due"]
        if not t_open <= due < t_close:
            continue
        attempted += 1
        late.append(row["sent"] - due)
        submit_wait.append(row["submitted"] - row["sent"])
        got = stamps.get(row["req"].uid, [])
        if row["req"].reject_reason or row["req"].error or not got \
                or got[0] > t_grace:
            failed += 1
            ttft.append(t_grace - due)     # slower than every answer
        else:
            ttft.append(got[0] - due)
    tokens_in_window = 0
    for times in stamps.values():
        tokens_in_window += sum(1 for t in times if t_open <= t < t_close)
        gaps.extend(b - a for a, b in zip(times, times[1:])
                    if t_open <= b < t_close)
    return {"attempted": attempted, "failed": failed, "ttft": ttft,
            "late": late, "submit_wait": submit_wait, "gaps": gaps,
            "tokens_in_window": tokens_in_window}


def check_logits(ctx, built, rows):
    """Next-token logits of two finished sequences, one short and one
    chunked, against the plain reference. Returns ``(ok, details)``."""
    tokens, hf, shapes = built["tokens"], built["hf"], built["shapes"]
    chunk = ctx.config["deployment"]["prefill_chunk"]
    picked = {}
    for row in rows:
        req = row["req"]
        kind = "chunked" if len(req.prompt) > chunk else "short"
        if kind not in picked and req.uid in tokens.rows:
            picked[kind] = req
    if set(picked) != {"short", "chunked"}:
        return False, {"reason": "no finished short and chunked sequence "
                                 f"to compare (have {sorted(picked)})"}
    outer = weights.seeded_tree(
        shapes, ctx.seed, hf["torch_dtype"],
        only=("embed_tokens", "norm", "lm_head"))

    def layer(i):
        name = f"layers_{i}"
        return weights.seeded_tree(shapes, ctx.seed, hf["torch_dtype"],
                                   only=(name,))[name]

    contexts = {kind: list(req.prompt) + list(req.tokens_out[:-1])
                for kind, req in picked.items()}
    padded = _bucket(max(len(c) for c in contexts.values()), 256)
    details, ok = {}, True
    for kind, context in contexts.items():
        ids = np.zeros(padded, np.int32)
        ids[:len(context)] = context
        ref = reference.next_token_logits(ids, len(context), hf, outer,
                                          layer)
        gap = reference.logit_gap(tokens.rows[picked[kind].uid], ref)
        details[kind] = {"context_tokens": len(context),
                         "logit_gap": round(gap, 5)}
        ok = ok and bool(np.isfinite(gap)) and gap <= LOGIT_TOL
    return ok, details


def paged_calls(steps, hf):
    """The paged kernel's calls in ``steps``, as keyword arguments of
    ``flops.paged_attention_counts``: per step one decode dispatch over
    the lanes and one call per prompt slice, each once per layer."""
    shape = dict(n_head=hf["num_attention_heads"],
                 n_kv_head=hf["num_key_value_heads"],
                 head_dim=hf["hidden_size"] // hf["num_attention_heads"],
                 itemsize=2)
    calls = []
    for step in steps:
        per_layer = []
        if step["decode_ctx"]:
            per_layer.append(dict(shape, context_lens=step["decode_ctx"],
                                  q_lens=[1] * len(step["decode_ctx"])))
        for q, end in step["slices"]:
            per_layer.append(dict(shape, context_lens=[end], q_lens=[q]))
        calls.extend(per_layer * hf["num_hidden_layers"])
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
    faults = server.scheduler.fault_summary()
    logits_ok, logit_details = check_logits(ctx, built, rows)
    correct = bool(logits_ok and leaked == 0 and
                   faults["total_faults"] == 0 and
                   nums["attempted"] == sum(1 for a in arrivals
                                            if a.in_window))
    in_window = [s for s in built["steps"].steps
                 if t_open <= s["t"] < t_close]
    compiles = {"ramp": ctx.meter.between(t0, t_open),
                "window": ctx.meter.between(t_open, t_close)}
    print(f"check: logits {logit_details}, leaked_blocks {leaked}, "
          f"faults {faults['total_faults']}, programs built or fetched "
          f"{compiles}, steps in window {len(in_window)}, longest "
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
    head = built["hf"]["hidden_size"] // built["hf"]["num_attention_heads"]
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
            "fallbacks": fallback_count()},
        "memory": {"peak_bytes": result["device"]["memory_peak_bytes"]},
        "trace": reduction,
        "device_kind": devices[0].device_kind,
        "arch": built["hf"],
        "paged_calls": paged_calls(traced, built["hf"]),
        "placeholders": {
            "kv_pool": f"{dep['num_blocks'] * dep['block_size']}_{head}_",
            "kv_blocks": f"{built['hf']['num_key_value_heads']},"
                         f"{dep['num_blocks']},{dep['block_size']},{head}"},
    }
    result["metrics"] = layer_metrics.compute(ctx.cell, "serve", evidence)
    result["device"].update(busy_s=reduction.busy_s,
                            window_s=reduction.window_s)
    result["breakdown"] = reduction.breakdown()
    return result
