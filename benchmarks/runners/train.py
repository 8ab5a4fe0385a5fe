"""``train``: one training cell through the training main path.

``LlamaForCausalLM`` -> ``hds.initialize`` -> ``HDSEngine.train_batch``
on the mesh the configuration states, steps issued back to back with
the input pipeline running in a thread beside them. Set-up: the engine
(which makes its own seeded, sharded weights), the warm-up steps. A
step's completion is read one step late (the loss of step k is waited
for after step k+1 is enqueued), so the host never stalls the device to
take a time. The check runs after the window.
"""

import collections
import queue
import threading
import time

import numpy as np

from .. import contract, layer_metrics
from ..reference import llama as reference
from ..trace import xplane
from .common import TracedStretch, device_line, fallback_count
from .serve import hf_config

#: the engine computes the loss in bf16 activations from bf16 weights,
#: the reference in float32 at "highest" from the same bf16 weights. At
#: a loss near ln(vocab) = 10.4 the two differ in the third digit; 2%
#: fails a step that dropped a layer, mis-scaled attention or read
#: another batch, and passes bf16 rounding.
LOSS_TOL = 0.02
TRACE_S = 4.0


class _Pipeline:
    """The input pipeline: a thread that keeps a few batches ready."""

    def __init__(self, batches, depth=4):
        self._q = queue.Queue(maxsize=depth)
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._fill, args=(batches,),
                                        name="bench-input", daemon=True)
        self._thread.start()

    def _fill(self, batches):
        for batch in batches:
            while not self._stop.is_set():
                try:
                    self._q.put(batch, timeout=0.1)
                    break
                except queue.Full:
                    continue
            if self._stop.is_set():
                return

    def next(self):
        return self._q.get(timeout=60.0)

    def close(self):
        self._stop.set()
        self._thread.join(timeout=10.0)


def build(ctx, hf, example_batch):
    import jax
    from dataclasses import replace

    import hcache_deepspeed_tpu as hds
    from hcache_deepspeed_tpu.inference.factory import MODEL_FAMILIES
    from hcache_deepspeed_tpu.models.llama import LlamaForCausalLM
    from hcache_deepspeed_tpu.parallel.topology import (MeshTopology,
                                                        TopologySpec)
    dep = ctx.config["deployment"]
    model_config = replace(MODEL_FAMILIES[hf["model_type"]](hf),
                           max_positions=int(ctx.traffic["seq_len"]))
    chips = ctx.cell["chips"]
    config = {
        "train_batch_size": int(ctx.traffic["global_batch"]),
        "optimizer": dep["optimizer"],
        "bf16": {"enabled": bool(dep["bf16"])},
        "gradient_clipping": dep["gradient_clipping"],
        "zero_optimization": {"stage": dep["zero_stage"]},
        "steps_per_print": 10 ** 9,
        "seed": int(ctx.seed) & 0xFFFFFFFF,
    }
    topology = MeshTopology(TopologySpec(**dep["mesh"]),
                            devices=jax.devices()[:chips])
    engine, _, _, _ = hds.initialize(
        model=LlamaForCausalLM(model_config), config=config,
        example_batch=example_batch, topology=topology)
    return engine


def check_loss(engine, hf, batch):
    """The engine's loss on ``batch`` against the plain reference's, on
    the weights the engine holds now. Returns ``(ok, details)``."""
    import jax
    params = engine.state["params"]
    one = jax.devices()[0]

    def on_one(tree):
        return jax.device_put(tree, one)

    outer = on_one({k: params[k]
                    for k in ("embed_tokens", "norm", "lm_head")})
    want = float(np.mean([
        reference.lm_loss(seq, hf, outer,
                          lambda i: on_one(params[f"layers_{i}"]))
        for seq in np.asarray(batch["input_ids"])]))
    got = float(engine.train_batch(batch=batch))
    gap = abs(got - want) / max(abs(want), 1.0)
    return bool(np.isfinite(got) and gap <= LOSS_TOL), {
        "engine_loss": round(got, 4), "reference_loss": round(want, 4),
        "gap": round(gap, 5)}


def run(ctx):
    import jax

    from hcache_deepspeed_tpu.telemetry.tracer import get_tracer

    gen = contract.load_kind("generators", ctx.traffic["kind"])
    hf = hf_config(ctx.config)
    pipeline = _Pipeline(gen.batches(ctx.traffic, ctx.seed,
                                     hf["vocab_size"]))
    chips = ctx.cell["chips"]
    tokens_per_step = int(ctx.traffic["global_batch"]) * \
        int(ctx.traffic["seq_len"])
    try:
        first = pipeline.next()
        with ctx.phase("engine"):
            engine = build(ctx, hf, first)
        with ctx.phase("warm"):
            batch = first
            for _ in range(int(ctx.traffic["warmup_steps"])):
                loss = engine.train_batch(batch=batch)
                batch = pipeline.next()
            jax.block_until_ready((engine.state["params"], loss))
        setup_compiles = ctx.meter.take()
        if ctx.trace:
            get_tracer().configure(enabled=True)
        t_open = time.monotonic()
        ctx.phases["setup_s"] = t_open - ctx.t_start
        stretch = None
        if ctx.trace:
            stretch = TracedStretch(ctx.root, ctx.cell["name"])
            stretch.run(t_open + 1.0,
                        t_open + 1.0 + min(TRACE_S, ctx.seconds - 1.0))
        pending = collections.deque()
        done_at, losses = [], []
        while not done_at or done_at[-1] - t_open < ctx.seconds:
            pending.append(engine.train_batch(batch=batch))
            batch = pipeline.next()
            if len(pending) > 1:
                losses.append(float(pending.popleft()))
                done_at.append(time.monotonic())
        while pending:
            losses.append(float(pending.popleft()))
            done_at.append(time.monotonic())
        window_s = done_at[-1] - t_open
        window_compiles = ctx.meter.between(t_open, done_at[-1])
        if stretch is not None:
            stretch.join()
        ok, details = check_loss(engine, hf, batch)
    finally:
        pipeline.close()
    finite = [bool(np.isfinite(x)) for x in losses]
    print(f"check: {details}, steps {len(losses)}, first loss "
          f"{losses[0]:.4f}, last {losses[-1]:.4f}", flush=True)

    devices = jax.devices()
    rate = tokens_per_step * len(losses) / window_s / chips
    result = {"correct": bool(ok and all(finite)),
              "attempted": len(losses),
              "failed": finite.count(False),
              "device": device_line(devices, chips)}
    ctx.phases.update(programs=setup_compiles["programs"],
                      cache_hits=setup_compiles["cache_hits"],
                      compile_or_fetch_s=setup_compiles["seconds"])
    if not ctx.trace:
        result["metrics"] = {
            "train_tok_s_chip": {"value": rate, "unit": "tokens/s/chip"},
            "setup_s": {"value": ctx.phases["setup_s"], "unit": "s"}}
        return result

    reduction = xplane.reduce_file(stretch.path)
    head_dim = hf["hidden_size"] // hf["num_attention_heads"]
    per_chip = int(ctx.traffic["global_batch"]) // chips
    flash = dict(batch=per_chip, q_len=int(ctx.traffic["seq_len"]),
                 kv_len=int(ctx.traffic["seq_len"]),
                 n_head=hf["num_attention_heads"],
                 n_kv_head=hf["num_key_value_heads"], head_dim=head_dim,
                 itemsize=2, causal=True)
    leaves = jax.tree.leaves(engine.state["params"])
    evidence = {
        "series": {"step_s": [b - a for a, b in
                              zip([t_open] + done_at, done_at)]},
        "counters": {
            "tokens_per_s_per_chip": rate,
            "compiles_in_window": window_compiles,
            "fallbacks": fallback_count()},
        "memory": {"peak_bytes": result["device"]["memory_peak_bytes"]},
        "trace": reduction,
        "device_kind": devices[0].device_kind,
        "arch": hf, "seq_len": int(ctx.traffic["seq_len"]),
        "placeholders": {
            "flash_q": f"{per_chip},{hf['num_attention_heads']},"
                       f"{int(ctx.traffic['seq_len'])},{head_dim}"},
        "flash_fwd_call": dict(flash, backward=False),
        "flash_bwd_call": dict(flash, backward=True),
        "param_labels": sorted({
            "_".join(str(d) for d in x.shape) + "_" for x in leaves}),
    }
    result["metrics"] = layer_metrics.compute(ctx.cell, "train", evidence)
    result["device"].update(busy_s=reduction.busy_s,
                            window_s=reduction.window_s)
    result["breakdown"] = reduction.breakdown()
    return result
