"""Serving benchmark smoke (reference: the FastGen bench harness) —
keeps the measurement tool itself green across engine changes."""

import numpy as np
import pytest

from hcache_deepspeed_tpu.inference.benchmark import run


def test_serve_bench_all_modes():
    for kw in ({}, {"quantize": "int8"}, {"prefill_chunk": 32}):
        results = run(model_size="tiny", max_context=128, prompt_len=32,
                      decode_steps=4, batches=(1,), **kw)
        phases = {r["phase"] for r in results}
        assert "prefill" in phases and "decode" in phases
        assert "decode-context-scaling" in phases
        for r in results:
            if "tokens_per_sec" in r:
                assert r["tokens_per_sec"] > 0


def test_serve_bench_fused_mode():
    results = run(model_size="tiny", max_context=128, prompt_len=32,
                  decode_steps=4, batches=(1,), fused=True)
    phases = {r["phase"] for r in results}
    assert "decode-fused" in phases


def test_serve_bench_fused_oom_falls_back_to_host_decode(monkeypatch):
    """A fused-decode compile OOM (seen at 7B bf16 on a 16 GB chip:
    stacked-QKV layout copies) must not kill the measurement — the tool
    emits an error row and still produces host-driven decode numbers."""
    from hcache_deepspeed_tpu.inference.engine_v2 import InferenceEngineV2

    def boom(self, prompts, **kw):
        raise RuntimeError(
            "RESOURCE_EXHAUSTED: Ran out of memory in memory space hbm")

    monkeypatch.setattr(InferenceEngineV2, "generate_fused", boom)
    results = run(model_size="tiny", max_context=128, prompt_len=32,
                  decode_steps=4, batches=(1,), fused=True)
    phases = [r["phase"] for r in results]
    oom_rows = [r for r in results
                if r["phase"] == "decode-fused" and "error" in r]
    host_rows = [r for r in results
                 if r["phase"] == "decode" and "note" in r]
    assert oom_rows and host_rows
    assert host_rows[0]["tokens_per_sec"] > 0
    # context-scaling phase still runs after the fallback
    assert "decode-context-scaling" in phases


def test_serve_bench_sweep():
    from hcache_deepspeed_tpu.inference.benchmark import run_sweep
    rows = run_sweep(model_size="tiny", max_context=128, prompt_len=16,
                     max_new=4, rates=(50.0,), n_requests=5, max_batch=4)
    (row,) = rows
    assert row["phase"] == "sweep"
    assert row["effective_rps"] > 0
    assert row["ttft_s"]["p50"] <= row["e2e_s"]["p50"]
    assert row["gen_tokens_per_sec"] > 0


def test_serve_bench_lookup_mode():
    results = run(model_size="tiny", max_context=128, prompt_len=32,
                  decode_steps=8, batches=(2,), lookup=True)
    rows = {r["phase"]: r for r in results}
    assert rows["decode-lookup"]["dispatches"] >= 1
    assert rows["decode-lookup"]["tokens_per_dispatch"] >= 1.0
    assert rows["decode-lookup-fused"]["device_steps"] >= 1
    assert rows["decode-lookup-fused"]["tokens_per_device_step"] >= 1.0


def test_serve_bench_sweep_fused():
    from hcache_deepspeed_tpu.inference.benchmark import run_sweep_fused
    rows = run_sweep_fused(model_size="tiny", max_context=128,
                           prompt_len=16, max_new=4, rates=(50.0,),
                           n_requests=5, max_batch=4)
    (row,) = rows
    assert row["phase"] == "sweep-fused"
    assert row["decode_path"] == "fused"
    assert row["effective_rps"] > 0
    assert row["waves"] >= 2   # 5 requests, max_batch 4
    assert row["gen_tokens_per_sec"] > 0


def test_bench_model_sizes_trace():
    """The 1b/7b bench configs must build and trace (eval_shape — no
    weights materialized) with sane parameter counts, so a 7B chip
    run can't die on a config bug."""
    import jax
    from hcache_deepspeed_tpu.models.llama import (LlamaConfig,
                                                   LlamaForCausalLM)
    from hcache_deepspeed_tpu.inference.benchmark import _MODEL_SIZES
    # exact arithmetic: per-layer 4h^2 + 3*h*ffn, plus two vocab
    # matrices (untied embed + head)
    sizes = {"1b": 1.35e9, "7b": 6.74e9}
    for name in sizes:
        assert name in _MODEL_SIZES, name
    for name in sizes:
        spec = _MODEL_SIZES[name]
        cfg = LlamaConfig(max_positions=512, dtype="bfloat16",
                          use_flash=False, **spec)
        model = LlamaForCausalLM(cfg)
        shapes = jax.eval_shape(
            lambda k: model.init(k, {"input_ids": np.zeros((1, 8),
                                                           np.int32)},
                                 train=False),
            jax.random.PRNGKey(0))
        n = sum(int(np.prod(x.shape))
                for x in jax.tree.leaves(shapes["params"]))
        assert abs(n - sizes[name]) / sizes[name] < 0.15, (name, n)


def test_serve_bench_restore_mode():
    from hcache_deepspeed_tpu.inference.benchmark import run_restore
    rows = run_restore(model_size="tiny", max_context=128, prompt_len=16,
                       batches=(1,))
    (row,) = rows
    assert row["phase"] == "hcache-restore"
    assert row["restore_kv_ms"] > 0 and row["prefill_recompute_ms"] > 0


def test_serve_bench_restore_marginal_mode():
    """Marginal decomposition: device replay cost vs link ship cost
    (chained dispatches, one sync)."""
    from hcache_deepspeed_tpu.inference.benchmark import \
        run_restore_marginal
    rows = run_restore_marginal(model_size="tiny", max_context=128,
                                prompt_len=16, batches=(1, 2), chain=3)
    assert len(rows) == 2
    for row in rows:
        assert row["phase"] == "hcache-restore-marginal"
        # CPU slope timings are noise-dominated on the tiny model — this
        # smoke asserts row shape/sanity, not magnitudes
        for key in ("replay_ms", "prefill_ms", "restore_e2e_ms",
                    "ship_ms"):
            assert row[key] >= 0, (key, row)
        assert row["link_gbps"] > 0


def test_serve_loop_mode(tmp_path):
    """serve_loop: the serving subsystem end-to-end over a Poisson
    trace — zero drops, percentile rows, and at least one
    preempt→suspend→restore_kv cycle with exact token parity (the
    runner raises on drops or parity failure). Virtual clock keeps the
    test deterministic and fast; the acceptance command runs the same
    path with the wall clock."""
    from hcache_deepspeed_tpu.inference.benchmark import run_serve_loop
    out = tmp_path / "serve_loop.jsonl"
    rows = run_serve_loop(model_size="tiny", n_requests=16, rps=100.0,
                          virtual_clock=True, out=str(out))
    summary = rows[-1]
    assert summary["phase"] == "serve-loop-summary"
    assert summary["dropped"] == 0
    assert summary["preemptions"] >= 1 and summary["restores"] >= 1
    assert summary["parity"]["checked"] >= 1
    assert summary["parity"]["ok"] == summary["parity"]["checked"]
    assert summary["ttft_s"]["count"] == 16
    assert summary["ttft_s"]["p90"] >= summary["ttft_s"]["p50"]
    assert summary["tpot_s"]["p50"] > 0
    per_req = [r for r in rows if r["phase"] == "serve-loop"]
    assert len(per_req) == 16
    assert all(r["state"] == "DONE" for r in per_req)
    # the artifact file mirrors the emitted rows
    import json as _json
    lines = [_json.loads(l) for l in out.read_text().splitlines()]
    assert len(lines) == len(rows)


def test_serve_loop_overlap_ratio_positive(tmp_path):
    """The acceptance gate: the span-derived restore-overlap ratio in
    the serve_loop artifact is > 0 (restore lanes genuinely advance
    under resident decode) and agrees with the scheduler counters."""
    from hcache_deepspeed_tpu.inference.benchmark import run_serve_loop
    rows = run_serve_loop(model_size="tiny", n_requests=16, rps=100.0,
                          virtual_clock=True,
                          out=str(tmp_path / "sl.jsonl"))
    summary = rows[-1]
    assert summary["restore_overlap_ratio"] > 0
    span_rs = summary["extra"]["step_breakdown"]["restore"]
    assert span_rs["overlap_ratio"] == pytest.approx(
        summary["restore_overlap_ratio"])
    assert span_rs["overlap_ratio"] > 0
    assert span_rs["chunks_issued"] >= span_rs["scheduler_restores"]


def test_serve_bench_restore_crossover_mode(tmp_path):
    """restore_crossover: one JSONL row per prompt length carrying the
    measured marginal costs AND the analytic model's verdict, plus a
    summary row with the calibrated rates — and the model's choice
    always matches its own cheaper analytic side."""
    from hcache_deepspeed_tpu.inference.benchmark import \
        run_restore_crossover
    out = tmp_path / "crossover.jsonl"
    rows = run_restore_crossover(model_size="tiny", max_context=128,
                                 prompt_lens=(16, 48), chain=2,
                                 out=str(out))
    curve = [r for r in rows if r["phase"] == "restore-crossover"]
    assert [r["prompt_len"] for r in curve] == [16, 48]
    for row in curve:
        assert row["prefill_ms"] >= 0 and row["restore_ms"] >= 0
        assert row["model_choice"] in ("restore", "recompute")
        assert row["measured_winner"] in ("restore", "recompute")
        cheaper = "restore" if row["restore_pred_ms"] <= \
            row["recompute_pred_ms"] else "recompute"
        assert row["model_choice"] == cheaper
    summary = rows[-1]
    assert summary["phase"] == "restore-crossover-summary"
    assert summary["calibration"]["calibrated"]
    assert summary["calibration"]["samples"]["prefill"] >= 2
    import json as _json
    lines = [_json.loads(l) for l in out.read_text().splitlines()]
    assert len(lines) == len(rows)
