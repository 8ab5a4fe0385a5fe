"""Restore-vs-recompute crossover policy + decode-interleaved lanes.

Deterministic (VirtualClock + SimulatedEngine) coverage of the
re-entry policy: the analytic model's crossover shape under a
synthetic bandwidth (recompute for short cached prefixes, restore for
long ones), the scheduler consulting it per preempted sequence, token
parity through BOTH re-entry mechanisms, multi-step lane overlap
accounting, and trace determinism with the policy on.
"""

import numpy as np
import pytest

from hcache_deepspeed_tpu.inference import RaggedInferenceEngineConfig
from hcache_deepspeed_tpu.serving import (CrossoverConfig, Request,
                                          RestoreCrossoverModel,
                                          ServerConfig, ServingServer,
                                          SimulatedEngine, VirtualClock)

PROFILE = {"n_layer": 2, "latent_bytes_per_token": 32,
           "replay_flops_frac": 0.5, "restore_chunk_layers": 1,
           "restore_chunk_bytes": 0}


def make_model(chunk_overhead_s=5e-3, attn=1e-6, link=1e9,
               prefill=1e4, **cfg_over):
    """Synthetic-bandwidth model: restore pays 2 chunk dispatches
    (10 ms fixed) + a fast link + half-rate replay; recompute pays one
    dispatch + the full stack + a quadratic attention term. Crossover
    lands near T ~ 48."""
    model = RestoreCrossoverModel(
        PROFILE, CrossoverConfig(chunk_overhead_s=chunk_overhead_s,
                                 attn_s_per_token2=attn,
                                 min_samples=1, **cfg_over))
    model.observe_ship(1e6, 1e6 / link)
    model.observe_prefill(1e4, 1e4 / prefill)
    return model


def sim_server(latents=True, crossover=None, **over):
    kw = dict(state_manager={"max_tracked_sequences": 8,
                             "max_ragged_batch_size": 128,
                             "max_ragged_sequence_count": 4,
                             "max_context": 128},
              kv_cache={"block_size": 8, "num_blocks": 9},
              hcache={"enable_latents": latents})
    for k, v in over.items():
        kw[k].update(v) if k in kw else kw.update({k: v})
    eng = SimulatedEngine(RaggedInferenceEngineConfig(**kw))
    return ServingServer(eng, clock=VirtualClock(),
                         config=ServerConfig(
                             kv_demand_fraction=float("inf")),
                         crossover=crossover)


def req(uid, n_prompt=20, max_new=8, t=0.0, prio=0, **kw):
    return Request(uid=uid, prompt=list(range(n_prompt)),
                   max_new_tokens=max_new, arrival_time=t,
                   priority=prio, **kw)


def preempt_trace():
    return [req(0, n_prompt=20, max_new=20, t=0.0, prio=0),
            req(1, n_prompt=20, max_new=20, t=0.0, prio=0),
            req(2, n_prompt=20, max_new=8, t=0.01, prio=5)]


def uninterrupted_tokens(engine_factory, r):
    eng = engine_factory()
    logits, _ = eng.put([r.uid], [r.prompt])
    out = [int(np.argmax(logits[0]))]
    for _ in range(r.max_new_tokens - 1):
        logits, _ = eng.put([r.uid], [[out[-1]]])
        out.append(int(np.argmax(logits[0])))
    return out


def events(server, kind):
    return [e for e in server.scheduler.events if e[1] == kind]


# ------------------------------------------------------------------ #
# the analytic model itself
# ------------------------------------------------------------------ #
def test_uncalibrated_model_defaults_to_restore():
    model = RestoreCrossoverModel(PROFILE,
                                  CrossoverConfig(min_samples=1))
    assert not model.calibrated
    assert model.decide(10_000) == "restore"


def test_crossover_short_recompute_long_restore():
    """The curve shape the benchmark measures: the model must pick the
    cheaper side at every point, with ONE flip — recompute below the
    crossover, restore above it."""
    model = make_model()
    lengths = [8, 16, 32, 64, 128, 256]
    decisions = [model.decide(t) for t in lengths]
    # each decision matches the cheaper analytic side
    for t, d in zip(lengths, decisions):
        cheaper = "restore" if model.restore_cost_s(t) <= \
            model.recompute_cost_s(t) else "recompute"
        assert d == cheaper
    assert decisions[0] == "recompute"
    assert decisions[-1] == "restore"
    flips = sum(a != b for a, b in zip(decisions, decisions[1:]))
    assert flips == 1, decisions


def test_occupancy_shifts_crossover_toward_restore():
    """A busy batch slows both compute terms but not the link, so the
    same length can flip from recompute (idle) to restore (loaded)."""
    model = make_model()
    t = 40            # just below the idle crossover (~48)
    assert model.decide(t, occupancy=0.0) == "recompute"
    assert model.decide(t, occupancy=1.0) == "restore"


# ------------------------------------------------------------------ #
# scheduler integration (deterministic sim)
# ------------------------------------------------------------------ #
def test_scheduler_recompute_reentry_token_parity():
    # overhead so large every restore loses: all re-entries recompute
    model = make_model(chunk_overhead_s=10.0)
    srv = sim_server(crossover=model)
    reqs = preempt_trace()
    srv.run_trace(reqs)
    sched = srv.scheduler
    assert sched.total_recomputes >= 1
    assert sched.total_restores == 0
    assert any("mode=recompute" in e[3] for e in events(srv, "restore"))
    assert all(r.state.name == "DONE" for r in reqs)
    pre = [r for r in reqs if r.n_preemptions > 0]
    assert pre and all(r.n_recomputes >= 1 for r in pre)
    # the recomputed stream equals an uninterrupted run — the policy
    # may change COST, never tokens
    for r in pre:
        assert r.tokens_out == uninterrupted_tokens(
            lambda: sim_server().scheduler.engine, r)
    assert srv.metrics.counters["recompute_reentries"] == \
        sched.total_recomputes


def test_scheduler_restore_when_model_prefers_it():
    # zero fixed overhead + fast link: restore always wins
    model = make_model(chunk_overhead_s=0.0, attn=1e-4)
    srv = sim_server(crossover=model)
    reqs = preempt_trace()
    srv.run_trace(reqs)
    sched = srv.scheduler
    assert sched.total_restores >= 1
    assert sched.total_recomputes == 0
    assert all(r.state.name == "DONE" for r in reqs)
    pre = [r for r in reqs if r.n_preemptions > 0]
    for r in pre:
        assert r.tokens_out == uninterrupted_tokens(
            lambda: sim_server().scheduler.engine, r)


def test_recompute_infeasible_falls_back_to_restore():
    # model demands recompute, but the cached prefix overflows the
    # per-forward token budget — the scheduler must restore instead
    model = make_model(chunk_overhead_s=10.0)
    srv = sim_server(crossover=model,
                     state_manager={"max_ragged_batch_size": 21})
    reqs = preempt_trace()
    srv.run_trace(reqs)
    sched = srv.scheduler
    assert all(r.state.name == "DONE" for r in reqs)
    assert sched.total_recomputes == 0
    assert sched.total_restores >= 1


# ------------------------------------------------------------------ #
# decode-interleaved lanes
# ------------------------------------------------------------------ #
def test_lane_spans_steps_and_overlap_ratio_positive():
    """The sim engine's 2-chunk lanes at 1 chunk/step keep a request
    RESTORING across >= 2 steps; a lane advancing while residents
    decode earns exactly one overlap credit, so the span-derived ratio
    the telemetry computes is > 0 (the acceptance gate)."""
    srv = sim_server()          # default crossover: uncalibrated ⇒ lanes
    reqs = preempt_trace()
    srv.run_trace(reqs)
    sched = srv.scheduler
    assert sched.total_restores >= 1
    assert sched.overlapped_restores >= 1
    assert srv.metrics.gauges["restore_overlap_ratio"] > 0
    assert srv.metrics.counters["restore_chunks"] == \
        2 * sched.total_restores
    # begin/completion pairing: every lane opened also completed
    assert len(events(srv, "restore_begin")) == sched.total_restores
    modes = [e for e in events(srv, "restore")
             if "mode=latents" in e[3]]
    assert len(modes) == sched.total_restores
    assert all(r.state.name == "DONE" for r in reqs)


def test_crossover_trace_determinism():
    def trace(seed):
        rng = np.random.default_rng(seed)
        t, out = 0.0, []
        for i in range(16):
            t += float(rng.exponential(0.01))
            out.append(Request(
                uid=i,
                prompt=list(rng.integers(0, 64,
                                         int(rng.integers(4, 24)))),
                max_new_tokens=int(rng.integers(2, 10)),
                arrival_time=t, priority=int(rng.integers(0, 3))))
        return out

    srv1 = sim_server(crossover=make_model())
    srv2 = sim_server(crossover=make_model())
    srv1.run_trace(trace(7))
    srv2.run_trace(trace(7))
    assert srv1.scheduler.events == srv2.scheduler.events
    assert srv1.metrics.summary() == srv2.metrics.summary()
