"""Server frontend: ingress admission control, thread mode, and the
metrics -> MonitorMaster event-path wiring."""

import time

import numpy as np
import pytest

from hcache_deepspeed_tpu.inference import RaggedInferenceEngineConfig
from hcache_deepspeed_tpu.serving import (Request, ServerConfig,
                                          ServingMetrics, ServingServer,
                                          SimulatedEngine, VirtualClock)


def sim_engine(num_blocks=9):
    return SimulatedEngine(RaggedInferenceEngineConfig(
        state_manager={"max_tracked_sequences": 8,
                       "max_ragged_batch_size": 128,
                       "max_ragged_sequence_count": 4,
                       "max_context": 128},
        kv_cache={"block_size": 8, "num_blocks": num_blocks}))


def test_queue_full_rejection():
    srv = ServingServer(sim_engine(), clock=VirtualClock(),
                        config=ServerConfig(max_queue_depth=2,
                                            kv_demand_fraction=1e9))
    rs = [srv.submit(prompt=list(range(8)), max_new_tokens=2)
          for _ in range(4)]
    rejected = [r for r in rs if r.state.name == "REJECTED"]
    assert len(rejected) == 2
    assert all(r.reject_reason == "queue_full" for r in rejected)
    assert srv.metrics.rejected["queue_full"] == 2
    # the accepted two still run to completion
    while srv.scheduler.has_work or srv._ingress:
        srv.step()
    assert sum(r.state.name == "DONE" for r in rs) == 2


def test_kv_overload_rejection():
    # 8 usable blocks; demand cap 1.0x => ~2 requests of 3 blocks fit
    # the budget, the rest reject with a distinct reason
    srv = ServingServer(sim_engine(), clock=VirtualClock(),
                        config=ServerConfig(max_queue_depth=100,
                                            kv_demand_fraction=1.0))
    rs = [srv.submit(prompt=list(range(16)), max_new_tokens=8)
          for _ in range(4)]
    rejected = [r for r in rs if r.state.name == "REJECTED"]
    assert rejected and all(r.reject_reason == "kv_overload"
                            for r in rejected)
    accepted = [r for r in rs if r.state.name != "REJECTED"]
    assert accepted
    while srv.scheduler.has_work or srv._ingress:
        srv.step()
    assert all(r.state.name == "DONE" for r in accepted)


def test_metrics_flow_through_monitor_event_path():
    from hcache_deepspeed_tpu.monitor import InMemoryMonitor

    mon = InMemoryMonitor(capacity=256)
    srv = ServingServer(sim_engine(), clock=VirtualClock(),
                        monitor=mon, emit_every_steps=1,
                        config=ServerConfig(kv_demand_fraction=1e9))
    srv.run_trace([Request(uid=0, prompt=list(range(8)),
                           max_new_tokens=3, arrival_time=0.0)])
    labels = set(mon.latest)
    # the MonitorMaster tuple protocol: (label, value, step)
    assert all(len(e) == 3 for e in mon.events)
    assert "serving/kv_utilization" in labels
    assert "serving/batch_occupancy" in labels
    assert "serving/ttft_s/p50" in labels
    assert all(isinstance(v, float) for _, v, _ in mon.events)
    # latest-value view reflects the final emission
    value, step = mon.latest["serving/finished"]
    assert value == 1.0 and step == srv.scheduler.step_idx
    assert len(mon.events) <= mon.capacity


def test_thread_mode_serves_submissions():
    srv = ServingServer(sim_engine(num_blocks=20),
                        config=ServerConfig(idle_sleep_s=0.001,
                                            kv_demand_fraction=1e9))
    srv.start()
    try:
        rs = [srv.submit(prompt=list(range(10)), max_new_tokens=4)
              for _ in range(6)]
        for r in rs:
            srv.wait(r, timeout=30.0)
    finally:
        srv.stop()
    assert all(r.state.name == "DONE" for r in rs)
    assert all(len(r.tokens_out) == 4 for r in rs)
    # same stream a synchronous run produces (engine determinism holds
    # across the thread boundary because one thread owns the engine)
    ref = ServingServer(sim_engine(num_blocks=20), clock=VirtualClock(),
                        config=ServerConfig(kv_demand_fraction=1e9))
    ref_reqs = [Request(uid=r.uid, prompt=list(r.prompt),
                        max_new_tokens=4, arrival_time=0.0) for r in rs]
    ref.run_trace(ref_reqs)
    assert [r.tokens_out for r in rs] == \
        [r.tokens_out for r in ref_reqs]


def test_a_blocked_submit_announces_itself_to_the_loop():
    """``threading.Lock`` is not fair: the loop thread releases the
    server lock after a step and retakes it microseconds later, so a
    ``submit()`` from another thread used to wait until the scheduler
    ran out of work (with a real engine under load: for the whole
    backlog). A caller blocked on the lock now leaves a marker the loop
    reads between steps to stand back. The end-to-end effect — a request
    submitted beside decoding residents preempts one of them — is what
    ``chip_smoke.py``'s serve phase depends on
    (test_chip_entry_points.py); here the marker's lifetime."""
    import threading
    srv = ServingServer(sim_engine(num_blocks=20),
                        config=ServerConfig(kv_demand_fraction=1e9))
    done = []
    srv._lock.acquire()                   # the loop, mid-step
    try:
        caller = threading.Thread(target=lambda: done.append(
            srv.submit(prompt=list(range(10)), max_new_tokens=4)))
        caller.start()
        deadline = time.monotonic() + 5.0
        while not srv._lock_waiters:
            assert time.monotonic() < deadline
        assert len(srv._lock_waiters) == 1 and not done
    finally:
        srv._lock.release()
    caller.join(timeout=5.0)
    assert not caller.is_alive() and len(done) == 1
    assert not srv._lock_waiters


def test_wait_raises_typed_timeout_with_the_live_request():
    """A request still live when the time is up is an error carrying
    its state, never a quiet return of the unfinished request."""
    from hcache_deepspeed_tpu.serving import RequestTimeout
    srv = ServingServer(sim_engine(num_blocks=20),
                        config=ServerConfig(kv_demand_fraction=1e9))
    # never started: nothing drains the ingress queue
    r = srv.submit(prompt=list(range(10)), max_new_tokens=4)
    with pytest.raises(RequestTimeout) as exc:
        srv.wait(r, timeout=0.05)
    assert isinstance(exc.value, TimeoutError)
    assert exc.value.request is r and not r.finished
    assert r.state.name in str(exc.value)


def test_serving_metrics_histograms():
    m = ServingMetrics()
    for v in (0.1, 0.2, 0.3, 0.4):
        m.ttft.observe(v)
    assert m.ttft.count == 4
    assert m.ttft.percentile(50) == np.percentile([0.1, 0.2, 0.3, 0.4],
                                                  50)
    s = m.ttft.summary()
    assert s["count"] == 4 and s["p90"] >= s["p50"]
