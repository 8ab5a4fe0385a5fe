"""The runners' parts at tiny size on the CPU (the command itself
refuses to measure there)."""

import json
import os
import subprocess
import sys
import time

import pytest

from benchmarks import contract
from benchmarks.compile_meter import CompileMeter
from benchmarks.runners import serve, train
from benchmarks.runners.common import Context

TINY = os.path.join(os.path.dirname(__file__), "tiny")


def _load(name):
    with open(os.path.join(TINY, name)) as f:
        return json.load(f)


def _ctx(cell, config, traffic, seconds, tmp_path):
    return Context(cell=cell, config=_load(config), traffic=_load(traffic),
                   seed=2 ** 31 + 11, seconds=seconds, trace=False,
                   t_start=time.monotonic(), root=str(tmp_path),
                   meter=CompileMeter())


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    ctx = _ctx({"name": "tiny-serve", "chips": 1}, "tiny-serve.json",
               "tiny-chat.json", 3.0, tmp_path_factory.mktemp("serve"))
    return ctx, serve.run(ctx)


def test_serve_run_is_correct_and_counts_every_due_request(served):
    ctx, result = served
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] == int(ctx.traffic["rate"] * ctx.seconds)
    assert set(result) >= {"correct", "attempted", "failed", "metrics",
                           "device"}
    assert set(result["device"]) >= {"platform", "kind", "count",
                                     "memory_peak_bytes"}


def test_serve_run_reports_the_end_to_end_metrics(served):
    _, result = served
    metrics = result["metrics"]
    assert set(metrics) == {"ttft_p90_s", "itl_mean_s", "serve_tok_s",
                            "setup_s"}
    assert all(m["value"] > 0 for m in metrics.values())
    assert metrics["serve_tok_s"]["unit"] == "tokens/s"


def test_serve_setup_breakdown_names_its_phases(served):
    ctx, result = served
    for phase in ("weights", "engine", "warm", "ramp", "setup_s",
                  "programs"):
        assert phase in ctx.phases
    assert ctx.phases["setup_s"] == result["metrics"]["setup_s"]["value"]
    assert ctx.phases["setup_s"] >= ctx.phases["weights"] + \
        ctx.phases["engine"] + ctx.phases["warm"] + ctx.phases["ramp"]


def test_warm_plan_follows_the_traffic_file():
    traffic, config = _load("tiny-chat.json"), _load("tiny-serve.json")
    prefill, decode = serve.warm_plan(traffic, config["deployment"])
    # 16 tracked sequences: decode buckets 8 and 16, reached by 5 and 9
    assert decode == [5, 9]
    chunk = config["deployment"]["prefill_chunk"]
    assert all(1 <= length <= chunk for _, length in prefill)
    assert all(lanes * length <= config["deployment"]
               ["max_ragged_batch_size"] for lanes, length in prefill)
    real = contract.load_traffic("chat-steady")
    dep = contract.load_config(contract.load_benchmark(),
                               "mistral-7b-serve-l8")["deployment"]
    prefill, decode = serve.warm_plan(real, dep)
    assert decode == [5, 9, 17, 33, 65]
    assert max(length for _, length in prefill) <= 512
    assert all(lanes * length <= 768 for lanes, length in prefill)


def test_window_numbers_count_what_is_due_inside():
    class Req:
        def __init__(self, uid):
            self.uid, self.reject_reason, self.error = uid, "", ""

    rows = [{"due": 9.0, "sent": 9.0, "submitted": 9.0, "req": Req(0)},
            {"due": 10.5, "sent": 10.6, "submitted": 10.7, "req": Req(1)},
            {"due": 12.0, "sent": 12.0, "submitted": 12.0, "req": Req(2)},
            {"due": 21.0, "sent": 21.0, "submitted": 21.0, "req": Req(3)}]
    stamps = {0: [9.5, 10.5, 11.5], 1: [11.0, 11.4, 20.5], 3: [21.5]}
    got = serve.window_numbers(rows, stamps, 10.0, 20.0, 22.0)
    assert got["attempted"] == 2 and got["failed"] == 1
    assert got["ttft"] == pytest.approx([0.5, 10.0])
    assert got["late"] == pytest.approx([0.1, 0.0])
    assert got["submit_wait"] == pytest.approx([0.1, 0.0])
    assert got["tokens_in_window"] == 4
    assert sorted(got["gaps"]) == pytest.approx([0.4, 1.0, 1.0])


def test_train_run_is_correct_on_four_virtual_devices(tmp_path):
    ctx = _ctx({"name": "tiny-train", "chips": 4}, "tiny-train.json",
               "tiny-pretrain.json", 2.0, tmp_path)
    result = train.run(ctx)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 3
    assert set(result["metrics"]) == {"train_tok_s_chip", "setup_s"}
    assert result["metrics"]["train_tok_s_chip"]["value"] > 0
    assert result["device"]["count"] >= 4


def test_the_command_refuses_to_measure_without_a_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "--workload",
         contract.load_benchmark()["workloads"][0]["name"], "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=contract.ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert proc.returncode != 0
    assert "no accelerator" in proc.stderr
    assert not proc.stdout.strip().startswith("{")
