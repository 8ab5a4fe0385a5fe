"""Every cell's files load by name, BENCHMARK.json keeps to the
contract's letter, and a new metric is a new file."""

import json
import os
import re
import shutil

import pytest

from benchmarks import contract, layer_metrics

BENCH = contract.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.mark.parametrize("cell", BENCH["workloads"],
                         ids=[c["name"] for c in BENCH["workloads"]])
def test_cell_files_load_by_name(cell):
    config = contract.load_config(BENCH, cell["config"])
    traffic = contract.load_traffic(cell["traffic"])
    assert config["chips"] == cell["chips"] in (1, 4)
    runner = contract.load_kind("runners", config["runner"])
    assert callable(runner.run)
    gen = contract.load_kind("generators", traffic["kind"])
    assert gen is not None
    assert traffic["name"] == cell["traffic"]
    assert len(cell["why"]) <= 200 and "\n" not in cell["why"]
    specs = contract.load_metric_specs()
    mine = [s for s in specs.values()
            if contract.metric_applies(s, cell, config["runner"])]
    assert mine, "a cell reports at least one per-layer metric"


def test_top_level_keys_are_the_contracts():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(contract.ROOT,
                                        "BENCHMARK.json")) < 64 * 1024
    for path in BENCH["paths"]:
        assert os.path.isdir(os.path.join(contract.ROOT, path))
    four = [c for c in BENCH["workloads"] if c["chips"] == 4]
    assert len(four) <= max(1, len(BENCH["workloads"]) // 4)


@pytest.mark.parametrize(
    "entry", BENCH["end_to_end"] + BENCH["per_layer"],
    ids=[m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]])
def test_metric_entries_keep_to_the_letter(entry):
    assert NAME.match(entry["name"]) and UNIT.match(entry["unit"])
    assert entry["better"] in ("lower", "higher")
    assert entry["source"] in SOURCES
    cells = {c["name"] for c in BENCH["workloads"]}
    assert set(entry.get("workloads", cells)) <= cells
    if "bound" in entry:
        assert set(entry) <= {"name", "unit", "better", "bound", "source",
                              "workloads"}
        assert 0.01 <= entry["bound"] <= 0.1
        assert entry["source"] in ("host_clock", "device_trace")
    else:
        assert set(entry) <= {"name", "unit", "better", "source", "layer",
                              "moves", "workloads"}
        e2e = {m["name"]: m for m in BENCH["end_to_end"]}
        assert entry["moves"] in e2e
        moved = e2e[entry["moves"]]
        assert set(entry.get("workloads", cells)) <= \
            set(moved.get("workloads", cells))


def test_names_are_unique_and_setup_is_everywhere():
    names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(names) == len(set(names))
    setup = next(m for m in BENCH["end_to_end"] if m["name"] == "setup_s")
    assert "workloads" not in setup and setup["bound"] <= 0.1
    for group in ("configs", "workloads"):
        got = [x["name"] for x in BENCH[group]]
        assert len(got) == len(set(got)) and all(NAME.match(n) for n in got)


@pytest.mark.parametrize("entry", BENCH["per_layer"],
                         ids=[m["name"] for m in BENCH["per_layer"]])
def test_each_per_layer_metric_has_its_file(entry):
    spec = contract.load_metric_specs()[entry["name"]]
    for key in ("unit", "better", "layer", "moves", "source"):
        assert spec[key] == entry[key], key
    contract.load_kind("reducers", spec["reads"])
    runner = spec["cells"].get("runner")
    cells = [c["name"] for c in BENCH["workloads"]
             if contract.load_config(BENCH, c["config"])["runner"] == runner]
    assert sorted(entry["workloads"]) == sorted(cells)


def test_config_files_name_their_cuts():
    for entry in BENCH["configs"]:
        config = contract.load_config(BENCH, entry["name"])
        assert config["source"] == entry["source"]
        assert sorted(config["reduced"]) == sorted(entry["reduced"])
        assert config["hidden_size"] == 4096
        assert config["intermediate_size"] == 14336
        assert config["num_attention_heads"] == 32
        assert config["num_key_value_heads"] == 8
        assert config["vocab_size"] == 32000
        assert "assumed" in config and "stands_for" in config


def test_a_metric_added_as_a_file_is_picked_up(tmp_path):
    bench_dir = tmp_path / "benchmarks"
    shutil.copytree(os.path.join(contract.HERE, "metrics"),
                    bench_dir / "metrics")
    new = {"name": "ttft_max_s", "layer": "scheduler", "unit": "s",
           "better": "lower", "moves": "ttft_p90_s", "source": "host_clock",
           "cells": {"workloads": ["some-new-cell"]}, "reads": "series",
           "series": "ttft_s", "how": "max"}
    (bench_dir / "metrics" / "ttft_max_s.json").write_text(json.dumps(new))
    specs = contract.load_metric_specs(str(bench_dir))
    assert "ttft_max_s" in specs
    evidence = {"series": {"ttft_s": [0.2, 0.9, 0.4]}}
    got = layer_metrics.compute({"name": "some-new-cell"}, "serve",
                                evidence, specs)
    assert got["ttft_max_s"] == {"value": 0.9, "unit": "s"}
    # by runner kind it also gets whatever the serve cells get and the
    # evidence holds; nothing of the train cells
    assert "train_mfu" not in got
    other = layer_metrics.compute({"name": "another"}, "serve", evidence,
                                  specs)
    assert "ttft_max_s" not in other


def test_a_reader_with_nothing_to_read_leaves_the_metric_out():
    got = layer_metrics.compute(BENCH["workloads"][0], "serve",
                                {"series": {"gen_late_s": [0.001, 0.003]}})
    assert set(got) == {"gen_late_p90_s"}


def test_unknown_names_are_errors():
    with pytest.raises(contract.ContractError):
        contract.find_cell(BENCH, "no-such-cell")
    with pytest.raises(contract.ContractError):
        contract.load_kind("generators", "no_such_kind")
    with pytest.raises(contract.ContractError):
        contract.load_traffic("no-such-mix")
    with pytest.raises(contract.ContractError):
        contract.apply_overrides({"rate": 1.0}, ["speed=2"])
    assert contract.apply_overrides({"rate": 1.0}, ["rate=2.5"]) == \
        {"rate": 2.5}
