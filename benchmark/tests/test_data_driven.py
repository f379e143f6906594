"""BENCHMARK.json, the data files and the harness agree, and a cell, a
configuration and a per-layer metric over an existing reader can each be
added by adding files only (the recipes of benchmark/README.md)."""
import fnmatch
import json
import os
import shutil

import pytest

from benchmark.harness import readers, server, tracefacts, traffic

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_every_cell_finds_its_files(bench):
    configs = {c["name"]: c for c in bench["configs"]}
    for w in bench["workloads"]:
        c = configs[w["config"]]
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"]
        for k in cfg["reduced"]:
            assert k in cfg["published"]
        hf = server.hf_config(cfg, rehearsal=False)
        assert hf["localai_synthetic"] is True and "serving" not in hf
        spec = traffic.load_traffic(BENCH, w["name"], w["traffic"])
        assert spec["rate_rps"] == pytest.approx(spec["knee_rps"] * spec["factor"])
        assert spec["file"] == f"traffic/{w['name']}.json"


def test_per_layer_entries_match_their_files(bench):
    layer = readers.load_layer_metrics(BENCH)
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    cells = [w["name"] for w in bench["workloads"]]
    assert {m["name"] for m in bench["per_layer"]} == set(layer)
    for m in bench["per_layer"]:
        spec = layer[m["name"]]
        for k in ("unit", "better", "source", "layer", "moves"):
            assert spec[k] == m[k], (m["name"], k)
        assert spec["reader"] in readers.READERS
        mine = [c for c in cells if readers.applies(spec, c)]
        assert mine == [c for c in cells
                        if "workloads" not in m or c in m["workloads"]]
        moved = e2e[m["moves"]]
        for c in mine:          # the metric it moves is reported wherever it is
            assert "workloads" not in moved or c in moved["workloads"]


def test_peaks_and_program_classes_are_data():
    with open(os.path.join(BENCH, "peaks", "TPU_v5_lite.json")) as f:
        p = json.load(f)
    assert p["device_kind"] == "TPU v5 lite" and p["hbm_bytes_per_s"] == 819e9
    assert p["bf16_flops"] == 197e12 and p["source"]
    assert set(tracefacts.load_program_classes(BENCH)) == {"decode", "prefill"}


def test_a_cell_a_config_and_a_metric_are_added_by_files_only(tmp_path):
    bench_dir = tmp_path / "benchmark"
    shutil.copytree(BENCH, bench_dir, ignore=shutil.ignore_patterns(
        "__pycache__", "tests", "recorded"))
    # a cell: one traffic file (the entry in BENCHMARK.json is the other half)
    (bench_dir / "traffic" / "mistral-7b.chat-burst.json").write_text(json.dumps({
        "extends": "chat", "rate_rps": 2.0, "knee_rps": 2.5, "factor": 0.8,
        "burst": {"size_min": 8, "size_max": 24, "within_s": 0.2}}))
    spec = traffic.load_traffic(str(bench_dir), "mistral-7b.chat-burst", "chat-burst")
    assert spec["burst"]["size_max"] == 24 and spec["prompt_tokens"]["median"] == 256
    assert traffic.schedule(spec, 3, 20, 32768, 2048, 4)
    # a configuration: one file (here the dense model the expert one shares
    # its code with, paged)
    with open(bench_dir / "configs" / "mixtral-8x7b-d6.json") as f:
        cfg = json.load(f)
    for k in ("num_local_experts", "num_experts_per_tok"):
        cfg.pop(k)
    cfg.update(architectures=["MistralForCausalLM"], model_type="mistral",
               num_hidden_layers=32, vocab_size=32768, reduced=[])
    cfg["serving"]["kv_pages"] = 513
    (bench_dir / "configs" / "mistral-7b-paged.json").write_text(json.dumps(cfg))
    assert server.serving(cfg, False)["kv_pages"] == 513
    assert server.hf_config(cfg, rehearsal=False)["num_hidden_layers"] == 32
    # a per-layer metric over an existing reader: one file
    (bench_dir / "layer_metrics" / "host_sync_ms.over.json").write_text(json.dumps({
        "name": "host_sync_ms.over", "layer": "Engine scheduler (engine/engine.py)",
        "unit": "ms", "better": "lower", "source": "program_counter",
        "moves": "tokens_per_s", "cells": ["*.chat-over"],
        "reader": "counter-ratio",
        "args": {"num": "host_sync_wait_ms", "den": "decode_dispatches"}}))
    layer = readers.load_layer_metrics(str(bench_dir))
    m = layer["host_sync_ms.over"]
    assert readers.applies(m, "mistral-7b.chat-over")
    assert not readers.applies(m, "mistral-7b.chat")
    ctx = {"counters": {"window": ({"host_sync_wait_ms": 10.0, "decode_dispatches": 4},
                                   {"host_sync_wait_ms": 70.0, "decode_dispatches": 24})}}
    assert readers.read(m, ctx) == pytest.approx(3.0)


def test_readers_return_nothing_when_there_is_nothing_to_read():
    ctx = {"counters": {"window": ({}, {})}, "trace": None,
           "slice": None, "system": {}, "records": [], "window": (0, 1),
           "acct": {"ttft_ms": [], "tpot_ms": []}, "peaks": None}
    for m in readers.load_layer_metrics(BENCH).values():
        assert readers.read(m, ctx) is None, m["name"]
    # a counter sample that could not be had costs the metric, not the run
    ctx["counters"] = {"window": (None, {"tokens_generated": 5})}
    for m in readers.load_layer_metrics(BENCH).values():
        assert readers.read(m, ctx) is None, m["name"]


def test_hist_mean_and_module_time():
    before = {"hist_ttft__loop__sum": 1.0, "hist_ttft__loop__count": 2.0,
              "decode_steps_dispatched": 100, "tokens_generated": 1000}
    after = {"hist_ttft__loop__sum": 4.0, "hist_ttft__loop__count": 8.0,
             "hist_ttft__dense__sum": 1.0, "hist_ttft__dense__count": 2.0,
             "decode_steps_dispatched": 160, "tokens_generated": 2200}
    ctx = {"counters": {"window": (before, after)},
           "trace": {"class_s": {"decode": 2.4}, "idle_share": 0.25,
                     "idle_share_in_flight": 0.2, "window_s": 3.0,
                     "class_steps": {"decode": 60.0}}}
    assert readers.hist_mean(ctx, "ttft") == pytest.approx(4.0 / 8.0 * 1e3)
    assert readers.trace_module_time(ctx, "decode", "trace-steps") \
        == pytest.approx(40.0)
    assert readers.trace_class_share(ctx, "decode") == pytest.approx(80.0)
    # a class that did not run in the slice is a reading of 0, not nothing
    assert readers.trace_class_share(ctx, "prefill") == 0.0
    assert readers.trace_idle(ctx, "slice") == pytest.approx(25.0)
    assert readers.trace_idle(ctx, "in-flight") == pytest.approx(20.0)
    assert readers.counter_ratio(ctx, "tokens_generated",
                                 "decode_steps_dispatched") == pytest.approx(20.0)
