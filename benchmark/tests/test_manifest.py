import json
import os
import re

import pytest

from benchmark.harness import correct, manifest, readers

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
BENCH = manifest.benchmark()


def test_exactly_the_contracts_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["benchmark"] and 1 <= BENCH["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(manifest.ROOT, "BENCHMARK.json")) < 64 * 1024


def test_names_units_and_lines():
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in BENCH[group]]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names), names
    for w in BENCH["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
    for m in BENCH["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert 0.01 <= m["bound"] <= 0.1 and m["source"] in ("host_clock", "device_trace")
    assert "setup_s" in [m["name"] for m in BENCH["end_to_end"]]
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("benchmark/") and 1 <= len(c["source"]) <= 200
        cfg = json.load(open(os.path.join(manifest.ROOT, c["file"])))
        assert cfg["reduced"] == c["reduced"]
        for key in c["reduced"]:
            assert not re.search(r"(_dim|_rank|hidden|intermediate)", key)


@pytest.mark.parametrize("name", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_is_whole(name):
    cell = manifest.cell(name)
    e2e = {m["name"] for m in cell["end_to_end"]}
    assert "setup_s" in e2e and len(e2e) >= 2 and cell["per_layer"]
    for m in cell["per_layer"]:
        # a cell that reports a layer metric reports the metric it moves
        assert m["moves"] in e2e, (name, m["name"])
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        spec, mod = manifest.layer_metric(m["name"])
        assert spec["layer"] == m["layer"] and spec["source"] == m["source"]
        assert mod is not None or spec["reader"] in readers.READERS
    assert cell["traffic"]["kind"] in ("train_steps", "open_loop", "closed_loop")
    limits = correct.limits_for(name)
    assert limits and all(v >= 0 for v in limits.values())
    assert any("mfu" in re.split(r"[._]", m["name"]) for m in cell["per_layer"])


def test_per_layer_workloads_name_cells_and_share_layers():
    cells = {w["name"] for w in BENCH["workloads"]}
    for m in BENCH["per_layer"] + BENCH["end_to_end"]:
        assert set(m.get("workloads", [])) <= cells
    for m in BENCH["per_layer"]:
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"


def test_the_metric_that_went_and_the_one_that_came():
    """PR 29: paged_attn_roofline (silent since PR 26 named the kernel) is
    out; mfu.decode, the whole decode step's share of the peak, is what a
    claim on tpot_p90_ms stands on. Every per-layer entry names its cells."""
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    assert "paged_attn_roofline" not in by_name
    assert not os.path.exists(os.path.join(
        manifest.BENCH_DIR, "layer_metrics", "paged_attn_roofline.json"))
    serve = ["serve-1.3B.chat", "serve-1.3B.docqa"]
    assert by_name["mfu.decode"] == {
        "name": "mfu.decode", "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "whole step",
        "moves": "tpot_p90_ms", "workloads": serve}
    assert by_name["paged_decode_roofline"]["workloads"] == serve
    assert by_name["mfu.serve"]["moves"] == "serve_tokens_per_s"
    assert all(m.get("workloads") for m in BENCH["per_layer"])
    # every roofline that moves an end-to-end metric has a whole-step mfu
    # beside it that moves the same one in the same cells
    for m in BENCH["per_layer"]:
        if m["name"].endswith("_roofline"):
            assert any("mfu" in re.split(r"[._]", w["name"])
                       and w["moves"] == m["moves"]
                       and set(w["workloads"]) >= set(m["workloads"])
                       for w in BENCH["per_layer"]), m["name"]


def test_mfu_decode_reads_the_familys_count_over_the_decode_modules():
    spec, mod = manifest.layer_metric("mfu.decode")
    ctx = {"trace": {"modules": {"jit_step(7)": [0.05, 0.03, 0.02],
                                 "jit_serve_prefill(8)": [0.4]}},
           "work": {"decode_step": {"flops": 2.0e9, "bytes": 1.0}},
           "peaks": {"flops_per_s": 1.0e12, "bytes_per_s": 1.0}, "chips": 1}
    # 2 GFLOP over 0.1 s of decode modules at 1 TFLOP/s: 2%
    assert mod.read(ctx, spec) == pytest.approx(2.0)
    # nothing decoded, no decode module, or a family without that work:
    # silent, never 0
    for quiet in ({"work": {"decode_step": {"flops": 0.0, "bytes": 0.0}}},
                  {"work": {}}, {"trace": {"modules": {"jit_pf(1)": [0.1]}}},
                  {"trace": {}}):
        assert mod.read({**ctx, **quiet}, spec) is None


def test_a_reader_that_finds_nothing_returns_nothing():
    ctx = {"counters": {}, "clocks": {}, "trace": {}, "work": {},
           "peaks": {"flops_per_s": 1.0, "bytes_per_s": 1.0}, "chips": 1}
    got = readers.read_all(BENCH["per_layer"], ctx)
    assert got == {}
    ctx["counters"] = {"model_flops": 50.0, "window_s": 1.0}
    got = readers.read_all([m for m in BENCH["per_layer"] if "mfu" in m["name"]], ctx)
    assert got and all(abs(v["value"] - 5000.0) < 1e-9 for v in got.values())


def test_decide_needs_every_number_under_its_limit():
    ok, checks = correct.decide({"a": 0.1, "b": 0.0}, {"a": 0.2, "b": 0})
    assert ok and checks == {"a": [0.1, 0.2], "b": [0.0, 0]}
    assert not correct.decide({"a": 0.3, "b": 0.0}, {"a": 0.2, "b": 0})[0]
    assert not correct.decide({"a": float("nan")}, {"a": 0.2})[0]
    assert not correct.decide({}, {"a": 0.2})[0]
    assert not correct.decide({"a": 0.1, "c": 0.0}, {"a": 0.2})[0]
