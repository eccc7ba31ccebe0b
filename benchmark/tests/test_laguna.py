"""The `laguna` family under the benchmark's contract: its work module held
to counts written out by hand for the configuration the benchmark runs, the
configuration held to the catalog's published widths, and a tiny `laguna`
configuration and cell driven through run.py on the CPU, sound and then with
the window taken out of the served path."""
import json
import os

import pytest

from benchmark import run as bench_run
from benchmark.harness import manifest
from benchmark.models import laguna_reference, laguna_work

DATA = os.path.join(os.path.dirname(__file__), "data")
TINY = os.path.join(DATA, "tiny-laguna-manifest.json")
CELL = "serve-laguna-s.codegen"


@pytest.fixture(scope="module")
def cfg():
    return manifest.cell(CELL)["config"]


def test_work_counts_are_the_hand_written_ones(cfg):
    sliding = 3072 * (72 + 8 + 8 + 72) * 128 + 3072 * 72
    full = 3072 * (48 + 8 + 8 + 48) * 128 + 3072 * 48
    assert (sliding, full) == (63_135_744, 44_187_648)
    assert laguna_work.attention_params(cfg, 72) == sliding
    assert laguna_work.attention_params(cfg, 48) == full
    router, expert, dense_mlp = 3072 * 256, 3 * 3072 * 1024, 3 * 3072 * 12288
    head = 3072 * 50176
    dense = head + (full + dense_mlp) + 3 * (sliding + router + expert) \
        + (full + router + expert)
    assert dense == 586_063_872 == laguna_work.dense_params(cfg)
    # 10 experts a token, half of them held here in expectation, 4 sparse layers
    assert laguna_work.matmul_params(cfg) == dense + 4 * expert * 10 * 0.5
    assert laguna_work.held_share(cfg) == 0.5
    assert laguna_work.sparse_layers(cfg) == 4
    # the weights the chip holds: the dense ones, the embedding, 128 experts
    # a sparse layer, norms aside (ISSUE 30: 5,572M parameters)
    held = dense + head + 4 * 128 * expert
    assert round(held / 1e6) == 5572
    leaves = laguna_reference.leaf_shapes(cfg)
    total = 0
    for shape, _ in leaves.values():
        n = 1
        for s in shape:
            n *= s
        total += n
    assert total == held + 11 * 3072          # and eleven norms


def test_a_token_reads_the_window_in_window_layers_and_grouped_kv(cfg):
    w = laguna_work.traced_work(cfg, {"decode_contexts": [3000], "ticks": 1,
                                      "prefill_tokens": 0})
    # layers 0 and 4 full at 48 query heads: 3,000 keys; 1-3 at 72: 512
    keys_flops = 4.0 * 128 * (2 * 48 * 3000 + 3 * 72 * 512)
    assert w["paged_attention"] == {
        "flops": keys_flops,
        "bytes": 2.0 * 8 * 128 * 2 * (2 * 3000 + 3 * 512)}
    assert w["decode_step"]["flops"] == \
        2.0 * laguna_work.matmul_params(cfg) + keys_flops
    short = laguna_work.traced_work(cfg, {"decode_contexts": [100], "ticks": 1,
                                          "prefill_tokens": 0})
    assert short["paged_attention"]["bytes"] == 2.0 * 8 * 128 * 2 * 5 * 100
    # one token touches 10 x 1/2 experts in expectation: 128 (1 - (246/256))
    assert laguna_work.experts_touched(cfg, 1) == pytest.approx(5.0)
    assert laguna_work.experts_touched(cfg, 32) == pytest.approx(
        128 * (1 - (246 / 256) ** 32))
    moe = w["moe_experts"]
    assert moe["flops"] == 4 * 2.0 * 9_437_184 * 10 * 0.5
    assert moe["bytes"] == pytest.approx(
        4 * (5.0 * 9_437_184 * 2 + 5 * 2 * 3072 * 2))


def test_served_flops_follow_prefill_then_decode(cfg):
    # a 600-token prompt, nothing cached, 3 tokens out: contexts 1..600
    # prefilled, then 601 and 602 decoded
    got = laguna_work.served_flops(cfg, [(600, 0, 3), (10, 0, 0)])
    full = sum(range(1, 603))
    window = sum(min(c, 512) for c in range(1, 603))
    want = 2.0 * laguna_work.matmul_params(cfg) * 602 \
        + 4.0 * 128 * (2 * 48 * full + 3 * 72 * window)
    assert got == pytest.approx(want, rel=1e-12)
    assert laguna_work._sum_contexts(500, 520, 512) == \
        sum(min(c, 512) for c in range(500, 521))
    assert laguna_work._sum_contexts(600, 700, 512) == 101 * 512
    assert laguna_work._sum_contexts(5, 4, 512) == 0


def test_the_configuration_keeps_every_published_width(cfg):
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    row = next(r for r in map(json.loads, open(catalog))
               if r["name"] == "Laguna-S-2.1")
    bench = manifest.benchmark()
    entry = next(c for c in bench["configs"] if c["name"] == "laguna-s-2.1-ep2")
    assert entry["reduced"] == cfg["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size"]
    for key, value in row["config"].items():
        if key in entry["reduced"]:
            assert cfg["published"][key] == value
        else:
            assert cfg[key] == value, key
    assert (cfg["num_hidden_layers"], cfg["num_experts"], cfg["vocab_size"]) \
        == (5, 128, 50176)
    assert set(cfg["assumed"]) >= {"gate", "router", "shared_expert",
                                   "hidden_act", "qk_norm", "init"}
    assert "2 chips share each layer" in cfg["deployment"]


def drive(capsys, trace=0, seed=2290000077):
    rc = bench_run.main(["--workload", "tiny.codegen", "--seed", str(seed),
                         "--seconds", "3", "--trace", str(trace),
                         "--manifest", TINY, "--rehearse-on-cpu"])
    assert rc == 0
    out = capsys.readouterr()
    line = json.loads(out.out.strip().splitlines()[-1])
    line["logged"] = next(json.loads(ln) for ln in out.err.splitlines()
                          if ln.startswith('{"notes"'))
    return line


def test_a_tiny_laguna_cell_runs_through_run_py(capsys):
    line = drive(capsys, trace=1)
    assert line["correct"] and line["failed"] == 0 and line["attempted"] > 5
    m = line["metrics"]
    assert m["compiles_in_window"]["value"] == 0
    assert m["expert_load_max_over_mean"]["value"] >= 1.0
    # contexts pass the window of 24: less is read than a full layer would
    assert 0 < m["window_keys_share"]["value"] < 100
    assert m["mfu.serve"]["value"] > 0
    assert line["logged"]["counters"]["model_flops"] > 0


def test_a_window_cut_short_in_the_served_path_is_not_correct(capsys, monkeypatch):
    from paddle_tpu.ops.pallas import paged_attention as pa

    real = pa.paged_attention_xla
    # the fault of a decode kernel that masks by the wrong position: a
    # window layer's query sees its own key and no other
    monkeypatch.setattr(
        pa, "paged_attention_xla",
        lambda q, k, v, bt, cl, scale=None, window=None:
        real(q, k, v, bt, cl, scale, None if window is None else 1))
    line = drive(capsys)
    assert not line["correct"]
    assert line["checks"]["logit_gap"][0] > line["checks"]["logit_gap"][1]
