"""The `xing` family under the benchmark's contract: its work module held to
counts written out by hand for the configuration the benchmark runs, the
configuration held to the catalog's published widths, and a tiny `xing`
configuration and cell driven through run.py on the CPU, sound and then with
a plain residual stream in place of the four mixed ones."""
import json
import os

import pytest

from benchmark import run as bench_run
from benchmark.harness import manifest
from benchmark.models import xing_reference, xing_work

DATA = os.path.join(os.path.dirname(__file__), "data")
TINY = os.path.join(DATA, "tiny-xing-manifest.json")
CELL = "serve-xing-a4b.agent"


@pytest.fixture(scope="module")
def cfg():
    return manifest.cell(CELL)["config"]


def test_work_counts_are_the_hand_written_ones(cfg):
    w = xing_work
    attn = 3584 * 768 + 768 * 6144 + 3584 * 576 + 512 * 8192 + 4096 * 3584
    assert attn == 28_409_856 == w.attention_params(cfg)       # 28.41M
    expert, router = 3 * 3584 * 1024, 3584 * 64
    assert expert == 11_010_048 == w.expert_params(cfg)        # 11.01M
    mixes = 2 * (14336 * 24 + 27)
    assert mixes == 688_182 == 2 * w.mhc_params(cfg)           # 0.69M
    outside = attn + router + expert + mixes
    assert outside == w.layer_params_outside_experts(cfg)
    assert round(outside / 1e6, 2) == 40.34
    layer0 = attn + 3 * 3584 * 9216 + mixes
    assert layer0 == w.dense_layer_params(cfg) and round(layer0 / 1e6, 2) == 128.19
    sparse = outside + 64 * expert
    assert round(sparse / 1e6, 2) == 744.98
    head = 131072 * 3584
    held = layer0 + 6 * sparse + 2 * head
    # ISSUE 36: 128.19 + 6 x 744.98 + 939.52 = 5,537.6M = 11.08 GB
    assert held == w.weight_params(cfg) and round(held / 1e6, 1) == 5537.6
    assert round(held * 2 / 1e9, 2) == 11.08
    assert w.weight_bytes(cfg) == held * 2 + 14 * (14336 * 24 + 27) * 2
    assert w.dense_params(cfg) == head + layer0 + 6 * outside
    # every expert is here: 4 a token, none elsewhere
    assert w.held_share(cfg) == 1.0
    assert w.matmul_params(cfg) == w.dense_params(cfg) + 6 * expert * 4
    # a cache row: 576 values counted, 640 lanes stored
    assert w.cache_bytes_per_token(cfg) == 7 * 576 * 2 == 8064
    assert w.stored_cache_bytes_per_token(cfg) == 7 * 640 * 2 == 8960
    total = 0
    for shape, _ in xing_reference.leaf_shapes(cfg).values():
        n = 1
        for s in shape:
            n *= s
        total += n
    # and the norms (two a layer of 3,584, a 768 and a 512; the last) and six
    # biases of 64
    assert total == held + 7 * (2 * 3584 + 768 + 512) + 3584 + 6 * 64


def test_the_mix_is_counted_by_what_an_application_has_to_move(cfg):
    w = xing_work
    assert w.mhc_bytes_per_application(cfg) == (2 * 4 + 2) * 3584 * 2 == 71_680
    assert w.mhc_applications(cfg) == 14
    one = w.mhc(cfg, 1)
    assert one["bytes"] == 14 * 71_680
    assert one["flops"] == 14 * (2 * 14336 * 24 + 2 * 4 * 3584
                                 + 2 * 16 * 3584 + 2 * 4 * 3584)
    work = w.traced_work(cfg, {"decode_contexts": [3000, 9000], "ticks": 1,
                               "prefill_tokens": 512})
    # the kernels' work is the prefilled tokens': a decode step's rows take
    # the XLA form and are counted in decode_step
    assert work["mhc"]["bytes"] == 14 * 71_680 * 512
    assert work["latent_decode"] == {
        "flops": 7 * 2.0 * 32 * (576 + 512) * 12000,
        "bytes": 8064 * 12000}
    # the model's own operations count attention in the expanded form: 32
    # heads x (192 + 128) a pair; the product with phi among the matmuls
    mix_sums = 14 * (2 * 4 * 3584 + 2 * 16 * 3584 + 2 * 4 * 3584)
    assert work["decode_step"]["flops"] == pytest.approx(
        2 * (2.0 * w.matmul_params(cfg) + mix_sums)
        + 7 * 2.0 * 32 * 320 * 12000, rel=1e-12)
    assert "latent_prefill" not in work
    assert w.experts_touched(cfg, 24) == pytest.approx(
        64 * (1 - (60 / 64) ** 24))
    assert w.traced_work(cfg, {"batch": 8, "sequence": 1024}) == {}


def test_served_flops_follow_prefill_then_decode(cfg):
    got = xing_work.served_flops(cfg, [(600, 512, 3), (10, 0, 0)])
    pairs = sum(range(513, 603))
    mix_sums = 14 * (2 * 4 * 3584 + 2 * 16 * 3584 + 2 * 4 * 3584)
    assert got == pytest.approx(
        (2.0 * xing_work.matmul_params(cfg) + mix_sums) * 90
        + 7 * 2.0 * 32 * 320 * pairs, rel=1e-12)
    assert xing_work.train_flops_per_token(cfg, 1024) == pytest.approx(
        3 * xing_work.forward_flops(cfg, [(512, 512)]))


def test_the_configuration_keeps_every_published_width(cfg):
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    row = next(r for r in map(json.loads, open(catalog))
               if r["name"] == "Xing4.0-29B-A4B")
    bench = manifest.benchmark()
    entry = next(c for c in bench["configs"]
                 if c["name"] == "xing4.0-29b-a4b-ep1")
    assert entry["source"] == row["source_url"] == cfg["source"]
    assert entry["reduced"] == cfg["reduced"] == [
        "num_hidden_layers", "first_k_dense_replace"]
    for key, value in row["config"].items():
        if key in entry["reduced"]:
            assert cfg["published"][key] == value
        else:
            assert cfg[key] == value, key
    assert (cfg["num_hidden_layers"], cfg["first_k_dense_replace"],
            cfg["n_routed_experts"], cfg["vocab_size"],
            cfg["experts_held"]) == (7, 1, 64, 131072, [0, 64])
    assert set(cfg["assumed"]) >= {"mhc_equations", "mhc_open_close",
                                   "mhc_seeded_values", "rotary",
                                   "attention_scale", "router", "init", "mtp"}
    assert "ep_size 1 as published" in cfg["deployment"]
    sv = cfg["serve"]
    assert (sv["slots"], sv["block_size"], sv["max_model_len"],
            sv["num_blocks"], sv["prefix_cache"], sv["spec_k"],
            sv["fuse_steps"]) == (24, 128, 17664, 2561, True, 0, 1)
    pool = (sv["num_blocks"] - 1) * 128 * \
        xing_work.stored_cache_bytes_per_token(cfg)
    assert round(pool / 1e9, 2) == 2.94
    # weights and pool: at least 80% of the chip's 16 GB
    assert (xing_work.weight_bytes(cfg) + pool) / 16e9 > 0.8
    # the cell is the issue's traffic, to the number
    mix = manifest.cell(CELL)["traffic"]
    assert (mix["kind"], mix["clients"], mix["turns"]) == ("closed_loop", 32, 8)
    assert mix["shared_tokens"] == {"values": [4096, 8192, 16384],
                                    "weights": [0.3, 0.5, 0.2]}
    assert mix["fresh_tokens"] == {"values": [128, 256, 512, 1024],
                                   "weights": [0.3, 0.3, 0.2, 0.2]}
    assert mix["output_tokens"] == {"dist": "uniform", "min": 32, "max": 256}
    assert (mix["check_requests"], mix["trace_seconds"],
            mix["order_seed"]) == (4, 6, 1)
    # a session's longest turn fits the engine
    assert 16384 + 1024 + 256 <= sv["max_model_len"]


def test_the_new_readers_find_nothing_where_there_is_nothing(monkeypatch):
    from benchmark.harness import trace

    spec, mod = manifest.layer_metric("mhc_share_of_busy")
    assert mod.read({"trace": {}}, spec) is None
    monkeypatch.setattr(trace, "op_seconds", lambda red, pattern: 0.3)
    assert mod.read({"trace": {"busy_s": 6.0}}, spec) == pytest.approx(5.0)
    spec, mod = manifest.layer_metric("mhc_unbalanced_share")
    # a program without the counters (the parent): nothing, and no error
    assert mod.read({}, dict(spec, unbalanced="serving_no_such_total")) is None
    spec, _ = manifest.layer_metric("mhc_roofline")
    import re
    assert re.search(spec["pattern"], "%mhc_pre.3 = ")
    assert re.search(spec["pattern"], "%mhc_post.12 = ")
    assert not re.search(spec["pattern"], "%mhc_prefix.1 = ")


def drive(capsys, trace=0, seed=2290000077):
    rc = bench_run.main(["--workload", "tiny.agent", "--seed", str(seed),
                         "--seconds", "3", "--trace", str(trace),
                         "--manifest", TINY, "--rehearse-on-cpu"])
    assert rc == 0
    out = capsys.readouterr()
    return json.loads(out.out.strip().splitlines()[-1])


def test_a_tiny_agent_cell_runs_through_run_py(capsys):
    line = drive(capsys, trace=1)
    assert line["correct"] and line["failed"] == 0 and line["attempted"] > 5
    m = line["metrics"]
    assert m["compiles_in_window"]["value"] == 0
    # follow-up turns find their session's latent pages in the prefix cache
    assert m["prefix_hit_share"]["value"] > 30
    assert m["expert_load_max_over_mean"]["value"] >= 1.0
    assert m["mfu.serve"]["value"] > 0
    # twenty rounds balance every map of every decode step
    assert m["mhc_unbalanced_share"]["value"] == 0.0
    # the CPU's trace has no device plane: the kernels' shares stay silent
    assert "mhc_roofline" not in m and "mhc_share_of_busy" not in m


def test_a_plain_residual_stream_is_not_correct(capsys, monkeypatch):
    import jax.numpy as jnp

    from paddle_tpu.ops.kernels import nn_ops

    # the planted fault is in the mathematics: H_res = I, H_pre = 1/n,
    # H_post = 2/n, the n streams copies of one plain residual stream
    def plain(x, phi, a, b, n, eps, clamp, iters):
        t = x.shape[0]
        return (jnp.full((t, n), 1.0 / n), jnp.full((t, n), 2.0 / n),
                jnp.broadcast_to(jnp.eye(n), (t, n, n)), jnp.zeros((t, 1)))

    monkeypatch.setattr(nn_ops, "_mhc_maps_xla", plain)
    line = drive(capsys)
    assert not line["correct"]
    assert line["checks"]["logit_gap"][0] > line["checks"]["logit_gap"][1]
