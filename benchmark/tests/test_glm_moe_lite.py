"""The `glm_moe_lite` family under the benchmark's contract: its work module
held to counts written out by hand for the configuration the benchmark runs,
the configuration held to the catalog's published widths, and a tiny
`glm_moe_lite` configuration and cell driven through run.py on the CPU, sound
and then with a decode step that sees its own key alone."""
import json
import os

import pytest

from benchmark import run as bench_run
from benchmark.harness import manifest
from benchmark.models import glm_moe_lite_reference, glm_moe_lite_work

DATA = os.path.join(os.path.dirname(__file__), "data")
TINY = os.path.join(DATA, "tiny-glm-manifest.json")
CELL = "serve-glm-flash.longdoc"


@pytest.fixture(scope="module")
def cfg():
    return manifest.cell(CELL)["config"]


def test_work_counts_are_the_hand_written_ones(cfg):
    w = glm_moe_lite_work
    attn = 2048 * 768 + 768 * 5120 + 2048 * 576 + 512 * 8960 + 5120 * 2048
    assert attn == 21_757_952 == w.attention_params(cfg)
    expert, router = 3 * 2048 * 1536, 2048 * 64
    assert expert == 9_437_184 == w.expert_params(cfg)
    assert w.layer_params_outside_experts(cfg) == attn + router + expert \
        == 31_326_208
    layer0 = attn + 3 * 2048 * 10240
    head = 77440 * 2048
    assert w.dense_params(cfg) == head + layer0 + 7 * (attn + router + expert)
    # ISSUE 34: 84.7M + 7 x 333.3M + 317.2M = 2,735M parameters, 5.47 GB
    held = 2 * head + layer0 + 7 * (attn + router + expert + 32 * expert)
    assert held == w.weight_params(cfg) and round(held / 1e6) == 2735
    assert round(held * 2 / 1e9, 2) == 5.47
    # 4 experts a token, half of them held here in expectation
    assert w.matmul_params(cfg) == w.dense_params(cfg) + 7 * expert * 4 * 0.5
    assert w.cache_bytes_per_token(cfg) == 8 * 576 * 2 == 9216
    total = 0
    for shape, _ in glm_moe_lite_reference.leaf_shapes(cfg).values():
        n = 1
        for s in shape:
            n *= s
        total += n
    # and the norms (two a layer of 2,048, a 768 and a 512; the last) and
    # seven biases of 64
    assert total == held + 8 * (2 * 2048 + 768 + 512) + 2048 + 7 * 64


def test_latent_work_reads_a_row_once_and_counts_the_absorbed_products(cfg):
    w = glm_moe_lite_work.traced_work(
        cfg, {"decode_contexts": [3000, 17000], "ticks": 1,
              "prefill_tokens": 0})
    assert w["latent_decode"] == {
        "flops": 8 * 2.0 * 20 * (576 + 512) * 20000,
        "bytes": 9216 * 20000}
    # the model's own operations count the expanded form: 1,024 a pair a head
    assert w["decode_step"]["flops"] == \
        2 * 2.0 * glm_moe_lite_work.matmul_params(cfg) \
        + 8 * 2.0 * 20 * 512 * 20000
    assert "latent_prefill" not in w
    # a follow-up turn: 48 tokens at 8,192 in a chunk of 1,024; and a first
    # chunk. Only the prompt's own tokens count
    pre = glm_moe_lite_work.traced_work(
        cfg, {"decode_contexts": [], "ticks": 0, "prefill_tokens": 1072,
              "prefill_chunks": [(8192, 48), (0, 1024)]})["latent_prefill"]
    pairs = sum(range(8193, 8241)) + sum(range(1, 1025))
    assert pre["flops"] == 8 * 2.0 * 20 * (576 + 512) * pairs
    assert pre["bytes"] == 9216 * (8240 + 1024)
    assert glm_moe_lite_work.experts_touched(cfg, 24) == pytest.approx(
        32 * (1 - (60 / 64) ** 24))


def test_served_flops_follow_prefill_then_decode(cfg):
    # a 600-token prompt of which 512 were cached, 3 tokens out
    got = glm_moe_lite_work.served_flops(cfg, [(600, 512, 3), (10, 0, 0)])
    pairs = sum(range(513, 603))
    assert got == pytest.approx(
        2.0 * glm_moe_lite_work.matmul_params(cfg) * 90
        + 8 * 2.0 * 20 * 512 * pairs, rel=1e-12)


def test_the_configuration_keeps_every_published_width(cfg):
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    row = next(r for r in map(json.loads, open(catalog))
               if r["name"] == "GLM-4.7-Flash")
    bench = manifest.benchmark()
    entry = next(c for c in bench["configs"]
                 if c["name"] == "glm-4.7-flash-ep2")
    assert row["source_url"] in entry["source"] and len(entry["source"]) <= 200
    assert entry["reduced"] == cfg["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size"]
    for key, value in row["config"].items():
        if key in entry["reduced"]:
            assert cfg["published"][key] == value
        else:
            assert cfg[key] == value, key
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"], cfg["experts_held"]) == (8, 32, 77440, [0, 32])
    assert set(cfg["assumed"]) >= {"rotary", "router", "init", "mtp",
                                   "e_score_correction_bias"}
    assert "2 chips share each layer" in cfg["deployment"]
    sv = cfg["serve"]
    assert (sv["slots"], sv["block_size"], sv["max_model_len"],
            sv["prefix_cache"], sv["spec_k"], sv["fuse_steps"]) \
        == (24, 128, 33280, True, 0, 1)
    pool = (sv["num_blocks"] - 1) * 128 * 9216
    assert 6.5e9 <= pool <= 7.5e9


def test_the_prefill_roofline_reads_the_chunks_of_the_traced_session(
        cfg, monkeypatch):
    from benchmark.harness import span_readers, trace

    spec, mod = manifest.layer_metric("latent_prefill_roofline")
    chunks = [{"name": "serving.prefill_chunk",
               "args": {"start": 8192, "tokens": 48}},
              {"name": "serving.prefill_chunk",
               "args": {"start": 0, "tokens": 1024}},
              {"name": "serving.tick", "args": {}}]
    monkeypatch.setattr(span_readers, "ring", lambda ctx: chunks)
    monkeypatch.setattr(trace, "op_seconds", lambda red, pattern: 0.004)
    ctx = {"config": cfg, "trace": {"window_s": 6.0}, "chips": 1,
           "facts": {"decode_contexts": [], "ticks": 0, "prefill_tokens": 1072},
           "peaks": {"flops_per_s": 197e12, "bytes_per_s": 819e9}}
    pairs = sum(range(8193, 8241)) + sum(range(1, 1025))
    least = 8 * 2.0 * 20 * (576 + 512) * pairs / 197e12
    assert mod.read(ctx, spec) == pytest.approx(100 * least / 0.004)
    # a program whose spans carry no `start` (the parent), or no kernel
    # event in the trace: nothing to read, and no error
    monkeypatch.setattr(span_readers, "ring", lambda ctx: [
        {"name": "serving.prefill_chunk", "args": {"tokens": 48}}])
    assert mod.read(ctx, spec) is None
    monkeypatch.setattr(span_readers, "ring", lambda ctx: chunks)
    monkeypatch.setattr(trace, "op_seconds", lambda red, pattern: 0.0)
    assert mod.read(ctx, spec) is None
    busy_spec, busy = manifest.layer_metric("latent_share_of_busy")
    assert busy.read({"trace": {}}, busy_spec) is None


def drive(capsys, trace=0, seed=2290000077):
    rc = bench_run.main(["--workload", "tiny.longdoc", "--seed", str(seed),
                         "--seconds", "3", "--trace", str(trace),
                         "--manifest", TINY, "--rehearse-on-cpu"])
    assert rc == 0
    out = capsys.readouterr()
    return json.loads(out.out.strip().splitlines()[-1])


def test_a_tiny_longdoc_cell_runs_through_run_py(capsys):
    line = drive(capsys, trace=1)
    assert line["correct"] and line["failed"] == 0 and line["attempted"] > 5
    m = line["metrics"]
    assert m["compiles_in_window"]["value"] == 0
    # follow-up turns find their document's latent pages in the prefix cache
    assert m["prefix_hit_share"]["value"] > 30
    assert m["expert_load_max_over_mean"]["value"] >= 1.0
    assert m["mfu.serve"]["value"] > 0
    # the CPU's trace has no device plane: the kernels' shares stay silent
    assert "latent_decode_roofline" not in m


def test_a_decode_that_sees_its_own_key_alone_is_not_correct(
        capsys, monkeypatch):
    import jax.numpy as jnp

    from paddle_tpu.ops.kernels import nn_ops

    real = nn_ops._latent_scores_xla

    # the planted fault is in the mathematics, not in timing (PERF.md
    # question 4): a decode step's query sees the key just appended and no
    # other, as a kernel that takes the wrong loop bounds would
    def own_key_alone(q, latent, live, scale, v_dim):
        if q.shape[1] == 1:
            last = jnp.sum(live, -1, keepdims=True) - 1
            live = jnp.arange(live.shape[-1])[None, None, :] == last
        return real(q, latent, live, scale, v_dim)

    monkeypatch.setattr(nn_ops, "_latent_scores_xla", own_key_alone)
    line = drive(capsys)
    assert not line["correct"]
    assert line["checks"]["logit_gap"][0] > line["checks"]["logit_gap"][1]
