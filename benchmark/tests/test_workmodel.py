"""The GPT family's work counts (benchmark/models/gpt_work.py) by hand at a
small size, and number for number against the formulas that stood in
harness/workmodel.py until PR 29 (a copy of them is kept here, and only
here); what is left in harness/workmodel.py is the chip's."""
import json
import os

import pytest

from benchmark.harness import device, manifest, workmodel
from benchmark.models import gpt_work

SMALL = {"hidden_size": 8, "num_layers": 2, "num_heads": 2, "intermediate_size": 32,
         "vocab_size": 16, "max_position_embeddings": 4,
         "serve": {"weight_dtype": "bfloat16"}}


def real(name):
    return json.load(open(os.path.join(manifest.BENCH_DIR, "configs", name + ".json")))


def test_parameter_counts_by_hand():
    # per layer: ln 2x(8+8)=32... written out: ln1 16, qkv 8*24+24=216,
    # proj 8*8+8=72, ln2 16, fc 8*32+32=288, out 32*8+8=264 -> 872
    assert gpt_work.n_params(SMALL) == 16 * 8 + 4 * 8 + 2 * 872 + 16
    # matmul weights only: 192 + 64 + 256 + 256 = 768 a layer, + head 128
    assert gpt_work.matmul_params(SMALL) == 2 * 768 + 128


def test_the_real_configurations_have_their_published_sizes():
    med, xl = real("gpt3-medium-355M"), real("gpt3-xl-1.3B")
    assert 350e6 < gpt_work.n_params(med) < 360e6
    assert 1.30e9 < gpt_work.n_params(xl) < 1.33e9
    assert med["hidden_size"] == med["num_heads"] * med["head_dim"]
    assert xl["hidden_size"] == xl["num_heads"] * xl["head_dim"]
    # bench.py / MFU_PROBE.jsonl: 6N + 12 L h s
    assert gpt_work.train_flops_per_token(med, 1024) == \
        6.0 * gpt_work.n_params(med) + 12.0 * 24 * 1024 * 1024


def test_forward_flops_by_hand():
    # 3 tokens, 6 attended pairs: 2*1664*3 + 4*2*8*6
    assert gpt_work.forward_flops(SMALL, 3, 6) == 2 * 1664 * 3 + 4 * 2 * 8 * 6


def test_served_flops_by_hand():
    # a prompt of 5 with 2 cached, 3 tokens out by the close: the prefill
    # passes 3 tokens over (5*6 - 2*3)/2 = 12 pairs; the two later tokens
    # attend to 2*5 + 3 = 13 keys; a request with no token yet counts nothing
    got = gpt_work.served_flops(SMALL, [(5, 2, 3), (7, 0, 0)])
    assert got == (2 * 1664 * 3 + 4 * 2 * 8 * 12) + (2 * 1664 * 2 + 4 * 2 * 8 * 13)


def test_flash_attention_by_hand():
    # b=1, s=4: 10 causal pairs; one matmul over them = 2*10*8 = 160 flops;
    # 6 matmuls (2 forward, 4 backward) x 2 layers; 3 steps traced
    w = gpt_work.traced_work(SMALL, {"batch": 1, "sequence": 4, "steps": 3})
    assert set(w) == {"flash_attention"}
    assert w["flash_attention"]["flops"] == 3 * 2 * 6 * 160
    # one [1, 4, 8] bf16 array = 64 B; 4 moved forward, 8 backward, 2 layers
    assert w["flash_attention"]["bytes"] == 3 * 2 * 12 * 64


def test_paged_attention_and_decode_step_by_hand():
    # two tokens decoded at contexts 5 and 7, in 2 ticks: 12 keys; per key
    # and layer 4*h flops and K+V = 2*h*2 bytes
    w = gpt_work.traced_work(SMALL, {"decode_contexts": [5, 7], "ticks": 2,
                                     "prefill_tokens": 0})
    assert set(w) == {"paged_attention", "decode_step"}
    assert w["paged_attention"]["flops"] == 2 * 4 * 8 * 12
    assert w["paged_attention"]["bytes"] == 2 * 2 * 8 * 2 * 12
    # the whole step: each token through the 1,664 matmul parameters, and
    # the attention above; the bf16 weights read once a tick, and the K, V
    assert w["decode_step"]["flops"] == 2 * 1664 * 2 + 2 * 4 * 8 * 12
    assert w["decode_step"]["bytes"] == 2 * 1664 * 2 + 2 * 2 * 8 * 2 * 12
    # nothing decoded in the period: no operations, and the reader is silent
    idle = gpt_work.traced_work(SMALL, {"decode_contexts": [], "ticks": 0,
                                        "prefill_tokens": 0})
    assert idle["decode_step"]["flops"] == 0


# ---- harness/workmodel.py as it stood at PR 28, kept to hold gpt_work to it
def _old_matmul_params(cfg):
    h, nl = int(cfg["hidden_size"]), int(cfg["num_layers"])
    f = int(cfg.get("intermediate_size") or 4 * h)
    v = int(cfg["vocab_size"])
    return v * h + nl * (h * 3 * h + h * h + h * f + f * h)


def _old_n_params(cfg):
    h, nl = int(cfg["hidden_size"]), int(cfg["num_layers"])
    f = int(cfg.get("intermediate_size") or 4 * h)
    v, p = int(cfg["vocab_size"]), int(cfg["max_position_embeddings"])
    per_layer = 4 * h + (h * 3 * h + 3 * h) + (h * h + h) + (h * f + f) + (f * h + h)
    return v * h + p * h + nl * per_layer + 2 * h


def _old_forward_flops(cfg, n_tokens, attended):
    return 2.0 * _old_matmul_params(cfg) * n_tokens \
        + 4.0 * int(cfg["num_layers"]) * int(cfg["hidden_size"]) * attended


def _old_served_work(cfg, requests):
    """drive_serve._served_work, over the triples it read off the requests."""
    flops = 0.0
    for plen, m, n in requests:
        if n < 1:
            continue
        pairs = (plen * (plen + 1) - m * (m + 1)) / 2.0
        flops += _old_forward_flops(cfg, plen - m, pairs)
        d = n - 1
        flops += _old_forward_flops(cfg, d, d * plen + d * (d + 1) / 2.0)
    return flops


def _old_flash_attention_train(cfg, batch, seq):
    h, nl = int(cfg["hidden_size"]), int(cfg["num_layers"])
    one = 2.0 * (batch * seq * (seq + 1) / 2.0) * h
    act = batch * seq * h * 2.0
    return {"flops": nl * 6.0 * one, "bytes": nl * 12.0 * act}


def _old_paged_attention_decode(cfg, contexts, kv_bytes=2):
    h, nl = int(cfg["hidden_size"]), int(cfg["num_layers"])
    ctx = float(sum(contexts))
    return {"flops": nl * 4.0 * h * ctx, "bytes": nl * 2.0 * h * kv_bytes * ctx}


# a window's worth of requests as docqa and chat make them: long prompts with
# and without a cached prefix, one still prefilling at the close
REQUESTS = [(800, 768, 33), (1296, 0, 64), (1312, 1280, 17), (32, 0, 8),
            (1024, 0, 256), (784, 768, 0), (206, 0, 1)]
CONTEXTS = [801 + j for j in range(32)] + [1297 + j for j in range(63)] + [33, 1279]


@pytest.mark.parametrize("name", ["gpt3-medium-355M", "gpt3-xl-1.3B"])
def test_gpt_work_is_the_old_workmodel_number_for_number(name):
    cfg = real(name)
    assert gpt_work.served_flops(cfg, REQUESTS) == _old_served_work(cfg, REQUESTS)
    assert gpt_work.train_flops_per_token(cfg, 1024) == \
        6.0 * _old_n_params(cfg) + 12.0 * cfg["num_layers"] * cfg["hidden_size"] * 1024
    steps = 13
    per_step = _old_flash_attention_train(cfg, 8, 1024)
    assert gpt_work.traced_work(cfg, {"batch": 8, "sequence": 1024, "steps": steps}) \
        == {"flash_attention": {k: v * steps for k, v in per_step.items()}}
    cfg.setdefault("serve", {"weight_dtype": "bfloat16"})
    w = gpt_work.traced_work(cfg, {"decode_contexts": CONTEXTS, "ticks": 70,
                                   "prefill_tokens": 512})
    assert w["paged_attention"] == _old_paged_attention_decode(cfg, CONTEXTS)
    # the new count stands on the old one: a forward pass a token
    assert w["decode_step"]["flops"] == \
        sum(_old_forward_flops(cfg, 1, c) for c in CONTEXTS)


def test_roofline_names_its_bound_and_peaks_have_no_default():
    peaks = device.peaks_for("TPU v5 lite")
    assert peaks["flops_per_s"] == 1.97e14 and peaks["bytes_per_s"] == 8.19e11
    t, bound = workmodel.roofline_seconds({"flops": 1.97e14, "bytes": 1.0}, peaks)
    assert bound == "flops" and abs(t - 1.0) < 1e-12
    t, bound = workmodel.roofline_seconds({"flops": 1.0, "bytes": 8.19e11}, peaks)
    assert bound == "bytes" and abs(t - 1.0) < 1e-12
    with pytest.raises(SystemExit):
        device.peaks_for("an unknown chip")


def test_the_harness_knows_no_models_sizes():
    """No file under harness/ reads a width or a depth: those are the
    family's, in benchmark/models/<family>_work.py."""
    hdir = os.path.join(manifest.BENCH_DIR, "harness")
    for f in sorted(os.listdir(hdir)):
        if f.endswith(".py"):
            src = open(os.path.join(hdir, f)).read()
            for word in ("hidden_size", "intermediate_size", "num_layers", "num_heads"):
                assert word not in src, (f, word)
