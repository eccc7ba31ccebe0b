import json
import os

from benchmark.harness import device, manifest, workmodel

SMALL = {"hidden_size": 8, "num_layers": 2, "num_heads": 2, "intermediate_size": 32,
         "vocab_size": 16, "max_position_embeddings": 4}


def test_parameter_counts_by_hand():
    # per layer: ln 2x(8+8)=32... written out: ln1 16, qkv 8*24+24=216,
    # proj 8*8+8=72, ln2 16, fc 8*32+32=288, out 32*8+8=264 -> 872
    assert workmodel.n_params(SMALL) == 16 * 8 + 4 * 8 + 2 * 872 + 16
    # matmul weights only: 192 + 64 + 256 + 256 = 768 a layer, + head 128
    assert workmodel.matmul_params(SMALL) == 2 * 768 + 128


def test_the_real_configurations_have_their_published_sizes():
    med = json.load(open(os.path.join(manifest.BENCH_DIR, "configs", "gpt3-medium-355M.json")))
    xl = json.load(open(os.path.join(manifest.BENCH_DIR, "configs", "gpt3-xl-1.3B.json")))
    assert 350e6 < workmodel.n_params(med) < 360e6
    assert 1.30e9 < workmodel.n_params(xl) < 1.33e9
    assert med["hidden_size"] == med["num_heads"] * med["head_dim"]
    assert xl["hidden_size"] == xl["num_heads"] * xl["head_dim"]
    # bench.py / MFU_PROBE.jsonl: 6N + 12 L h s
    assert workmodel.train_flops_per_token(med, 1024) == \
        6.0 * workmodel.n_params(med) + 12.0 * 24 * 1024 * 1024


def test_forward_flops_by_hand():
    # 3 tokens, 6 attended pairs: 2*1664*3 + 4*2*8*6
    assert workmodel.forward_flops(SMALL, 3, 6) == 2 * 1664 * 3 + 4 * 2 * 8 * 6


def test_flash_attention_by_hand():
    # b=1, s=4: 10 causal pairs; one matmul over them = 2*10*8 = 160 flops;
    # 6 matmuls (2 forward, 4 backward) x 2 layers
    w = workmodel.flash_attention_train(SMALL, 1, 4)
    assert w["flops"] == 2 * 6 * 160
    # one [1, 4, 8] bf16 array = 64 B; 4 moved forward, 8 backward, 2 layers
    assert w["bytes"] == 2 * 12 * 64


def test_paged_attention_by_hand():
    # two tokens decoded at contexts 5 and 7: 12 keys; per key and layer
    # 4*h flops and K+V = 2*h*2 bytes
    w = workmodel.paged_attention_decode(SMALL, [5, 7])
    assert w["flops"] == 2 * 4 * 8 * 12
    assert w["bytes"] == 2 * 2 * 8 * 2 * 12


def test_roofline_names_its_bound_and_peaks_have_no_default():
    peaks = device.peaks_for("TPU v5 lite")
    assert peaks["flops_per_s"] == 1.97e14 and peaks["bytes_per_s"] == 8.19e11
    t, bound = workmodel.roofline_seconds({"flops": 1.97e14, "bytes": 1.0}, peaks)
    assert bound == "flops" and abs(t - 1.0) < 1e-12
    t, bound = workmodel.roofline_seconds({"flops": 1.0, "bytes": 8.19e11}, peaks)
    assert bound == "bytes" and abs(t - 1.0) < 1e-12
    try:
        device.peaks_for("an unknown chip")
    except SystemExit:
        pass
    else:
        raise AssertionError("an unknown device got peaks")
