"""The plain reference against the program at a small size, and the control:
the reference one precision down must read well above what the stated
precision reads, or no limit could hold it off."""
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness import correct, traffic
from benchmark.models import gpt_program, gpt_reference as ref

CFG = json.load(open(os.path.join(os.path.dirname(__file__), "data", "tiny-gpt.json")))


def test_program_and_reference_agree_on_logits_in_float32():
    import paddle_tpu as paddle

    model, names = gpt_program.build_model(CFG, 11, "float32")
    model.eval()
    assert sorted(names) == sorted(ref.leaf_names(CFG))
    ids = traffic.rng_for(11, 0).integers(0, CFG["vocab_size"], (2, 48), dtype=np.int32)
    got = np.asarray(model(paddle.to_tensor(ids))._value)
    p = ref.init_weights(CFG, 11, "float32")
    hs = ref.hidden_states(p, jnp.asarray(ids), CFG["num_heads"])
    want = np.asarray(ref.highest_matmul(hs, p["wte"].T))
    assert np.abs(got - want).max() < 2e-4 * np.abs(want).max()


def test_stacked_and_per_layer_weights_are_the_same_numbers():
    a = ref.init_weights(CFG, 2**31 + 5, "bfloat16")
    b = ref.init_weights(CFG, 2**31 + 5, "bfloat16", per_layer=True)
    assert (np.asarray(a["fc_w"][1], np.float32)
            == np.asarray(b["blocks.1.fc_w"], np.float32)).all()
    c = ref.init_weights(CFG, 5, "bfloat16")
    assert (np.asarray(a["wte"], np.float32) != np.asarray(c["wte"], np.float32)).any()


@pytest.fixture(scope="module")
def train_readings():
    tr = CFG["train"]
    batches = traffic.train_batches(3, CFG["vocab_size"], tr["batch"], tr["sequence"])
    first = [next(batches) for _ in range(3)]
    run = lambda **kw: ref.train_reference(CFG, 3, first, tr["optimizer"], **kw)  # noqa: E731
    return {k: run(**kw) for k, kw in {
        "reference": {}, "stated": {"precision": "bf16"},
        "control": {"precision": "fp8"}, "half": {"half_batch": True}}.items()}


def test_train_control_reads_well_above_the_stated_precision(train_readings):
    r = train_readings
    stated = correct.train_numbers(r["stated"], r["reference"])["numbers"]
    control = correct.train_numbers(r["control"], r["reference"])["numbers"]
    limits = correct.limits_for("tiny.train")
    assert correct.decide(stated, limits)[0], stated
    assert not correct.decide(control, limits)[0], control
    assert any(control[k] >= 3 * stated[k] for k in stated), (stated, control)


def test_half_the_batch_left_out_is_caught(train_readings):
    r = train_readings
    half = correct.train_numbers(r["half"], r["reference"])["numbers"]
    assert not correct.decide(half, correct.limits_for("tiny.train"))[0], half


def test_a_state_left_unchanged_reads_one():
    ref_norms = {"a": 2.0, "b": 0.5, "c": 1.0}
    gap, leaf = correct.norm_gaps({k: 0.0 for k in ref_norms}, ref_norms)
    assert gap == 1.0
    gap, _ = correct.norm_gaps({k: 2 * v for k, v in ref_norms.items()}, ref_norms)
    assert gap == 1.0      # moved double reads 1 too


def test_key_bias_is_left_out_of_the_change_by_the_rule_on_its_gradient(train_readings):
    cmp_ = correct.train_numbers(train_readings["stated"], train_readings["reference"])
    out = cmp_["where"]["leaves_left_out"]
    assert out and all(k.endswith("qkv_b.k") for k in out)


def test_served_control_reads_above_the_served_tokens():
    p = ref.init_weights(CFG, 9, "bfloat16")
    rng = traffic.rng_for(9, 0)
    rows = []
    for plen in (40, 90):
        prompt = [int(t) for t in rng.integers(0, CFG["vocab_size"] - 1, plen)]
        toks = []
        for _ in range(12):     # greedy tokens of the stated precision
            ids = jnp.asarray(prompt + toks, jnp.int32)
            lg = ref.logits_at(p, ids, jnp.asarray([len(ids) - 1]),
                               CFG["num_heads"], ref.bf16_matmul)
            toks.append(int(jnp.argmax(lg[0])))
        rows.append((prompt, toks))
    served = ref.served_gaps(CFG, 9, rows, width=128, n_pos=16, pad_to=16)
    # the control need not decode: at every position of long rows it reads
    # the gap of the token that the lower precision puts first
    long_rows = [([int(t) for t in rng.integers(0, 511, 16)],
                  [int(t) for t in rng.integers(0, 511, 224)]) for _ in range(3)]
    control = ref.served_gaps(CFG, 9, long_rows, width=240, n_pos=224, pad_to=16,
                              control="fp8")
    altered = [(p_, [(t + 1) % 511 for t in toks]) for p_, toks in rows]
    wrong = ref.served_gaps(CFG, 9, altered, width=128, n_pos=16, pad_to=16)
    assert max(served) < 0.05
    assert max(control) > max(served)
    assert max(wrong) > correct.limits_for("tiny.chat")["logit_gap"]
