"""Drive a whole run (the look for a chip skipped: the CPU is allowed and
named in the result) at a small size, sound and then with the timed path
broken underneath, and see `correct` come out false for each fault."""
import json
import os

import pytest

from benchmark import run as bench_run

DATA = os.path.join(os.path.dirname(__file__), "data")


def drive(capsys, workload, seed=21, trace=0, seconds=2):
    rc = bench_run.main(["--workload", workload, "--seed", str(seed),
                         "--seconds", str(seconds), "--trace", str(trace),
                         "--manifest", os.path.join(DATA, "tiny-manifest.json"),
                         "--rehearse-on-cpu"])
    assert rc == 0
    out = capsys.readouterr()
    line = json.loads(out.out.strip().splitlines()[-1])
    assert set(line) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert list(line)[-1] == "checks"
    assert "correct:" in out.err.strip().splitlines()[-1]
    assert line["device"]["platform"] == "cpu"      # never under a chip's name
    return line


def test_a_sound_training_run_is_correct(capsys):
    line = drive(capsys, "tiny.train")
    assert line["correct"] and line["failed"] == 0
    assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert all(v is not None and v <= lim for v, lim in line["checks"].values())


def test_a_step_that_returns_its_state_unchanged_is_not_correct(capsys, monkeypatch):
    from paddle_tpu.jit.trainer import TrainStep

    real = TrainStep.__call__

    def frozen(self, *batch):
        params = [p._value for p in self.params]
        state, n = self.opt_state, self.optimizer._step_count
        # the step computes on copies (its inputs are donated) and the
        # state it leaves is the one it found
        self_params = [p.copy() for p in params]
        for p, v in zip(self.params, self_params):
            p._value = v
        self.opt_state = __import__("jax").tree_util.tree_map(
            lambda x: x.copy() if hasattr(x, "copy") else x, state)
        loss = real(self, *batch)
        for p, v in zip(self.params, params):
            p._value = v
        self.opt_state, self.optimizer._step_count = state, n
        return loss

    monkeypatch.setattr(TrainStep, "__call__", frozen)
    line = drive(capsys, "tiny.train")
    assert not line["correct"]
    assert line["checks"]["change_gap"][0] > line["checks"]["change_gap"][1]


def test_half_of_the_batch_left_out_is_not_correct(capsys, monkeypatch):
    from benchmark.harness import drive_train

    real = drive_train.feed
    monkeypatch.setattr(drive_train, "feed", lambda ids: real(ids[:len(ids) // 2]))
    line = drive(capsys, "tiny.train")
    assert not line["correct"]


@pytest.mark.parametrize("workload", ["tiny.chat", "tiny.docqa"])
def test_a_sound_serving_run_is_correct_and_compiles_nothing(capsys, workload):
    line = drive(capsys, workload, trace=1)
    assert line["correct"] and line["failed"] == 0 and line["attempted"] > 5
    assert line["metrics"]["compiles_in_window"]["value"] == 0
    if workload == "tiny.docqa":
        assert line["metrics"]["prefix_hit_share"]["value"] > 30


def test_a_token_altered_where_it_is_produced_is_not_correct(capsys, monkeypatch):
    from paddle_tpu.serving.engine import ServingEngine

    real = ServingEngine._sample_host
    monkeypatch.setattr(ServingEngine, "_sample_host",
                        lambda self, logits, req: (real(self, logits, req) + 1) % 511)
    line = drive(capsys, "tiny.chat")
    assert not line["correct"]
    assert line["checks"]["logit_gap"][0] > line["checks"]["logit_gap"][1]
