"""Drive a whole run (the look for a chip skipped: the CPU is allowed and
named in the result) at a small size, sound and then with the timed path
broken underneath, and see `correct` come out false for each fault; then
with a second model family's work module, and see the counts come from it."""
import importlib.util
import json
import os
import sys

import pytest

from benchmark import run as bench_run
from benchmark.models import gpt_program, gpt_reference, gpt_work

DATA = os.path.join(os.path.dirname(__file__), "data")
TINY = os.path.join(DATA, "tiny-manifest.json")


def drive(capsys, workload, seed=21, trace=0, seconds=2, manifest=TINY):
    rc = bench_run.main(["--workload", workload, "--seed", str(seed),
                         "--seconds", str(seconds), "--trace", str(trace),
                         "--manifest", manifest, "--rehearse-on-cpu"])
    assert rc == 0
    out = capsys.readouterr()
    line = json.loads(out.out.strip().splitlines()[-1])
    assert set(line) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert list(line)[-1] == "checks"
    assert "correct:" in out.err.strip().splitlines()[-1]
    assert line["device"]["platform"] == "cpu"      # never under a chip's name
    # what the harness counted, as it logs it beside the notes
    line["logged"] = next(json.loads(ln) for ln in out.err.splitlines()
                          if ln.startswith('{"notes"'))
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


# ---- a second model family: its work module is a fixture, the program
# underneath is still the tiny GPT. What is counted has to be the family's.
@pytest.fixture
def tinygqa(monkeypatch, tmp_path):
    """The family "tinygqa" (grouped KV heads, a window layer) and a manifest
    whose one configuration names it; yields (manifest path, configuration,
    work module, what the work module was called with)."""
    spec = importlib.util.spec_from_file_location(
        "benchmark.models.tinygqa_work", os.path.join(DATA, "tinygqa_work.py"))
    work = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(work)
    for part, mod in (("reference", gpt_reference), ("program", gpt_program),
                      ("work", work)):
        monkeypatch.setitem(sys.modules, f"benchmark.models.tinygqa_{part}", mod)
    seen = {}
    for fn in ("served_flops", "traced_work", "train_flops_per_token"):
        def spy(cfg, arg, fn=fn, real=getattr(work, fn)):
            seen[fn] = list(arg) if fn == "served_flops" else arg
            return real(cfg, arg)
        monkeypatch.setattr(work, fn, spy)
    cfg = json.load(open(os.path.join(DATA, "tiny-gpt.json")))
    cfg.update({"models": "tinygqa", "num_kv_heads": 1, "sliding_window": 32,
                "layer_types": ["sliding_attention", "full_attention"]})
    bench = json.load(open(TINY))
    bench["configs"][0]["file"] = str(tmp_path / "tiny-gqa.json")
    json.dump(cfg, open(bench["configs"][0]["file"], "w"))
    json.dump(bench, open(tmp_path / "manifest.json", "w"))
    return str(tmp_path / "manifest.json"), cfg, work, seen


def test_the_fixture_family_counts_grouped_heads_and_a_window(tinygqa):
    _, cfg, work, _ = tinygqa
    # q 64*64, k and v 2*64*16, out 64*64 = 10,240; mlp 2*64*256 = 32,768
    assert work.matmul_params(cfg) == 512 * 64 + 2 * (10240 + 32768)
    # a token at context 100: the window layer reads 32 keys, the full one 100
    assert work.keys_read(cfg, 100) == 132 and work.keys_read(cfg, 20) == 40
    w = work.traced_work(cfg, {"decode_contexts": [100], "ticks": 1,
                               "prefill_tokens": 0})
    assert w["paged_attention"] == {"flops": 4.0 * 64 * 132,       # 4 query heads
                                    "bytes": 2.0 * 16 * 2 * 132}   # 1 KV head
    # GPT's count of the same token: every layer the whole context, KV as
    # wide as the model
    g = gpt_work.traced_work(cfg, {"decode_contexts": [100], "ticks": 1,
                                   "prefill_tokens": 0})
    assert g["paged_attention"] == {"flops": 4.0 * 64 * 200, "bytes": 2.0 * 64 * 2 * 200}


def test_a_serving_cells_work_comes_from_its_family(capsys, tinygqa):
    manifest_path, cfg, work, seen = tinygqa
    line = drive(capsys, "tiny.chat", trace=1, manifest=manifest_path)
    assert line["correct"] and line["failed"] == 0
    requests, facts = seen["served_flops"], seen["traced_work"]
    assert len(requests) == line["attempted"]
    assert all(n >= 1 and m == 0 for _, m, n in requests)
    assert set(facts) == {"decode_contexts", "ticks", "prefill_tokens"}
    assert facts["decode_contexts"] and facts["ticks"] > 0
    counted = line["logged"]["counters"]["model_flops"]
    assert counted == work.served_flops(cfg, requests)
    assert counted != gpt_work.served_flops(cfg, requests)
    assert line["logged"]["work"] == work.traced_work(cfg, facts)
    # mfu.serve is that count and no other, over the window and the
    # rehearsal's stand-in peak (1e12 a second)
    assert line["metrics"]["mfu.serve"]["value"] == pytest.approx(
        100.0 * counted / (line["logged"]["counters"]["window_s"] * 1e12))


def test_a_training_cells_work_comes_from_its_family(capsys, tinygqa):
    manifest_path, cfg, work, seen = tinygqa
    line = drive(capsys, "tiny.train", trace=1, manifest=manifest_path)
    assert line["correct"]
    assert seen["train_flops_per_token"] == 128
    assert seen["traced_work"]["batch"] == 4 and seen["traced_work"]["steps"] > 0
    c = line["logged"]["counters"]
    assert c["model_flops"] == c["tokens"] * work.train_flops_per_token(cfg, 128)
    assert line["logged"]["work"] == {}     # the family names no flash work


def test_a_traffic_kind_brings_its_driver_as_a_file(capsys, tmp_path, monkeypatch):
    """A kind that run.DRIVERS does not list is driven by
    harness/drive_<kind>.py; one with no such file fails by name."""
    bench = json.load(open(TINY))
    mix = json.load(open(os.path.join(DATA, "tiny-train.json")))
    for kind in ("replayed_log", "../harness/drive_train"):
        mix["kind"] = kind
        json.dump(mix, open(tmp_path / "mix.json", "w"))
        bench["workloads"][0]["traffic"] = str(tmp_path / "mix")
        json.dump(bench, open(tmp_path / "manifest.json", "w"))
        with pytest.raises(SystemExit) as e:
            bench_run.main(["--workload", "tiny.train", "--seed", "1", "--seconds", "1",
                            "--manifest", str(tmp_path / "manifest.json"),
                            "--rehearse-on-cpu"])
        assert "unknown traffic kind" in str(e.value) and "open_loop" in str(e.value)
    from benchmark.harness import drive_train
    assert bench_run.driver_for("train_steps") is drive_train
    # a later PR's file: found by the kind's name alone
    monkeypatch.setitem(sys.modules, "benchmark.harness.drive_replayed_log", drive_train)
    assert bench_run.driver_for("replayed_log") is drive_train
