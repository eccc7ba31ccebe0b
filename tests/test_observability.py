"""Unified observability layer (paddle_tpu/observability/).

Covers the r9 ISSUE surface: metrics-registry semantics (labels, off-mode
no-op, thread safety), the per-step telemetry schema produced by a REAL
TrainStep run, flight-recorder dumps on a chaos NaN and on SIGTERM
preemption, the Prometheus textfile round-trip, and the chrome-trace merge
of pure-Python fallback spans recorded without the native tracer.
"""
import glob
import json
import os
import signal
import threading
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn, optimizer
from paddle_tpu.core import flags
from paddle_tpu.observability import (
    flight_recorder, registry, reset_all, sinks, spans, telemetry,
)
from paddle_tpu.resilience import CheckpointManager, chaos
from paddle_tpu.resilience.trainer import ResilientTrainer


@pytest.fixture(autouse=True)
def _clean():
    """Every test starts and ends with metrics off and fresh state."""
    reset_all()
    chaos.clear()
    yield
    flags.set_flags({"metrics": "off", "metrics_dir": ""})
    reset_all()
    chaos.clear()


@pytest.fixture
def metrics_dir(tmp_path):
    d = str(tmp_path / "metrics")
    flags.set_flags({"metrics": "on", "metrics_dir": d})
    return d


def _build():
    paddle.seed(7)
    return nn.Sequential(nn.Linear(4, 8), nn.Tanh(), nn.Linear(8, 1))


def _batches(n=8, seed=0):
    rng = np.random.RandomState(seed)
    return [(paddle.to_tensor(rng.randn(8, 4).astype(np.float32)),
             paddle.to_tensor(rng.randn(8, 1).astype(np.float32)))
            for _ in range(n)]


def _trainer(root, **kw):
    m = _build()
    opt = optimizer.SGD(0.1, parameters=m.parameters())
    loss_fn = nn.MSELoss()
    kw.setdefault("save_every", 2)
    kw.setdefault("nan_guard", True)
    return ResilientTrainer(m, lambda a, b: loss_fn(m(a), b), opt,
                            CheckpointManager(root), **kw)


# ------------------------------------------------------------ registry
class TestRegistry:
    def test_counter_labels_and_total(self, metrics_dir):
        c = registry.counter("t_req_total", "requests", labelnames=("code",))
        c.inc(code="200")
        c.inc(2, code="500")
        assert c.value(code="200") == 1
        assert c.value(code="500") == 2
        assert c.total() == 3
        with pytest.raises(ValueError):
            c.inc(-1, code="200")

    def test_label_names_enforced(self, metrics_dir):
        c = registry.counter("t_lbl_total", "x", labelnames=("a",))
        with pytest.raises(ValueError):
            c.inc(b="nope")

    def test_kind_mismatch_rejected(self, metrics_dir):
        registry.counter("t_kind", "x")
        with pytest.raises(ValueError):
            registry.gauge("t_kind", "x")

    def test_idempotent_registration(self, metrics_dir):
        a = registry.counter("t_same_total", "x")
        b = registry.counter("t_same_total", "x")
        assert a is b

    def test_off_mode_is_noop(self):
        assert not registry.metrics_enabled()
        c = registry.counter("t_off_total", "x")
        g = registry.gauge("t_off_gauge", "x")
        h = registry.histogram("t_off_hist", "x")
        c.inc()
        g.set(5.0)
        h.observe(0.1)
        assert c.total() == 0
        assert g.value() == 0.0
        assert h.stats()["count"] == 0

    def test_always_metrics_record_while_off(self):
        assert not registry.metrics_enabled()
        c = registry.counter("t_always_total", "x", always=True)
        c.inc(3)
        assert c.total() == 3

    def test_gauge_set_inc_dec(self, metrics_dir):
        g = registry.gauge("t_g", "x")
        g.set(10)
        g.inc(5)
        g.dec(2)
        assert g.value() == 13

    def test_histogram_buckets(self, metrics_dir):
        h = registry.histogram("t_h_seconds", "x", buckets=(0.1, 1.0))
        for v in (0.05, 0.5, 5.0):
            h.observe(v)
        st = h.stats()
        assert st["count"] == 3
        assert st["sum"] == pytest.approx(5.55)

    def test_thread_safety(self, metrics_dir):
        c = registry.counter("t_mt_total", "x", labelnames=("w",))

        def work(i):
            for _ in range(500):
                c.inc(w=str(i % 2))

        ts = [threading.Thread(target=work, args=(i,)) for i in range(8)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert c.total() == 8 * 500

    def test_snapshot_shape(self, metrics_dir):
        c = registry.counter("t_snap_total", "x", labelnames=("k",))
        c.inc(k="a")
        snap = registry.default_registry().snapshot()
        assert any("t_snap_total" in name for name in snap)


# ------------------------------------------------------------ prometheus
class TestPrometheus:
    def test_text_round_trip(self, metrics_dir):
        c = registry.counter("t_rt_total", "reqs", labelnames=("code",))
        c.inc(4, code="200")
        g = registry.gauge("t_rt_gauge", "temp")
        g.set(2.5)
        h = registry.histogram("t_rt_seconds", "lat", buckets=(0.1, 1.0))
        h.observe(0.05)
        h.observe(0.5)
        text = sinks.prometheus_text(registry.default_registry())
        parsed = sinks.parse_prometheus_text(text)
        assert parsed[("t_rt_total", (("code", "200"),))] == 4.0
        assert parsed[("t_rt_gauge", ())] == 2.5
        assert parsed[("t_rt_seconds_count", ())] == 2.0
        assert parsed[("t_rt_seconds_sum", ())] == pytest.approx(0.55)
        # cumulative buckets + the mandatory +Inf bucket
        assert parsed[("t_rt_seconds_bucket", (("le", "0.1"),))] == 1.0
        assert parsed[("t_rt_seconds_bucket", (("le", "+Inf"),))] == 2.0

    def test_textfile_write_is_atomic(self, metrics_dir):
        registry.counter("t_file_total", "x").inc()
        path = os.path.join(metrics_dir, sinks.PROM_FILENAME)
        sinks.write_prometheus_textfile(path, registry.default_registry())
        assert os.path.exists(path)
        assert not glob.glob(path + "*.tmp")
        parsed = sinks.parse_prometheus_text(open(path).read())
        assert parsed[("t_file_total", ())] == 1.0


# ------------------------------------------------------------ telemetry
class TestTelemetrySchema:
    @pytest.mark.slow  # compiles a fresh XLA program
    def test_three_step_trainstep_records(self, metrics_dir):
        from paddle_tpu.jit.trainer import TrainStep

        m = _build()
        opt = optimizer.SGD(0.1, parameters=m.parameters())
        loss_fn = nn.MSELoss()
        step = TrainStep(m, lambda a, b: loss_fn(m(a), b), opt,
                         nan_guard=True)
        for a, b in _batches(3):
            step(a, b)
        tele = telemetry.get_telemetry()
        tele.finalize()

        with open(os.path.join(metrics_dir, "events.jsonl")) as f:
            records = [json.loads(line) for line in f]
        srecs = [r for r in records if r["kind"] == "step"]
        assert [r["step"] for r in srecs] == [0, 1, 2]
        for r in srecs:
            assert isinstance(r["loss"], float)
            assert r["grad_norm"] > 0.0
            assert isinstance(r["lr"], float)
            assert set(r["phases"]) >= set(telemetry.PHASES)
            assert r["phases"]["compute"] > 0.0
            assert r["step_wall_s"] > 0.0
            assert r["samples"] == 8 and r["samples_per_s"] > 0
            assert r["skipped"] is False
            # migrated cache stats ride along on every record
            assert "entries" in r["autotune"] and "hits" in r["autotune"]
            assert "misses" in r["compile_cache"]
        # the first dispatch logged a compile event
        assert any(r["kind"] in ("compile", "recompile") for r in records)
        # registry mirrors moved too
        steps_total = registry.default_registry().get(
            "training_steps_total").total()
        assert steps_total == 3

    @pytest.mark.slow  # compiles a fresh XLA program
    def test_save_phase_merged_into_right_step(self, metrics_dir, tmp_path):
        tr = _trainer(str(tmp_path / "ck"), save_every=2)
        tr.run(_batches(4), epochs=1, resume=False)
        with open(os.path.join(metrics_dir, "events.jsonl")) as f:
            srecs = [r for r in (json.loads(x) for x in f)
                     if r["kind"] == "step"]
        assert len(srecs) == 4
        # saves land on the steps that did them, not on their successors
        saved = [r["step"] for r in srecs if r["phases"]["save"] > 0]
        assert saved, "no step carries save time"
        assert all(r["phases"]["data"] >= 0 for r in srecs)
        rep_summary = telemetry.get_telemetry().summary()
        assert rep_summary["records"] == 4
        assert set(rep_summary["phase_ms_avg"]) == set(telemetry.PHASES)

    @pytest.mark.slow  # compiles a fresh XLA program
    def test_disabled_means_no_record_and_no_extra_output(self, tmp_path):
        from paddle_tpu.jit.trainer import TrainStep

        assert not telemetry.enabled()
        m = _build()
        opt = optimizer.SGD(0.1, parameters=m.parameters())
        loss_fn = nn.MSELoss()
        step = TrainStep(m, lambda a, b: loss_fn(m(a), b), opt)
        a, b = _batches(1)[0]
        step(a, b)
        assert telemetry.get_telemetry().records_emitted == 0


# ------------------------------------------------------------ flight recorder
class TestFlightRecorder:
    @pytest.mark.slow  # compiles a fresh XLA program
    def test_dump_on_chaos_nan(self, metrics_dir, tmp_path):
        tr = _trainer(str(tmp_path / "ck"))
        with chaos.scope():
            chaos.poison_steps([2])
            rep = tr.run(_batches(5), epochs=1, resume=False)
        assert rep["steps_skipped"] == 1
        dumps = glob.glob(os.path.join(metrics_dir, "flight", "*.json"))
        assert len(dumps) == 1
        payload = json.load(open(dumps[0]))
        assert payload["reason"] == "nan_guard"
        assert 2 in [s.get("step") for s in payload["steps"]]
        skipped = [s for s in payload["steps"] if s.get("skipped")]
        assert skipped and skipped[0]["step"] == 2
        assert "metrics" in payload and "spans" in payload
        # atomic write: no torn temp files left behind
        assert not glob.glob(os.path.join(metrics_dir, "flight", "*.tmp"))

    @pytest.mark.slow  # compiles a fresh XLA program
    def test_dump_on_sigterm_preemption(self, metrics_dir, tmp_path):
        tr = _trainer(str(tmp_path / "ck"), save_every=0)
        batches = _batches(6)

        def feed():
            for i, b in enumerate(batches):
                if i == 3:
                    chaos.fake_preemption(signal.SIGTERM)
                yield b

        rep = tr.run(feed, epochs=1, resume=False)
        assert rep["status"] == "preempted"
        dumps = glob.glob(os.path.join(metrics_dir, "flight", "*.json"))
        assert len(dumps) == 1
        payload = json.load(open(dumps[0]))
        assert payload["reason"].startswith("preemption_")
        assert "SIGTERM" in payload["reason"]
        # ring carries the steps leading up to the signal
        assert [s["step"] for s in payload["steps"]][-1] == 2

    @pytest.mark.slow  # compiles a fresh XLA program
    def test_dump_on_uncaught_exception(self, metrics_dir, tmp_path):
        tr = _trainer(str(tmp_path / "ck"))

        def feed():
            yield _batches(1)[0]
            raise RuntimeError("boom in the dataloader")

        with pytest.raises(RuntimeError, match="boom"):
            tr.run(feed, epochs=1, resume=False)
        dumps = glob.glob(os.path.join(metrics_dir, "flight", "*.json"))
        assert len(dumps) == 1
        payload = json.load(open(dumps[0]))
        assert payload["reason"] == "exception"
        assert "boom in the dataloader" in payload["exception"]["message"]
        assert "RuntimeError" in payload["exception"]["traceback"]

    def test_noop_when_metrics_off(self, tmp_path):
        assert not registry.metrics_enabled()
        flight_recorder.on_nan_skip(3, loss=float("nan"))
        flight_recorder.on_exception(RuntimeError("x"))
        assert not os.path.exists("flight_recorder")

    def test_ring_is_bounded(self, metrics_dir):
        flags.set_flags({"flight_recorder_steps": 4})
        try:
            fr = flight_recorder.FlightRecorder()
            for i in range(10):
                fr.record_step({"step": i})
            d = os.path.join(metrics_dir, "flight")
            fr.dump("test_bound", directory=d)
            payload = json.load(open(glob.glob(os.path.join(d, "*.json"))[0]))
            assert [s["step"] for s in payload["steps"]] == [6, 7, 8, 9]
            # atomic write: no torn temp file left beside the dump
            assert not glob.glob(os.path.join(d, "*.tmp"))
        finally:
            flags.set_flags({"flight_recorder_steps": 64})


# ------------------------------------------------------------ span fallback
class TestSpanFallback:
    def test_record_event_falls_back_without_native(self, monkeypatch,
                                                    tmp_path):
        from paddle_tpu import native, profiler

        monkeypatch.setattr(native, "available", lambda: False)
        p = profiler.Profiler(targets=[profiler.ProfilerTarget.CPU])
        p.start()
        with profiler.RecordEvent("fallback_span"):
            time.sleep(0.002)
        p.stop()
        evs = p.events()
        mine = [e for e in evs if e["name"] == "fallback_span"]
        assert mine and mine[0]["end_ns"] > mine[0]["begin_ns"]

        out = tmp_path / "trace.json"
        p.export(str(out))
        tr = json.load(open(out))
        host = [e for e in tr["traceEvents"] if e.get("cat") == "host"]
        assert any(e["name"] == "fallback_span" for e in host)
        assert all(e["dur"] >= 0 for e in host)

    def test_record_event_noop_outside_session(self, monkeypatch):
        from paddle_tpu import native, profiler

        monkeypatch.setattr(native, "available", lambda: False)
        assert not spans.enabled()
        mark = spans.mark()
        with profiler.RecordEvent("outside"):
            pass
        assert spans.since(mark) == []

    @pytest.mark.slow  # compiles a fresh XLA program
    def test_subsystem_spans_reach_profiler_export(self, monkeypatch,
                                                   metrics_dir, tmp_path):
        """Runtime spans (ckpt save/commit) land in the same ring the
        profiler collects from — one merged timeline across subsystems."""
        from paddle_tpu import native, profiler

        monkeypatch.setattr(native, "available", lambda: False)
        p = profiler.Profiler(targets=[profiler.ProfilerTarget.CPU])
        p.start()
        tr = _trainer(str(tmp_path / "ck"), save_every=1)
        tr.run(_batches(2), epochs=1, resume=False)
        p.stop()
        names = {e["name"] for e in p.events()}
        assert "jit.train_step" in names
        assert "ckpt.commit" in names
