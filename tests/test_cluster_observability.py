"""Cluster-level observability (ISSUE r10).

Covers: the InProcStore TCPStore stand-in, cross-rank aggregation +
straggler flagging with threads simulating 4 ranks, the rolling-window
anomaly detectors (positive and no-false-positive), memory gauges on the
CPU backend + per-executable XLA accounting, the /metrics + /healthz HTTP
round-trip, the multi-host synchronized checkpoint commit, the analyzer's
real-VMEM resolution, and flight-dump filename uniqueness + anomaly/cluster
embedding.
"""
import glob
import json
import os
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn, optimizer
from paddle_tpu.core import flags
from paddle_tpu.distributed.env import InProcStore
from paddle_tpu.observability import (
    anomaly, cluster, flight_recorder, memory, registry, reset_all, serve,
)
from paddle_tpu.resilience import chaos
from paddle_tpu.resilience.checkpoint_manager import CheckpointManager


@pytest.fixture(autouse=True)
def _clean():
    reset_all()
    chaos.clear()
    yield
    flags.set_flags({"metrics": "off", "metrics_dir": "", "anomaly": "off"})
    reset_all()
    chaos.clear()


@pytest.fixture
def metrics_dir(tmp_path):
    d = str(tmp_path / "metrics")
    flags.set_flags({"metrics": "on", "metrics_dir": d})
    return d


def _rec(step, *, loss=1.0, compute=0.01, grad_norm=1.0, tps=1000.0,
         wall=None):
    return {
        "step": int(step), "loss": loss, "grad_norm": grad_norm,
        "step_wall_s": wall if wall is not None else compute + 0.002,
        "tokens_per_s": tps,
        "phases": {"data": 0.001, "compute": compute, "reduce": 0.0,
                   "save": 0.0},
    }


# ------------------------------------------------------------ InProcStore
class TestInProcStore:
    def test_set_get_roundtrip_and_encoding(self):
        s = InProcStore()
        s.set("a", "hello")
        assert s.get("a", blocking=False) == b"hello"
        s.set("b", b"\x00\x01")
        assert s.get("b") == b"\x00\x01"
        assert s.get("missing", blocking=False) is None
        assert s.num_keys() == 2
        s.delete("a")
        assert s.get("a", blocking=False) is None

    def test_add_and_wait_ge(self):
        s = InProcStore()
        assert s.add("n", 1) == 1
        assert s.add("n", 2) == 3
        assert s.wait_ge("n", 3, timeout_s=1) == 3

    def test_blocking_get_sees_later_set(self):
        s = InProcStore()
        out = {}

        def reader():
            out["v"] = s.get("late", blocking=True, timeout_s=5)

        t = threading.Thread(target=reader)
        t.start()
        time.sleep(0.05)
        s.set("late", "v1")
        t.join(timeout=5)
        assert out["v"] == b"v1"

    def test_barrier_waves(self):
        s = InProcStore()
        world, rounds = 3, 2
        hits = []

        def worker(r):
            for _ in range(rounds):
                s.barrier("b", world_size=world)
                hits.append(r)

        ts = [threading.Thread(target=worker, args=(r,))
              for r in range(world)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=10)
        assert len(hits) == world * rounds


# ------------------------------------------------------------ cluster agg
def _run_cluster(world, steps, delay_rank=None, inject_at=0, m=3):
    store = InProcStore()
    cts = [cluster.ClusterTelemetry(store, r, world, k=2.0, m=m,
                                    timeout_s=10.0)
           for r in range(world)]

    def run_rank(r):
        for s in range(steps):
            slow = delay_rank is not None and r == delay_rank \
                and s >= inject_at
            cts[r].publish(_rec(s, compute=0.05 if slow else 0.01,
                                loss=1.0 + 0.1 * r))

    threads = [threading.Thread(target=run_rank, args=(r,))
               for r in range(1, world)]
    for t in threads:
        t.start()
    run_rank(0)
    for t in threads:
        t.join(timeout=30)
    return cts[0]


class TestClusterAggregation:
    def test_aggregates_min_median_max(self, metrics_dir):
        ct = _run_cluster(world=4, steps=3)
        assert len(ct.aggregates) == 3
        agg = ct.aggregates[-1]
        assert agg["ranks"] == 4
        ph = agg["phases"]["compute"]
        assert ph["min"] == pytest.approx(0.01)
        assert ph["median"] == pytest.approx(0.01)
        assert ph["max"] == pytest.approx(0.01)
        # losses were 1.0 / 1.1 / 1.2 / 1.3 across ranks
        assert agg["loss"]["min"] == pytest.approx(1.0)
        assert agg["loss"]["max"] == pytest.approx(1.3)
        assert agg["tokens_per_s_total"] == pytest.approx(4000.0)

    def test_straggler_flagged_on_rising_edge(self, metrics_dir):
        ct = _run_cluster(world=4, steps=10, delay_rank=2, inject_at=4, m=3)
        evs = [e for e in ct.straggler_events if e["rank"] == 2]
        assert len(evs) == 1  # rising edge only, not one event per step
        ev = evs[0]
        assert ev["phase"] == "compute"
        # m consecutive slow steps starting at inject_at
        assert ev["step"] == 4 + 3 - 1
        assert ev["ratio"] > 2.0
        snap = ct.snapshot()
        assert snap["flagged"]["2"]["compute"] >= ev["step"]
        # the flight recorder got the cluster view for future dumps
        assert flight_recorder.cluster_snapshot()["flagged"]["2"]

    def test_no_false_positives_on_steady_ranks(self, metrics_dir):
        ct = _run_cluster(world=4, steps=10)
        assert ct.straggler_events == []
        assert not ct.snapshot()["flagged"]

    def test_store_drained_after_aggregation(self, metrics_dir):
        ct = _run_cluster(world=2, steps=4)
        assert len(ct.aggregates) == 4
        assert ct.store.num_keys() == 0


# ------------------------------------------------------------ anomaly
class TestAnomaly:
    def test_loss_spike_fires_and_dumps(self, metrics_dir):
        flags.set_flags({"anomaly": "on"})
        assert anomaly.anomaly_enabled()
        eng = anomaly.AnomalyEngine()
        for s in range(20):
            assert eng.observe(_rec(s, loss=2.0 + 0.001 * s)) == []
        found = eng.observe(_rec(20, loss=50.0))
        kinds = [e["kind"] for e in found]
        assert "loss_spike" in kinds
        assert len(eng.dumps) == 1
        with open(eng.dumps[0]) as f:
            payload = json.load(f)
        assert payload["anomaly"]["kind"] == "loss_spike"
        assert payload["anomaly"]["step"] == 20
        assert payload["anomalies"]  # the ring rides along
        # atomic write: no torn temp file left beside the dump
        assert not glob.glob(os.path.join(os.path.dirname(eng.dumps[0]),
                                          "*.tmp"))

    def test_grad_norm_spike(self, metrics_dir):
        eng = anomaly.AnomalyEngine(dump=False)
        for s in range(15):
            eng.observe(_rec(s, grad_norm=1.0))
        found = eng.observe(_rec(15, grad_norm=40.0))
        assert [e["kind"] for e in found] == ["grad_norm_spike"]

    def test_step_time_regression_needs_patience(self, metrics_dir):
        eng = anomaly.AnomalyEngine(dump=False)
        for s in range(15):
            eng.observe(_rec(s, wall=0.01))
        # one slow step is a hiccup, not a regression
        assert eng.observe(_rec(15, wall=0.03)) == []
        eng.observe(_rec(16, wall=0.03))
        found = eng.observe(_rec(17, wall=0.03))
        assert any(e["kind"] == "step_time_regression" for e in found)

    def test_throughput_collapse(self, metrics_dir):
        eng = anomaly.AnomalyEngine(dump=False)
        for s in range(15):
            eng.observe(_rec(s, tps=1000.0))
        for s in range(15, 17):
            assert eng.observe(_rec(s, tps=100.0)) == []
        found = eng.observe(_rec(17, tps=100.0))
        assert any(e["kind"] == "throughput_collapse" for e in found)

    def test_compile_cache_collapse(self, metrics_dir):
        eng = anomaly.AnomalyEngine(dump=False)
        misses = 0
        for s in range(5):
            r = _rec(s)
            r["compile_cache"] = {"hits": 100, "misses": misses}
            assert eng.observe(r) == []
        found = []
        for s in range(5, 10):
            misses += 1  # recompile storm: misses advance every step
            r = _rec(s)
            r["compile_cache"] = {"hits": 100, "misses": misses}
            found += eng.observe(r)
        assert any(e["kind"] == "compile_cache_collapse" for e in found)

    def test_steady_telemetry_stays_silent(self, metrics_dir):
        eng = anomaly.AnomalyEngine(dump=False)
        rng = np.random.RandomState(0)
        for s in range(60):
            found = eng.observe(_rec(
                s, loss=2.0 + 0.01 * rng.randn(),
                grad_norm=1.0 + 0.02 * rng.randn(),
                wall=0.01 + 0.0005 * abs(rng.randn()),
                tps=1000.0 + 10 * rng.randn()))
            assert found == []
        assert eng.recent() == []

    def test_dump_cooldown_limits_dumps(self, metrics_dir):
        flags.set_flags({"anomaly": "on"})
        eng = anomaly.AnomalyEngine(dump_cooldown_steps=100)
        for s in range(20):
            eng.observe(_rec(s, loss=2.0))
        eng.observe(_rec(20, loss=50.0))
        # detector cooldown re-arms after 25 steps; dump cooldown is 100
        for s in range(21, 60):
            eng.observe(_rec(s, loss=2.0))
        eng.observe(_rec(60, loss=50.0))
        assert len(eng.recent()) == 2  # both detected...
        assert len(eng.dumps) == 1    # ...one dump

    def test_from_flags_gating(self, metrics_dir):
        assert anomaly.from_flags() is None  # FLAGS_anomaly off
        flags.set_flags({"anomaly": "on"})
        assert isinstance(anomaly.from_flags(), anomaly.AnomalyEngine)


# ------------------------------------------------------------ memory
class TestMemory:
    def test_gauges_exist_on_cpu_backend(self, metrics_dir):
        summary = memory.update_memory_gauges()
        assert summary["devices"]  # devices enumerated even without stats
        assert summary["host"]["rss"] > 0
        assert summary["host"]["peak_rss"] > 0
        g = registry.REGISTRY.get("host_memory_bytes")
        assert g.value(kind="rss") > 0

    def test_note_executable_records_cost_analysis(self, metrics_dir):
        import jax
        import jax.numpy as jnp

        compiled = jax.jit(
            lambda x: jnp.sum(x @ x)).lower(
                jnp.ones((64, 64), jnp.float32)).compile()
        info = memory.note_executable("probe", compiled)
        assert info.get("flops", 0) > 0
        report = memory.memory_report()
        assert "probe" in report["executables"]
        assert report["executables"]["probe"]["flops"] > 0

    def test_note_executable_never_raises(self, metrics_dir):
        assert memory.note_executable("bogus", object()) == {}


# ------------------------------------------------------------ serve
class TestServe:
    def _get(self, port, path):
        req = urllib.request.Request(f"http://127.0.0.1:{port}{path}")
        try:
            with urllib.request.urlopen(req, timeout=5) as resp:
                return resp.status, resp.read()
        except urllib.error.HTTPError as e:
            return e.code, e.read()

    def test_metrics_and_healthz_roundtrip(self, metrics_dir):
        registry.counter("serve_probe_total", "probe").inc(3)
        srv = serve.start_metrics_server(port=0)
        assert srv.port > 0
        code, body = self._get(srv.port, "/metrics")
        assert code == 200
        assert b"serve_probe_total 3" in body
        assert b"host_memory_bytes" in body  # refreshed per scrape
        code, body = self._get(srv.port, "/healthz")
        health = json.loads(body)
        assert code == 200
        assert health["status"] == "idle"  # no steps yet is not failure
        code, _ = self._get(srv.port, "/nope")
        assert code == 404

    def test_healthz_503_on_recent_anomaly(self, metrics_dir):
        flags.set_flags({"anomaly": "on"})
        eng = anomaly.AnomalyEngine(dump=False)
        serve.set_health_engine(eng)
        for s in range(20):
            eng.observe(_rec(s, loss=2.0))
        eng.observe(_rec(20, loss=50.0))
        srv = serve.start_metrics_server(port=0)
        code, body = self._get(srv.port, "/healthz")
        assert code == 503
        health = json.loads(body)
        assert health["status"] == "anomalous"
        assert health["last_anomaly"]["kind"] == "loss_spike"


# ------------------------------------------------------------ ckpt commit
class TestCkptSyncCommit:
    def _state(self, seed=0):
        rng = np.random.RandomState(seed)
        return {"w": rng.randn(4, 4).astype(np.float32)}

    def test_two_rank_synchronized_commit(self, metrics_dir, tmp_path):
        store = InProcStore()
        root = str(tmp_path / "ckpt")
        leader = CheckpointManager(root, store=store, rank=0, world_size=2,
                                   sync_timeout_s=20.0)
        follower = CheckpointManager(root, store=store, rank=1,
                                     world_size=2, sync_timeout_s=20.0)
        state = self._state()
        events = []

        def follower_save():
            path = follower.save(7, self._state(1))  # payload ignored
            events.append(("follower_done", path, time.monotonic()))

        t = threading.Thread(target=follower_save)
        t.start()
        time.sleep(0.1)
        # the follower must still be parked on the committed marker
        assert not events
        final = leader.save(7, state)
        t.join(timeout=20)
        assert events and events[0][1] == final
        assert os.path.isdir(final)
        restored = leader.restore_latest()
        assert restored.step == 7
        np.testing.assert_allclose(restored.state["w"], state["w"])
        c = registry.REGISTRY.get("cluster_ckpt_commits_total")
        assert c.value(role="leader") == 1
        assert c.value(role="follower") == 1

    def test_leader_times_out_without_followers(self, tmp_path):
        store = InProcStore()
        leader = CheckpointManager(str(tmp_path / "c"), store=store, rank=0,
                                   world_size=2, sync_timeout_s=0.3)
        with pytest.raises(TimeoutError):
            leader.save(1, self._state())
        # the rename never happened: no committed checkpoint exists
        assert leader.all_steps() == []

    def test_single_process_bypasses_protocol(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path / "c"))
        assert not mgr._sync_enabled
        mgr.save(3, self._state())
        assert mgr.latest_step() == 3


# ------------------------------------------------------------ analyzer VMEM
class TestPallasVmem:
    def test_env_override_wins(self, monkeypatch):
        from paddle_tpu.analysis.rules import pallas_tiling as pt

        monkeypatch.setenv("PALLAS_VMEM_BYTES", str(64 * 1024 * 1024))
        assert pt.vmem_limit_bytes(refresh=True) == 64 * 1024 * 1024
        monkeypatch.setenv("PALLAS_VMEM_BYTES", "not-a-number")
        monkeypatch.setenv("XLA_FLAGS", "")
        assert pt.vmem_limit_bytes(refresh=True) == pt.VMEM_BYTES
        pt.vmem_limit_bytes(refresh=True)  # leave the cache coherent

    def test_xla_flags_scoped_limit(self, monkeypatch):
        from paddle_tpu.analysis.rules import pallas_tiling as pt

        monkeypatch.delenv("PALLAS_VMEM_BYTES", raising=False)
        monkeypatch.setenv(
            "XLA_FLAGS", "--foo=1 --xla_tpu_scoped_vmem_limit_kib=32768")
        assert pt.vmem_limit_bytes(refresh=True) == 32768 * 1024
        monkeypatch.setenv("XLA_FLAGS", "")
        # CPU backend has no vmem stats -> documented 16 MiB fallback
        assert pt.vmem_limit_bytes(refresh=True) == pt.VMEM_BYTES


# ------------------------------------------------------------ flight dumps
class TestFlightDumps:
    def test_same_second_dumps_do_not_collide(self, metrics_dir):
        rec = flight_recorder.get_flight_recorder()
        p1 = rec.dump("collide")
        p2 = rec.dump("collide")  # same reason, same wall-clock second
        assert p1 != p2
        assert os.path.exists(p1) and os.path.exists(p2)

    def test_dump_embeds_anomalies_and_cluster(self, metrics_dir):
        flight_recorder.note_anomaly({"kind": "loss_spike", "step": 9})
        flight_recorder.set_cluster_snapshot(
            {"world_size": 4, "flagged": {"2": {"compute": 9}}})
        path = flight_recorder.get_flight_recorder().dump(
            "forensics", extra={"anomaly": {"kind": "loss_spike"}})
        with open(path) as f:
            payload = json.load(f)
        assert payload["anomalies"][0]["kind"] == "loss_spike"
        assert payload["cluster"]["flagged"]["2"]["compute"] == 9
        assert payload["anomaly"]["kind"] == "loss_spike"
