"""Process-granularity fleet (ISSUE r20 tentpole): supervised OS-process
replicas behind the same FleetRouter placement path as thread replicas.

Cheap half: _RemoteEngine/_RemoteRequest driven against an in-process
ServingServer (no child spawn) — stream parity, cancel, telemetry, error
mapping. Expensive half: ONE module-scoped two-process fleet shared by
the crash-redispatch, zombie-fencing (satellite) and /healthz+/stats
supervision-surface (satellite) tests.
"""
import json
import os
import signal
import threading
import time
import urllib.request

import pytest

from paddle_tpu import native
from paddle_tpu.serving import (
    FleetServer,
    ServingEngine,
    ServingServer,
    build_process_fleet,
    wait_fleet_ready,
)
from paddle_tpu.serving.fleet_proc import (
    FENCED_EXIT,
    _RemoteEngine,
    demo_model,
)

ENGINE_KW = {"max_slots": 3, "block_size": 16, "prefill_chunk": 16}
PROMPT = [5, 6, 7, 8]


def _wait_for(cond, timeout_s=90.0, poll_s=0.05):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(poll_s)
    return False


# ---------------------------------------------------------------------------
# cheap: the remote duck type against an in-process server
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def local_srv():
    engine = ServingEngine(demo_model(), **ENGINE_KW)
    srv = ServingServer(engine, port=0)
    yield srv
    srv.stop()


@pytest.fixture()
def remote(local_srv):
    return _RemoteEngine(f"http://127.0.0.1:{local_srv.port}")


class TestRemoteEngine:
    def test_stream_parity_with_direct_engine(self, local_srv, remote):
        direct = local_srv.engine.submit(PROMPT, max_new_tokens=12)
        assert direct.wait(60)
        req = remote.submit(PROMPT, max_new_tokens=12)
        assert req.wait(60)
        assert req.output_tokens == direct.output_tokens
        assert req.finish_reason == direct.finish_reason
        assert req.state == "finished"
        toks, state, reason = remote.snapshot_output(req)
        assert toks == direct.output_tokens and state == "finished"

    def test_request_telemetry_merges_child_view(self, remote):
        req = remote.submit(PROMPT, max_new_tokens=8, tier="interactive")
        assert req.wait(60)
        t = req.telemetry()
        assert t["tier"] == "interactive"
        assert t["request_id"] == req.request_id
        assert t["output_tokens"] == len(req.output_tokens)
        assert req.ttft_seconds() is not None
        assert req.queue_seconds() is not None

    def test_cancel_severs_stream(self, remote):
        req = remote.submit(PROMPT, max_new_tokens=512)
        assert remote.cancel(req, "cancelled")
        assert req.wait(30)
        assert req.finish_reason == "cancelled"
        assert req.state == "finished"
        assert _wait_for(lambda: remote.inflight() == 0, 10)

    def test_drain_gates_submit(self, remote):
        from paddle_tpu.serving import EngineDrainingError

        remote.drain()
        with pytest.raises(EngineDrainingError):
            remote.submit(PROMPT, max_new_tokens=4)
        assert remote.drained()
        remote.resume()
        req = remote.submit(PROMPT, max_new_tokens=4)
        assert req.wait(60)

    def test_stats_and_health_proxy(self, remote):
        s = remote.stats()
        assert s["remote"] is True and "unreachable" not in s
        snap = remote.obs.health_snapshot()
        assert snap["ok"] and snap["remote"] is True and snap["loop_alive"]

    def test_dead_endpoint_maps_to_errors(self):
        eng = _RemoteEngine("http://127.0.0.1:9")   # discard port: refused
        with pytest.raises(RuntimeError):
            eng.submit(PROMPT, max_new_tokens=4)
        assert eng.stats().get("unreachable") is True
        snap = eng.obs.health_snapshot()
        assert snap["ok"] is False and snap["loop_alive"] is False

    def test_bad_request_maps_to_value_error(self, remote):
        with pytest.raises(ValueError):
            remote.submit([], max_new_tokens=4)

    def test_unspawned_incarnation_rejects_submit(self):
        eng = _RemoteEngine(None)
        with pytest.raises(RuntimeError):
            eng.submit(PROMPT)
        assert eng.stats().get("unreachable") is True


# ---------------------------------------------------------------------------
# expensive: one real two-process fleet, shared module-wide
# ---------------------------------------------------------------------------

needs_native = pytest.mark.skipif(not native.available(),
                                  reason="native TCPStore unavailable")


@pytest.fixture(scope="module")
def proc_fleet(tmp_path_factory):
    if not native.available():
        pytest.skip("native TCPStore unavailable")
    # respawn flight dumps go to FLAGS_metrics_dir/flight (./flight_recorder
    # when unset) — point them at a tmp dir so this module leaves no debris
    from paddle_tpu.core import flags
    prev = flags.get_flag("metrics_dir")
    flags.set_flags({"metrics_dir": str(tmp_path_factory.mktemp("flight"))})
    store = native.TCPStore("127.0.0.1", 0, is_master=True, world_size=1)
    router = build_process_fleet(
        2, store=store, store_addr=("127.0.0.1", store.port),
        spec_kwargs=dict(engine_kwargs=ENGINE_KW, child_heartbeat_s=0.2,
                         respawn_backoff_s=0.5, respawn_max=5),
        router_kwargs=dict(heartbeat_s=0.05, lease_ttl_s=1.0,
                           prefix="/t/fleetproc"))
    router.start()
    assert wait_fleet_ready(router, 120), "process fleet never warmed up"
    yield router, store
    router.stop()
    store.close()
    flags.set_flags({"metrics_dir": prev})


@needs_native
class TestProcessFleet:
    def _oracle(self, router):
        req = router.submit(PROMPT, max_new_tokens=32)
        assert req.wait(60) and req.finish_reason in ("stop", "length")
        return list(req.output_tokens)

    def test_spawn_serve_and_supervision_surface(self, proc_fleet):
        router, _ = proc_fleet
        oracle = self._oracle(router)
        assert oracle
        # the supervision fields ride the fleet HTTP surface (satellite):
        # /healthz and /stats expose incarnation/pid/respawns/last_exit
        srv = FleetServer(router, port=0)
        try:
            with urllib.request.urlopen(srv.url() + "/healthz",
                                        timeout=10) as r:
                health = json.loads(r.read().decode())
            with urllib.request.urlopen(srv.url() + "/stats",
                                        timeout=10) as r:
                stats = json.loads(r.read().decode())
        finally:
            srv._httpd.shutdown()
            srv._httpd.server_close()   # keep the router running
        for snap in health["replicas"].values():
            assert snap["incarnation"] >= 1
            assert isinstance(snap["pid"], int)
            assert snap["respawns"] == 0
            assert snap["warming"] is False
            assert snap["dead"] is False
        for snap in stats["replicas"].values():
            assert snap["incarnation"] >= 1 and "last_exit" in snap

    def test_sigkill_redispatch_bitwise_and_respawn(self, proc_fleet):
        router, _ = proc_fleet
        oracle = self._oracle(router)
        req = router.submit(PROMPT, max_new_tokens=32)
        victim = req.attempts[0].replica
        vinc = victim.incarnation
        os.kill(victim.pid, signal.SIGKILL)
        assert req.wait(90)
        assert req.redispatches >= 1
        assert list(req.output_tokens) == oracle   # bitwise re-dispatch
        # the supervisor respawns the victim under backoff and the new
        # incarnation serves the same bits
        assert _wait_for(lambda: (victim.incarnation > vinc
                                  and not victim.warming()
                                  and not victim.dead(router.lease_ttl_s)))
        assert victim.respawns >= 1
        assert victim.last_exit["exit_code"] == -signal.SIGKILL
        assert self._oracle(router) == oracle

    def test_zombie_is_fenced_not_trusted(self, proc_fleet):
        """Satellite: SIGSTOP past the lease -> replacement spawns; on
        SIGCONT the woken zombie sees the bumped fence token and exits
        with FENCED_EXIT before serving or heartbeating anything."""
        if not hasattr(signal, "SIGSTOP"):
            pytest.skip("no SIGSTOP on this platform")
        from paddle_tpu.observability import registry as oreg

        router, _ = proc_fleet
        oracle = self._oracle(router)
        fenced0 = oreg.REGISTRY.get("fleet_replica_fenced_total").total()
        z = next(iter(router.replicas.values()))
        zpid, zinc = z.pid, z.incarnation
        os.kill(zpid, signal.SIGSTOP)
        assert _wait_for(lambda: (z.incarnation > zinc and not z.warming()
                                  and not z.dead(router.lease_ttl_s)))
        assert z.last_exit["reason"] == "lease_expired"
        # requests keep flowing (and stay bitwise) while the zombie is out
        assert self._oracle(router) == oracle
        os.kill(zpid, signal.SIGCONT)
        assert _wait_for(lambda: (z.last_exit or {}).get("fenced_pid")
                         == zpid, 30)
        with pytest.raises(ProcessLookupError):
            os.kill(zpid, 0)
        assert oreg.REGISTRY.get("fleet_replica_fenced_total").total() \
            == fenced0 + 1
        # the replacement incarnation is healthy and still bitwise
        assert self._oracle(router) == oracle


@needs_native
def test_store_partition_heals_without_respawn(tmp_path):
    """A replica whose store traffic stalls past the lease is declared
    dead; when the partition heals inside the grace window the SAME
    incarnation revives: no respawn, no fence bump, the same bits."""
    from paddle_tpu.core import flags
    from paddle_tpu.resilience import chaos

    prev = flags.get_flag("metrics_dir")
    flags.set_flags({"metrics_dir": str(tmp_path)})
    store = native.TCPStore("127.0.0.1", 0, is_master=True, world_size=1)
    proxy = chaos.StorePartitionProxy("127.0.0.1", store.port)
    # the child reaches the store THROUGH the proxy, the supervisor directly;
    # a 20 s backoff base keeps the heal-grace window well past the stall
    router = build_process_fleet(
        1, store=store, store_addr=(proxy.host, proxy.port),
        spec_kwargs=dict(engine_kwargs=ENGINE_KW, child_heartbeat_s=0.2,
                         respawn_backoff_s=20.0, respawn_max=3),
        router_kwargs=dict(heartbeat_s=0.05, lease_ttl_s=1.0,
                           prefix="/t/fleetpart"))
    router.start()
    try:
        assert wait_fleet_ready(router, 120), "process fleet never warmed up"
        rep = router.replicas["replica-0"]
        inc0, respawns0, pid0 = rep.incarnation, rep.respawns, rep.pid
        first = router.submit(PROMPT, max_new_tokens=16)
        assert first.wait(60)
        oracle = list(first.output_tokens)
        proxy.partition(duration_s=2.5, mode="stall")
        assert _wait_for(lambda: rep.dead(router.lease_ttl_s), 10)
        assert _wait_for(lambda: (not rep.dead(router.lease_ttl_s)
                                  and not rep.warming()), 30)
        assert not proxy.partitioned
        assert (rep.incarnation, rep.respawns, rep.pid) == \
            (inc0, respawns0, pid0)
        again = router.submit(PROMPT, max_new_tokens=16)
        assert again.wait(60) and list(again.output_tokens) == oracle
    finally:
        router.stop()
        store.close()
        proxy.close()
        flags.set_flags({"metrics_dir": prev})
