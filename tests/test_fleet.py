"""Serving-fleet tests (ISSUE r18): circuit breaker state machine,
store-backed replica registry, jittered Retry-After, prefix-affinity
routing, dead-replica re-dispatch with bitwise greedy parity, hedged
retries with loser cancellation, graceful drain, fleet-level load
shedding, and the FleetServer HTTP front end.

Most router tests run the fleet UNSTARTED on a fake clock: replica
engines are stepped by hand and `router.poll()` is the monitor tick,
so failure detection, re-dispatch and hedging are fully deterministic
(no thread timing in the assertions). The drain and HTTP tests run the
real threads — that is the surface they exist to cover.
"""
import json
import os
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.core import flags as _flags
from paddle_tpu.distributed.env import InProcStore, ReplicaRegistry
from paddle_tpu.models import GPTConfig, GPTForCausalLM
from paddle_tpu.observability import registry, reset_all
from paddle_tpu.serving import (
    CircuitBreaker,
    EngineDrainingError,
    FleetAutoscaler,
    FleetRouter,
    FleetServer,
    QueueFullError,
    ServingEngine,
    export_fleet_trace,
    parse_fleet_roles,
)
from paddle_tpu.serving.fleet_observability import (
    coverage_of,
    unparented_spans,
)


def _model():
    # every replica (and the parity oracle) is seeded identically:
    # replicas must be bitwise-interchangeable for re-dispatch parity
    paddle.seed(11)
    cfg = GPTConfig.tiny()
    m = GPTForCausalLM(cfg)
    m.eval()
    return cfg, m


def _fleet(n=2, **router_kw):
    cfg = None
    engines = []
    for _ in range(n):
        cfg, m = _model()
        engines.append(ServingEngine(m, max_slots=3, block_size=16,
                                     prefill_chunk=16))
    return cfg, FleetRouter(engines, **router_kw)


def _drive(router, freqs, max_iters=5000):
    """Manual engine loop + monitor: step every live replica that has
    work, then poll, until every fleet request settles."""
    for _ in range(max_iters):
        if all(f.done for f in freqs):
            return
        for rep in router.replicas.values():
            if not rep._killed and rep.engine.sched.has_work():
                rep.engine.step()
        router.poll()
    raise AssertionError(
        f"requests did not settle: {[f.done for f in freqs]}")


# --------------------------------------------------------- circuit breaker
class TestCircuitBreaker:
    def test_closed_open_half_open_cycle(self):
        fake = [0.0]
        br = CircuitBreaker(max_errors=3, cooldown_s=2.0,
                            clock=lambda: fake[0])
        assert br.state == "closed" and br.allow()
        br.record_failure()
        br.record_failure()
        assert br.state == "closed"      # under the threshold
        br.record_failure()
        assert br.state == "open" and not br.allow()
        fake[0] = 1.9
        assert br.state == "open"        # cooldown not elapsed
        fake[0] = 2.0
        assert br.state == "half_open"
        # exactly ONE probe token while half-open
        assert br.allow()
        assert not br.allow()
        br.record_failure()              # probe failed: re-open, new clock
        assert br.state == "open" and not br.allow()
        fake[0] = 4.0
        assert br.state == "half_open" and br.allow()
        br.record_success()              # probe succeeded: fully closed
        assert br.state == "closed"
        assert br.allow() and br.allow()  # no probe rationing when closed

    def test_success_resets_error_streak(self):
        br = CircuitBreaker(max_errors=2, cooldown_s=1.0)
        br.record_failure()
        br.record_success()
        br.record_failure()
        assert br.state == "closed"      # streak broken — CONSECUTIVE errors


# --------------------------------------------------------- replica registry
class TestReplicaRegistry:
    def test_register_heartbeat_lease_deregister(self):
        fake = [0.0]
        reg = ReplicaRegistry(InProcStore(), clock=lambda: fake[0])
        reg.register("r0", meta={"slots": 4})
        reg.register("r1")
        assert reg.replicas() == ["r0", "r1"]
        assert reg.meta("r0") == {"slots": 4}
        assert reg.meta("r1") == {}
        assert reg.alive("r0", lease_ttl_s=0.5)
        fake[0] = 0.6                    # lease lapses without a heartbeat
        assert not reg.alive("r0", lease_ttl_s=0.5)
        reg.heartbeat("r0")
        assert reg.alive("r0", lease_ttl_s=0.5)
        assert reg.heartbeat_age("nope") == float("inf")
        reg.deregister("r1", reason="drain")
        assert reg.replicas() == ["r0"]
        assert reg.replicas(include_left=True) == ["r0", "r1"]
        assert reg.has_left("r1") and not reg.has_left("r0")
        reg.register("r1")               # rejoin clears the tombstone
        assert reg.replicas() == ["r0", "r1"]
        assert not reg.has_left("r1")


# ------------------------------------------------------- Retry-After jitter
class TestRetryAfterJitter:
    def test_jitter_is_forward_only_and_spread(self):
        old_base = _flags.get_flag("serving_retry_after_s")
        old_jit = _flags.get_flag("serving_retry_after_jitter")
        _flags.set_flags({"serving_retry_after_s": 2.0,
                          "serving_retry_after_jitter": 0.5})
        try:
            vals = {QueueFullError(1, 1).retry_after_s for _ in range(32)}
            # never earlier than the base hint, never past base*(1+jitter)
            assert all(2.0 <= v <= 3.0 for v in vals)
            assert len(vals) > 1         # the shed wave is actually spread
            _flags.set_flags({"serving_retry_after_jitter": 0.0})
            assert QueueFullError(1, 1).retry_after_s == 2.0
            # explicit value bypasses the jitter entirely
            assert QueueFullError(1, 1, retry_after_s=7.5).retry_after_s \
                == 7.5
        finally:
            _flags.set_flags({"serving_retry_after_s": old_base,
                              "serving_retry_after_jitter": old_jit})


# ------------------------------------------------------------- fleet router
class TestFleetRouter:
    def test_prefix_affinity_and_least_loaded_routing(self):
        fake = [0.0]
        cfg, router = _fleet(2, clock=lambda: fake[0], lease_ttl_s=1000.0)
        rng = np.random.default_rng(0)
        prompt = [int(t) for t in rng.integers(0, cfg.vocab_size, 20)]
        a = router.submit(prompt, max_new_tokens=4)
        assert a.attempts[0].replica.rid == "replica-0"  # idle tie: id order
        _drive(router, [a])
        # replica-0 now owns the prompt's chain in its prefix cache; the
        # follow-up must route there even though loads are equal again
        b = router.submit(prompt, max_new_tokens=4)
        assert b.attempts[0].replica.rid == "replica-0"
        # a cache-cold prompt balances AWAY from the busy replica
        cold = [int(t) for t in rng.integers(0, cfg.vocab_size, 10)]
        c = router.submit(cold, max_new_tokens=4)
        assert c.attempts[0].replica.rid == "replica-1"
        _drive(router, [b, c])
        ids = {a.request_id, b.request_id, c.request_id}
        assert len(ids) == 3             # auto-assigned ids are unique

    def test_kill_redispatch_bitwise_parity_zero_lost(self):
        fake = [0.0]
        cfg, router = _fleet(2, clock=lambda: fake[0], lease_ttl_s=1000.0)
        _, ref = _model()
        rng = np.random.default_rng(1)
        n_new = 8
        prompts = [[int(t) for t in rng.integers(0, cfg.vocab_size, n)]
                   for n in (5, 19, 33, 7)]
        expected = []
        for p in prompts:
            ids = np.asarray([p], np.int32)
            out = ref.generate(paddle.to_tensor(ids),
                               max_new_tokens=n_new).numpy()[0, -n_new:]
            expected.append([int(t) for t in out])

        red0 = registry.REGISTRY.get(
            "fleet_requests_redispatched_total").total()
        freqs = [router.submit(p, max_new_tokens=n_new) for p in prompts]
        on_r0 = [f for f in freqs
                 if f.attempts[0].replica.rid == "replica-0"]
        assert len(on_r0) == 2           # load balancing alternated
        # let the doomed replica make partial progress, then crash it
        for _ in range(3):
            router.replicas["replica-0"].engine.step()
        router.kill_replica("replica-0")
        router.poll()                    # detect + re-dispatch orphans
        for f in on_r0:
            (live,) = f.live_attempts()
            assert live.kind == "redispatch"
            assert live.replica.rid == "replica-1"
        _drive(router, freqs)
        # zero lost: every accepted request completed...
        assert all(f.finish_reason == "length" for f in freqs)
        # ...and greedy re-decode is bitwise what the dead replica owed
        for f, want in zip(freqs, expected):
            assert f.output_tokens == want
        assert sum(f.redispatches for f in freqs) == 2
        assert registry.REGISTRY.get(
            "fleet_requests_redispatched_total").total() == red0 + 2
        assert not router.routable(router.replicas["replica-0"])
        assert router.health()["ok"]     # fleet still serves on replica-1

    def test_hedge_fires_past_deadline_and_cancels_loser(self):
        fake = [0.0]
        cfg, router = _fleet(2, clock=lambda: fake[0], lease_ttl_s=1000.0,
                             hedge_ttft_ms=50.0)
        _, ref = _model()
        rng = np.random.default_rng(2)
        n_new = 6
        prompt = [int(t) for t in rng.integers(0, cfg.vocab_size, 6)]
        ids = np.asarray([prompt], np.int32)
        want = [int(t) for t in ref.generate(
            paddle.to_tensor(ids), max_new_tokens=n_new).numpy()[0, -n_new:]]

        hedged0 = registry.REGISTRY.get("fleet_requests_hedged_total").total()
        wins0 = registry.REGISTRY.get(
            "fleet_hedge_wins_total").value(winner="hedge")
        freq = router.submit(prompt, max_new_tokens=n_new)
        assert freq.attempts[0].replica.rid == "replica-0"
        r0 = router.replicas["replica-0"].engine
        r0.step()                        # admitted + prefilling, no token yet
        router.poll()
        assert not freq.hedged           # deadline not reached at t=0
        fake[0] = 0.1                    # past the 50ms TTFT deadline
        router.poll()
        assert freq.hedged
        assert [a.kind for a in freq.attempts] == ["primary", "hedge"]
        assert freq.attempts[1].replica.rid == "replica-1"
        assert registry.REGISTRY.get(
            "fleet_requests_hedged_total").total() == hedged0 + 1
        # ONLY the hedge replica makes progress (the primary is hung):
        # first token wins and the primary is cancelled mid-flight
        r1 = router.replicas["replica-1"].engine
        for _ in range(2000):
            if freq.done:
                break
            if r1.sched.has_work():
                r1.step()
            router.poll()
        assert freq.done
        assert freq.output_tokens == want
        winner = [a for a in freq.attempts if not a.failed]
        assert [a.kind for a in winner] == ["hedge"]
        assert registry.REGISTRY.get(
            "fleet_hedge_wins_total").value(winner="hedge") == wins0 + 1
        # the loser's slot + worst-case KV reservation went back to the
        # pool the moment it lost the race (not when it would have ended)
        st = r0.stats()
        assert st["running"] == 0 and st["waiting"] == 0
        assert st["prefilling"] == 0 and st["reserved_blocks"] == 0

    def test_fleet_shed_when_every_queue_full(self):
        fake = [0.0]
        old = _flags.get_flag("serving_max_queue")
        _flags.set_flags({"serving_max_queue": 1})
        try:
            cfg, router = _fleet(2, clock=lambda: fake[0],
                                 lease_ttl_s=1000.0)
            shed = registry.REGISTRY.get("fleet_requests_shed_total")
            before = shed.value(reason="queue_full")
            router.submit([1, 2, 3])     # replica-0's queue (never stepped)
            router.submit([4, 5, 6])     # balances to replica-1's queue
            with pytest.raises(QueueFullError) as ei:
                router.submit([7, 8, 9])
            assert ei.value.retry_after_s > 0
            assert shed.value(reason="queue_full") == before + 1
        finally:
            _flags.set_flags({"serving_max_queue": old})

    def test_shed_when_no_replica_routable(self):
        fake = [0.0]
        cfg, router = _fleet(2, clock=lambda: fake[0], lease_ttl_s=1000.0)
        shed = registry.REGISTRY.get("fleet_requests_shed_total")
        before = shed.value(reason="no_healthy_replica")
        router.kill_replica("replica-0")
        router.kill_replica("replica-1")
        with pytest.raises(QueueFullError):
            router.submit([1, 2, 3])
        assert shed.value(reason="no_healthy_replica") == before + 1
        assert router.health()["ok"] is False

    def test_breaker_takes_faulty_replica_out_of_rotation(self):
        fake = [0.0]
        cfg, router = _fleet(2, clock=lambda: fake[0], lease_ttl_s=1000.0,
                             breaker_errors=2, breaker_cooldown_s=5.0)
        r0 = router.replicas["replica-0"]
        boom = RuntimeError("injected submit fault")

        def bad_submit(*a, **kw):
            raise boom

        real_submit = r0.engine.submit
        r0.engine.submit = bad_submit
        # each submit strikes replica-0 once, then falls through to
        # replica-1 — the client never sees the fault
        a = router.submit([1, 2, 3], max_new_tokens=2)
        assert a.attempts[0].replica.rid == "replica-1"
        assert r0.breaker.state == "closed"
        b = router.submit([4, 5, 6], max_new_tokens=2)
        assert b.attempts[0].replica.rid == "replica-1"
        assert r0.breaker.state == "open"          # 2nd consecutive strike
        assert not router.routable(r0)
        assert router.health()["replicas"]["replica-0"]["breaker"] == "open"
        # cooldown elapses -> half-open -> the probe heals the replica
        r0.engine.submit = real_submit
        fake[0] = 5.0
        assert r0.breaker.state == "half_open"
        c = router.submit([7, 8, 9], max_new_tokens=2)
        assert c.attempts[0].replica.rid == "replica-0"  # the probe
        assert r0.breaker.state == "closed"
        _drive(router, [a, b, c])

    def test_drain_routes_around_and_resume_restores(self):
        cfg, router = _fleet(2)
        router.start()
        try:
            rng = np.random.default_rng(3)
            prompt = [int(t) for t in rng.integers(0, cfg.vocab_size, 12)]
            a = router.submit(prompt, max_new_tokens=48)
            assert a.attempts[0].replica.rid == "replica-0"
            router.drain("replica-0")
            # the draining engine itself refuses new work...
            with pytest.raises(EngineDrainingError):
                router.replicas["replica-0"].engine.submit([1, 2, 3])
            # ...and the router routes around it, even against affinity
            b = router.submit(prompt, max_new_tokens=4)
            assert b.attempts[0].replica.rid == "replica-1"
            health = router.health()
            assert health["ok"]          # fleet still up on replica-1
            snap = health["replicas"]["replica-0"]
            assert snap["status"] == "draining" and snap["ok"] is False
            # in-flight work on the draining replica runs to completion
            assert a.wait(timeout=120) and a.finish_reason == "length"
            deadline = time.monotonic() + 30
            while not router.drained("replica-0") \
                    and time.monotonic() < deadline:
                time.sleep(0.01)
            assert router.drained("replica-0")
            router.resume("replica-0")
            assert router.health()["replicas"]["replica-0"]["status"] \
                != "draining"
            c = router.submit(prompt, max_new_tokens=4)
            assert c.attempts[0].replica.rid == "replica-0"  # affinity back
            assert b.wait(timeout=120) and c.wait(timeout=120)
        finally:
            router.stop()


# ------------------------------------------------ disaggregated serving
class TestDisaggregatedFleet:
    def test_parse_fleet_roles(self):
        assert parse_fleet_roles(None, 3) == ["any"] * 3
        assert parse_fleet_roles("symmetric", 2) == ["any", "any"]
        assert (parse_fleet_roles("prefill:1,decode:2", 3)
                == ["prefill", "decode", "decode"])
        with pytest.raises(ValueError):
            parse_fleet_roles("prefill:1,decode:1", 3)  # doesn't cover
        with pytest.raises(ValueError):
            parse_fleet_roles("oracle:2", 2)            # unknown role

    def test_disagg_streams_kv_and_decode_pool_never_prefills(self):
        fake = [0.0]
        cfg, router = _fleet(3, clock=lambda: fake[0], lease_ttl_s=1000.0,
                             roles="prefill:1,decode:2")
        _, ref = _model()
        rng = np.random.default_rng(21)
        n_new = 6
        prompts = [[int(t) for t in rng.integers(0, cfg.vocab_size, 32)]
                   for _ in range(4)]
        want = []
        for p in prompts:
            ids = np.asarray([p], np.int32)
            out = ref.generate(paddle.to_tensor(ids),
                               max_new_tokens=n_new).numpy()[0, -n_new:]
            want.append([int(t) for t in out])
        freqs = [router.submit(p, max_new_tokens=n_new) for p in prompts]
        # admission lands every prompt on the (single) prefill replica
        assert all(f.attempts[0].kind == "prefill" for f in freqs)
        assert {f.attempts[0].replica.rid for f in freqs} == {"replica-0"}
        _drive(router, freqs)
        for f, w in zip(freqs, want):
            assert f.output_tokens == w           # bitwise vs the oracle
            # the winning attempt is the decode stage on a decode replica
            (winner,) = [a for a in f.attempts if not a.failed]
            assert winner.kind == "decode"
            assert winner.replica.role == "decode"
            # the whole prompt chain crossed the wire (2 blocks of 16)
            ks = f.kv_streamed
            assert ks and ks["kind"] == "prefill"
            assert ks["imported"] + ks["dedup"] == 2
            assert winner.req.prefix_matched == len(f.prompt)
        # the decode pool computed ZERO prefill tokens
        for rid in ("replica-1", "replica-2"):
            assert router.replicas[rid].engine.prefill_tokens == 0
        assert router.replicas["replica-0"].engine.prefill_tokens > 0

    def test_drain_migrates_mid_decode_with_zero_reprefill(self):
        fake = [0.0]
        cfg, router = _fleet(2, clock=lambda: fake[0], lease_ttl_s=1000.0)
        _, ref = _model()
        rng = np.random.default_rng(22)
        n_new = 48
        prompt = [int(t) for t in rng.integers(0, cfg.vocab_size, 32)]
        ids = np.asarray([prompt], np.int32)
        want = [int(t) for t in ref.generate(
            paddle.to_tensor(ids), max_new_tokens=n_new).numpy()[0, -n_new:]]
        f = router.submit(prompt, max_new_tokens=n_new)
        rep = f.attempts[0].replica
        for _ in range(8):          # 2 prefill chunks + a few decode steps
            rep.engine.step()
        _, state, _ = rep.engine.snapshot_output(f.attempts[0].req)
        assert state != "finished"  # caught mid-decode, KV chain live
        router.drain(rep.rid, migrate=True)   # synchronous migration
        assert f.migrations == 1
        _drive(router, [f])
        # exactly one handoff, no duplicate re-dispatch raced in
        assert [a.kind for a in f.attempts] == ["primary", "migrate"]
        mig = f.attempts[1]
        assert mig.replica.rid != rep.rid
        # the streamed prompt chain admitted as a FULL prefix hit: the
        # survivor re-prefilled nothing
        assert mig.req.prefix_matched == len(prompt)
        assert mig.replica.engine.prefill_tokens == 0
        assert f.output_tokens == want        # bitwise across the handoff
        assert router.drained(rep.rid)

    def test_autoscaler_tracks_load_up_and_down_bitwise(self):
        fake = [0.0]
        cfg, router = _fleet(1, clock=lambda: fake[0], lease_ttl_s=1000.0)
        _, ref = _model()

        def spawn():
            _, m = _model()
            return ServingEngine(m, max_slots=3, block_size=16,
                                 prefill_chunk=16)

        scaled = registry.REGISTRY.get("fleet_scale_events_total")
        ups0 = scaled.value(direction="up")
        downs0 = scaled.value(direction="down")
        scaler = FleetAutoscaler(router, spawn, min_replicas=1,
                                 max_replicas=3, hi=0.75, lo=0.25,
                                 cooldown_s=1.0)
        router.attach_autoscaler(scaler)
        rng = np.random.default_rng(23)
        n_new = 6
        prompts = [[int(t) for t in rng.integers(0, cfg.vocab_size, 8)]
                   for _ in range(8)]
        want = []
        for p in prompts:
            ids = np.asarray([p], np.int32)
            out = ref.generate(paddle.to_tensor(ids),
                               max_new_tokens=n_new).numpy()[0, -n_new:]
            want.append([int(t) for t in out])
        freqs = [router.submit(p, max_new_tokens=n_new) for p in prompts]
        # 8 queued requests over 3 slots: utilization >> hi, the pool
        # grows one replica per cooldown window up to the ceiling
        for _ in range(8):
            fake[0] += 1.1
            router.poll()
            if len(router.replicas) == 3:
                break
        assert len(router.replicas) == 3
        assert sum(e["dir"] == "up" for e in scaler.events) == 2
        _drive(router, freqs)
        for f, w in zip(freqs, want):
            assert f.output_tokens == w
        # idle pool: drains back to the floor, one retirement at a time
        for _ in range(64):
            fake[0] += 1.1
            router.poll()
            if (scaler._retiring is None
                    and len(router.replicas) == scaler.min_replicas):
                break
        assert len(router.replicas) == 1
        assert sum(e["dir"] == "down" for e in scaler.events) == 2
        assert len(router.obs.scale_log()) >= 4   # 2 up + 2 down
        scaled = registry.REGISTRY.get("fleet_scale_events_total")
        assert scaled.value(direction="up") - ups0 == 2
        assert scaled.value(direction="down") - downs0 == 2


# ---------------------------------------------------------------- HTTP API
class TestFleetHTTP:
    def test_fleet_server_roundtrip_drain_and_shed(self):
        cfg, router = _fleet(2)
        _, ref = _model()
        srv = FleetServer(router, port=0)
        old = _flags.get_flag("serving_max_queue")
        try:
            rng = np.random.default_rng(4)
            prompt = [int(t) for t in rng.integers(0, cfg.vocab_size, 5)]
            body = json.dumps({"prompt": prompt,
                               "max_new_tokens": 4}).encode()
            req = urllib.request.Request(
                srv.url() + "/generate", data=body,
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=120) as resp:
                assert resp.status == 200
                out = json.loads(resp.read())
            ids = np.asarray([prompt], np.int32)
            want = ref.generate(paddle.to_tensor(ids),
                                max_new_tokens=4).numpy()[0, -4:]
            assert out["output_tokens"] == [int(t) for t in want]
            assert out["finish_reason"] == "length"
            assert out["fleet"] == {"redispatches": 0, "hedged": False}

            with urllib.request.urlopen(srv.url() + "/healthz",
                                        timeout=30) as resp:
                assert resp.status == 200
                health = json.loads(resp.read())
            assert health["ok"] is True
            assert set(health["replicas"]) == {"replica-0", "replica-1"}
            with urllib.request.urlopen(srv.url() + "/stats",
                                        timeout=30) as resp:
                st = json.loads(resp.read())
            assert set(st["replicas"]) == {"replica-0", "replica-1"}

            # rolling-restart drain over the wire
            drain = urllib.request.Request(
                srv.url() + "/drain",
                data=json.dumps({"replica": "replica-0"}).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(drain, timeout=30) as resp:
                assert json.loads(resp.read())["status"] == "draining"
            with urllib.request.urlopen(srv.url() + "/healthz",
                                        timeout=30) as resp:
                health = json.loads(resp.read())
            assert health["replicas"]["replica-0"]["status"] == "draining"
            assert health["ok"] is True  # replica-1 still takes traffic
            resume = urllib.request.Request(
                srv.url() + "/resume",
                data=json.dumps({"replica": "replica-0"}).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(resume, timeout=30) as resp:
                assert json.loads(resp.read())["status"] == "ok"
            bad = urllib.request.Request(
                srv.url() + "/drain",
                data=json.dumps({"replica": "nope"}).encode(),
                headers={"Content-Type": "application/json"})
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(bad, timeout=30)
            assert ei.value.code == 404

            # fleet-wide shed: pause both replica loops (alive + leased,
            # just not draining their queues) and fill every queue
            _flags.set_flags({"serving_max_queue": 1})
            for rep in router.replicas.values():
                rep.pause()
            fillers = [router.submit([1, 2, 3], max_new_tokens=2),
                       router.submit([4, 5, 6], max_new_tokens=2)]
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(req, timeout=30)
            assert ei.value.code == 503
            assert int(ei.value.headers["Retry-After"]) >= 1
            assert json.loads(ei.value.read())["retry_after_s"] > 0
            for rep in router.replicas.values():
                rep.unpause()
            assert all(f.wait(timeout=120) for f in fillers)
        finally:
            _flags.set_flags({"serving_max_queue": old})
            srv.stop()


# ------------------------------------------------- fleet distributed tracing
class TestFleetTracing:
    """r19: trace-context propagation (attempt/cause tags on every span),
    cross-replica merged chrome traces, and attempt-attributed SLOs.
    Fake-clock, unstarted routers throughout — failure detection and
    hedging are deterministic, so the assertions are on tags and counts,
    never durations."""

    @pytest.fixture(autouse=True)
    def _traced(self):
        reset_all()
        _flags.set_flags({"metrics": "on"})
        yield
        _flags.set_flags({"metrics": "off"})
        reset_all()

    def test_redispatch_exports_one_merged_trace(self, tmp_path):
        fake = [0.0]
        cfg, router = _fleet(2, clock=lambda: fake[0], lease_ttl_s=1000.0)
        rng = np.random.default_rng(7)
        prompt = [int(t) for t in rng.integers(0, cfg.vocab_size, 8)]
        red0 = registry.REGISTRY.get(
            "fleet_requests_redispatched_total").total()
        freq = router.submit(prompt, max_new_tokens=6)
        assert freq.attempts[0].replica.rid == "replica-0"
        # the engine placement carries the router's trace context
        assert freq.attempts[0].req.trace_ctx == {
            "fleet_request_id": freq.request_id,
            "attempt": 0, "cause": "primary"}
        for _ in range(3):               # partial progress, then crash
            router.replicas["replica-0"].engine.step()
        router.kill_replica("replica-0")
        router.poll()                    # detect + re-dispatch
        (live,) = freq.live_attempts()
        assert live.kind == "redispatch" and live.index == 1
        assert live.req.trace_ctx == {
            "fleet_request_id": freq.request_id,
            "attempt": 1, "cause": "redispatch"}
        _drive(router, [freq])
        assert freq.finish_reason == "length"

        # ONE merged chrome trace: a lane per replica, attempt/cause on
        # every replica-lane span, dead attempt marked cancelled
        payload = router.obs.trace_payload(freq.request_id)
        assert payload is not None
        evs = payload["traceEvents"]
        # attempt count in the trace matches the re-dispatch counter
        reds = registry.REGISTRY.get(
            "fleet_requests_redispatched_total").total() - red0
        tags = {(e["args"]["attempt"], e["args"]["cause"])
                for e in evs if e.get("ph") == "X" and e["pid"] != 0}
        assert tags == {(0, "primary"), (1, "redispatch")}
        assert len(tags) == 1 + reds == len(freq.attempts)
        # both replicas contribute a process lane + the router lane
        lanes = {e["pid"] for e in evs if e.get("ph") == "X"}
        assert lanes == {0, 1, 2}
        # the dead primary's spans are all flagged cancelled; the
        # winner's never are
        for e in evs:
            if e.get("ph") != "X" or e["pid"] == 0:
                continue
            if e["args"]["cause"] == "primary":
                assert e["args"]["cancelled"] is True
            else:
                assert "cancelled" not in e["args"]
        # router lane recorded the route decision (probe results) for
        # both placements and the queue-at-router wait for the orphan
        router_spans = [e["name"] for e in evs
                        if e.get("ph") == "X" and e["pid"] == 0]
        assert router_spans.count("fleet.route") == 2
        assert "fleet.queue" in router_spans
        route = [e for e in evs if e["name"] == "fleet.route"][0]
        assert {p["replica"] for p in route["args"]["probes"]} \
            <= {"replica-0", "replica-1"}
        # single contiguous waterfall: covered wall time + no orphans
        assert coverage_of(evs) >= 0.99
        assert unparented_spans(evs, freq.request_id) == []
        # export round-trips through the file API too
        p = str(tmp_path / "fleet_trace.json")
        export_fleet_trace(router, freq.request_id, p)
        with open(p) as f:
            assert json.load(f)["traceEvents"]

    def test_hedge_exports_one_merged_trace_with_cancelled_arm(self):
        fake = [0.0]
        cfg, router = _fleet(2, clock=lambda: fake[0], lease_ttl_s=1000.0,
                             hedge_ttft_ms=50.0)
        rng = np.random.default_rng(8)
        prompt = [int(t) for t in rng.integers(0, cfg.vocab_size, 6)]
        hed0 = registry.REGISTRY.get("fleet_requests_hedged_total").total()
        freq = router.submit(prompt, max_new_tokens=6)
        r0 = router.replicas["replica-0"].engine
        r0.step()                        # admitted, no first token yet
        fake[0] = 0.1                    # past the 50ms deadline
        router.poll()
        assert freq.hedged
        assert freq.attempts[1].req.trace_ctx == {
            "fleet_request_id": freq.request_id,
            "attempt": 1, "cause": "hedge"}
        # only the hedge arm progresses: the primary is hung
        r1 = router.replicas["replica-1"].engine
        for _ in range(2000):
            if freq.done:
                break
            if r1.sched.has_work():
                r1.step()
            router.poll()
        assert freq.done

        payload = router.obs.trace_payload(freq.request_id)
        evs = payload["traceEvents"]
        heds = registry.REGISTRY.get(
            "fleet_requests_hedged_total").total() - hed0
        tags = {(e["args"]["attempt"], e["args"]["cause"])
                for e in evs if e.get("ph") == "X" and e["pid"] != 0}
        assert tags == {(0, "primary"), (1, "hedge")}
        assert len(tags) == 1 + heds == len(freq.attempts)
        # the losing arm is in the trace, marked cancelled
        primary = [e for e in evs if e.get("ph") == "X" and e["pid"] != 0
                   and e["args"]["cause"] == "primary"]
        assert primary and all(e["args"]["cancelled"] is True
                               for e in primary)
        names = {e["name"] for e in evs}
        assert {"fleet.hedge_fire", "fleet.hedge_win",
                "fleet.hedge_cancel"} <= names
        assert coverage_of(evs) >= 0.99
        assert unparented_spans(evs, freq.request_id) == []

    def test_attempt_attributed_slos_and_rollups(self):
        fake = [0.0]
        cfg, router = _fleet(2, clock=lambda: fake[0], lease_ttl_s=1000.0)
        rng = np.random.default_rng(9)
        prompts = [[int(t) for t in rng.integers(0, cfg.vocab_size, 6)]
                   for _ in range(2)]
        freqs = [router.submit(p, max_new_tokens=4) for p in prompts]
        _drive(router, freqs)
        ttft = registry.REGISTRY.get("fleet_attempt_ttft_seconds")
        e2e = registry.REGISTRY.get("fleet_attempt_e2e_seconds")
        # one primary attempt per replica (load-balanced), cause-labeled
        for rid in ("replica-0", "replica-1"):
            assert ttft.stats(tier="default", replica=rid,
                              cause="primary")["count"] == 1
            assert e2e.stats(tier="default", replica=rid,
                             cause="primary")["count"] == 1
        # fleet-level rollups merge every {tier,replica,cause} row
        roll = router.obs.publish_rollups()
        assert {"route", "queue", "ttft", "e2e"} <= set(roll)
        assert roll["ttft"]["p50"] <= roll["ttft"]["p99"]
        g = registry.REGISTRY.get("fleet_slo_seconds")
        assert g.value(metric="ttft", quantile="p99") == \
            pytest.approx(roll["ttft"]["p99"])
        # settled ring answers trace_payload after the fact
        for f in freqs:
            assert router.obs.trace_payload(f.request_id) is not None
        assert router.obs.trace_payload("no-such-id") is None

    def test_breaker_transitions_become_events(self):
        fake = [0.0]
        cfg, router = _fleet(2, clock=lambda: fake[0], lease_ttl_s=1000.0,
                             breaker_errors=2, breaker_cooldown_s=5.0)
        r0 = router.replicas["replica-0"]
        real_submit = r0.engine.submit

        def bad_submit(*a, **kw):
            raise RuntimeError("injected submit fault")

        r0.engine.submit = bad_submit
        router.submit([1, 2, 3], max_new_tokens=2)
        router.submit([4, 5, 6], max_new_tokens=2)
        assert r0.breaker.state == "open"
        fake[0] = 5.0                    # open -> half_open (time-derived)
        router.poll()
        r0.engine.submit = real_submit
        router.submit([7, 8, 9], max_new_tokens=2)   # probe heals
        states = [(t["replica"], t["from"], t["to"])
                  for t in router.obs._breaker_log]
        assert ("replica-0", "closed", "open") in states
        assert ("replica-0", "open", "half_open") in states
        assert ("replica-0", "half_open", "closed") in states

    @pytest.mark.parametrize("kind,field,value,ticks", [
        ("hedge_rate_spike", "hedge_rate", 0.5, 1),
        ("redispatch_storm", "redispatch_rate", 0.5, 1),
        ("breaker_flap", "breaker_flaps", 4.0, 1),
        ("replica_skew", "ttft_skew", 5.0, 3),    # sustained: patience 3
    ])
    def test_fleet_detector_fires_on_its_signal_and_dumps_router_state(
            self, tmp_path, kind, field, value, ticks):
        """Each fleet detector stays silent on a clean fleet, fires on its
        own signal through the seam tick() feeds, and the flight dump it
        triggers embeds the router's state and the settled requests'
        merged traces, written atomically."""
        import glob

        _flags.set_flags({"fleet_anomaly": "on",
                          "metrics_dir": str(tmp_path)})
        try:
            cfg, router = _fleet(2, clock=lambda: 0.0, lease_ttl_s=1000.0)
            freq = router.submit([1, 2, 3, 4], max_new_tokens=3)
            _drive(router, [freq])          # a settled trace for the dump
            clean = {"kind": "fleet_tick", "hedge_rate": 0.0,
                     "redispatch_rate": 0.0, "breaker_flaps": 0.0,
                     "ttft_skew": 1.0}
            fired = []
            for s in range(8):
                fired += router.obs.observe_record(dict(clean, step=s))
            assert fired == [] and router.obs.dumps == []
            for s in range(8, 8 + ticks):
                fired += router.obs.observe_record(
                    dict(clean, step=s, **{field: value}))
            assert [e["kind"] for e in fired] == [kind]
            (path,) = router.obs.dumps
            with open(path) as f:
                payload = json.load(f)
            assert payload["anomaly"]["kind"] == kind
            state = next(iter(payload["router"]["replicas"].values()))
            assert {"breaker", "load", "lease_age_s"} <= set(state)
            assert any(r.get("trace") for r in payload["fleet_requests"])
            assert not glob.glob(os.path.join(os.path.dirname(path),
                                              "*.tmp"))
        finally:
            _flags.set_flags({"fleet_anomaly": "auto", "metrics_dir": ""})

    def test_fleet_server_trace_endpoint(self):
        cfg, router = _fleet(2)
        srv = FleetServer(router, port=0)
        try:
            rng = np.random.default_rng(10)
            prompt = [int(t) for t in rng.integers(0, cfg.vocab_size, 5)]
            body = json.dumps({"prompt": prompt,
                               "max_new_tokens": 3}).encode()
            req = urllib.request.Request(
                srv.url() + "/generate", data=body,
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=120) as resp:
                rid = json.loads(resp.read())["request_id"]
            with urllib.request.urlopen(
                    srv.url() + f"/trace?id={rid}", timeout=30) as resp:
                assert resp.status == 200
                tr = json.loads(resp.read())
            assert tr["displayTimeUnit"] == "ms"
            assert unparented_spans(tr["traceEvents"], rid) == []
            assert any(e["name"] == "fleet.route"
                       for e in tr["traceEvents"])
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(srv.url() + "/trace?id=nope",
                                       timeout=30)
            assert ei.value.code == 404
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(srv.url() + "/trace", timeout=30)
            assert ei.value.code == 400
            # /metrics surfaces the fleet SLO rollup gauges
            with urllib.request.urlopen(srv.url() + "/metrics",
                                        timeout=30) as resp:
                text = resp.read().decode()
            assert "fleet_slo_seconds" in text
            assert "fleet_attempt_e2e_seconds" in text
        finally:
            srv.stop()
