"""Process-granularity fleet replicas: real OS processes under a supervisor.

The thread-backed fleet (serving/fleet.py) proves the routing/failover
logic but simulates every fault — kill() is a flag, a "dead" replica's
Python objects are still reachable. This module promotes one replica to a
real subprocess and supervises it the way an agent supervises a pod:

  * ProcessReplica launches ``python -m paddle_tpu.serving.fleet_proc``
    as a child: the child builds its own model + ServingEngine, binds a
    ServingServer on an ephemeral port, prints ONE ready line
    ``{"ready": true, "port": P, "pid": Q}`` and then heartbeats a
    per-incarnation lease into the shared TCPStore.
  * The router speaks to the child over its existing HTTP surface —
    _RemoteEngine/_RemoteRequest duck-type the ServingEngine/Request
    attributes FleetRouter and fleet_observability actually touch, so
    process replicas ride the exact same ``_place()`` path as threads
    (re-dispatch stays bitwise for greedy: the survivor replays the full
    prompt).
  * Death is detected two ways, matching two distinct fault classes:
    waitpid/exit-code for crashes (SIGKILL, OOM, bugs) and heartbeat-
    lease expiry for silent processes (SIGSTOP, network partition). A
    silent-but-alive child gets a heal grace window — a partition that
    heals before the respawn deadline revives the incarnation with NO
    respawn and NO fence bump.
  * Respawn uses resilience/retry.RetryPolicy pacing (capped exponential
    backoff + deterministic jitter, ProcessReplicaSpec.respawn_max attempts)
    and gates routing on a warm-up probe: the new incarnation is
    ``warming`` (unroutable, not dead) until /healthz says ok.
  * Every incarnation is stamped with a monotonically increasing fence
    token (a store counter bumped before each spawn). The child re-reads
    the counter on every heartbeat and ``os._exit(FENCED_EXIT)``s the
    moment it is superseded — a SIGSTOP'd zombie that wakes after its
    replacement spawned can never serve stale state (satellite: the
    zombie-fencing test drives exactly this SIGSTOP -> lease death ->
    respawn -> SIGCONT -> fence-exit sequence).

Supervisor-side state lives in ProcessReplica.supervise(), called from
every FleetRouter.poll() — the router stays the single supervision loop
for threads and processes alike.
"""
from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request
from typing import List, Optional, Tuple

from ..observability.registry import counter as _counter
from ..resilience.retry import RetryPolicy
from .engine import EngineDrainingError, QueueFullError
from .fleet import Replica

_RESPAWNS = _counter("fleet_replica_respawns_total",
                     "Process-replica incarnations respawned by the "
                     "supervisor, per replica id.",
                     labelnames=("replica",), always=True)
_FENCED = _counter("fleet_replica_fenced_total",
                   "Zombie incarnations that self-fenced (woke up already "
                   "superseded and exited rather than serve stale state).",
                   always=True)

# the child's self-fence exit code: distinguishable from crashes in
# last_exit and asserted by the zombie-fencing test
FENCED_EXIT = 43

_remote_lock = threading.Lock()
_remote_counter = 0


def _next_remote_id() -> str:
    global _remote_counter
    with _remote_lock:
        _remote_counter += 1
        return f"proc-{_remote_counter}"


def demo_model():
    """Seeded tiny-GPT factory for process replicas (importable by the
    child as ``paddle_tpu.serving.fleet_proc:demo_model``). Seeded like
    tests/test_fleet.py's _model(): every incarnation and every replica
    is bitwise-interchangeable, the property re-dispatch parity rests on."""
    import paddle_tpu as paddle
    from ..models import GPTConfig, GPTForCausalLM

    paddle.seed(11)
    cfg = GPTConfig.tiny()
    m = GPTForCausalLM(cfg)
    m.eval()
    return m


# ---------------------------------------------------------------------------
# remote engine: the router-facing duck type over the child's HTTP surface
# ---------------------------------------------------------------------------

class _RemoteRequest:
    """Client-side mirror of one generation request running in the child.
    Duck-types the serving.scheduler.Request attributes the router and
    fleet_observability touch: identity, token/state snapshots, lifecycle
    timestamps (this process's monotonic clock) and telemetry (the
    child's own telemetry block rides back on the final stream line)."""

    def __init__(self, prompt: List[int], max_new_tokens: int,
                 temperature: float, eos_token_id, request_id: Optional[str],
                 tier: str, trace_ctx: Optional[dict]):
        self.request_id = request_id or _next_remote_id()
        self.prompt = [int(t) for t in prompt]
        self.max_new_tokens = int(max_new_tokens)
        self.temperature = float(temperature)
        self.eos_token_id = eos_token_id
        self.tier = str(tier) if tier else "default"
        self.trace = None               # engine-side spans stay in the child
        self.trace_ctx = dict(trace_ctx) if trace_ctx else None
        self.state = "queued"
        self.finish_reason: Optional[str] = None
        self.output_tokens: List[int] = []
        self.prefix_matched = 0
        self.arrival_time = time.monotonic()
        self.prefill_start: Optional[float] = None
        self.first_token_time: Optional[float] = None
        self.finish_time: Optional[float] = None
        self._remote_telemetry: Optional[dict] = None
        self._cancelled = False
        self._resp = None               # live HTTP response (stream)
        self._lock = threading.Lock()
        self._done = threading.Event()

    def wait(self, timeout: Optional[float] = None) -> bool:
        return self._done.wait(timeout)

    def queue_seconds(self) -> Optional[float]:
        if self.prefill_start is None:
            return None
        return self.prefill_start - self.arrival_time

    def ttft_seconds(self) -> Optional[float]:
        if self.first_token_time is None:
            return None
        return self.first_token_time - self.arrival_time

    def decode_tokens_per_s(self) -> Optional[float]:
        if self.finish_time is None or self.first_token_time is None:
            return None
        n = len(self.output_tokens)
        dt = self.finish_time - self.first_token_time
        return (n - 1) / dt if n > 1 and dt > 0 else None

    def telemetry(self) -> dict:
        with self._lock:
            remote = dict(self._remote_telemetry or {})
        t = {
            "request_id": self.request_id,
            "tier": self.tier,
            "state": self.state,
            "finish_reason": self.finish_reason,
            "prompt_tokens": len(self.prompt),
            "prefix_matched_tokens": self.prefix_matched,
            "output_tokens": len(self.output_tokens),
            "queue_s": self.queue_seconds(),
            "ttft_s": self.ttft_seconds(),
            "decode_tok_s": self.decode_tokens_per_s(),
        }
        # the child's telemetry is the authoritative engine view (its
        # queue/prefix numbers); keep the router-side identity fields
        for k, v in remote.items():
            if k not in ("request_id", "tier", "state", "finish_reason"):
                t[k] = v
        return t


class _RemoteObs:
    """`engine.obs` facade: health_snapshot proxies the child /healthz."""

    def __init__(self, engine: "_RemoteEngine"):
        self._engine = engine

    def health_snapshot(self, loop_alive: bool = True) -> dict:
        snap = self._engine._get_json("/healthz", ok_codes=(200, 503))
        if snap is None:
            snap = {"ok": False, "status": "unreachable"}
        snap["loop_alive"] = bool(loop_alive) and bool(snap.get("ok"))
        snap["remote"] = True
        return snap


class _RemoteEngine:
    """ServingEngine duck type over one child incarnation's HTTP surface.
    submit() opens a streaming POST /generate and a daemon reader thread
    feeds the _RemoteRequest; cancel() severs the stream socket, which
    the child's server turns into an engine-side disconnect-cancel (slot
    and KV reservation freed). One _RemoteEngine per incarnation — after
    a respawn the replica swaps in a fresh one and requests still bound
    to the dead incarnation fail out and re-dispatch."""

    _HTTP_TIMEOUT_S = 5.0

    def __init__(self, base_url: Optional[str]):
        self.base_url = base_url        # None: incarnation not up yet
        self.obs = _RemoteObs(self)
        self._draining = False
        self._inflight = 0
        self._lock = threading.Lock()

    # -- shared HTTP helpers ------------------------------------------------
    def _get_json(self, path: str, ok_codes=(200,),
                  timeout: Optional[float] = None) -> Optional[dict]:
        if self.base_url is None:
            return None
        try:
            with urllib.request.urlopen(
                    self.base_url + path,
                    timeout=timeout or self._HTTP_TIMEOUT_S) as resp:
                return json.loads(resp.read().decode())
        except urllib.error.HTTPError as e:
            if e.code in ok_codes:
                try:
                    return json.loads(e.read().decode())
                except Exception:  # noqa: BLE001 — torn body
                    return None
            return None
        except Exception:  # noqa: BLE001 — dead/frozen child
            return None

    # -- engine surface used by the router ----------------------------------
    def submit(self, prompt: List[int], max_new_tokens: int = 16,
               temperature: float = 0.0, eos_token_id=None,
               request_id: Optional[str] = None, tier: str = "default",
               trace_ctx: Optional[dict] = None,
               prefill_only: bool = False) -> _RemoteRequest:
        if self.base_url is None:
            raise RuntimeError("replica incarnation not ready")
        if self._draining:
            raise EngineDrainingError()
        req = _RemoteRequest(prompt, max_new_tokens, temperature,
                             eos_token_id, request_id, tier, trace_ctx)
        body = json.dumps({
            "prompt": req.prompt, "max_new_tokens": req.max_new_tokens,
            "temperature": req.temperature, "eos_token_id": req.eos_token_id,
            "tier": req.tier, "stream": True,
            "prefill_only": bool(prefill_only),
        }).encode()
        http_req = urllib.request.Request(
            self.base_url + "/generate", data=body,
            headers={"Content-Type": "application/json"})
        try:
            resp = urllib.request.urlopen(http_req,
                                          timeout=self._HTTP_TIMEOUT_S)
        except urllib.error.HTTPError as e:
            try:
                detail = json.loads(e.read().decode())
            except Exception:  # noqa: BLE001
                detail = {}
            if e.code == 503:
                raise QueueFullError(int(detail.get("queue_depth", 0)),
                                     int(detail.get("queue_limit", 0)))
            if e.code == 400:
                raise ValueError(detail.get("error", "bad request"))
            raise RuntimeError(f"remote submit: HTTP {e.code}")
        except OSError as e:
            # dead/unreachable child between placement and submit: a
            # replica fault the router's _place turns into the next
            # candidate (or a re-dispatch), never a caller-visible
            # transport exception
            raise RuntimeError(f"remote submit failed: {e}")
        req._resp = resp
        req.state = "running"
        req.prefill_start = time.monotonic()
        with self._lock:
            self._inflight += 1
        threading.Thread(target=self._consume, args=(req, resp),
                         name="fleet-proc-stream", daemon=True).start()
        return req

    def _consume(self, req: _RemoteRequest, resp) -> None:
        """Reader thread: one NDJSON line per child flush. Any transport
        fault marks the request finished with a non-good reason, which
        the router's settle pass turns into a failed attempt -> the
        request re-dispatches even when the replica itself is judged
        alive (e.g. the child restarted between placement and finish)."""
        reason = "error"
        telemetry = None
        try:
            while True:
                line = resp.readline()
                if not line:
                    break
                line = line.strip()
                if not line:
                    continue
                msg = json.loads(line.decode())
                with req._lock:
                    toks = msg.get("tokens")
                    if toks:
                        if req.first_token_time is None:
                            req.first_token_time = time.monotonic()
                        req.output_tokens.extend(int(t) for t in toks)
                    if msg.get("done"):
                        reason = msg.get("finish_reason") or "stop"
                        telemetry = msg.get("telemetry")
                        break
        except Exception:  # noqa: BLE001 — severed stream
            pass
        finally:
            try:
                resp.close()
            except Exception:  # noqa: BLE001
                pass
            with self._lock:
                self._inflight = max(0, self._inflight - 1)
            with req._lock:
                if req._cancelled and reason == "error":
                    reason = "cancelled"
                if req.finish_reason is None:
                    req.finish_reason = reason
                if telemetry:
                    req._remote_telemetry = telemetry
                req.state = "finished"
                req.finish_time = time.monotonic()
            req._done.set()

    def snapshot_output(self, req: _RemoteRequest
                        ) -> Tuple[List[int], str, Optional[str]]:
        with req._lock:
            return list(req.output_tokens), req.state, req.finish_reason

    def cancel(self, req: _RemoteRequest, reason: str = "cancelled") -> bool:
        with req._lock:
            if req.state == "finished":
                return False
            req._cancelled = True
            req.finish_reason = reason
            resp = req._resp
        # severing the stream socket is the cancel signal: the child's
        # handler sees the broken pipe and engine-cancels the request
        if resp is not None:
            try:
                resp.close()
            except Exception:  # noqa: BLE001
                pass
        return True

    def drain(self) -> None:
        self._draining = True

    def resume(self) -> None:
        self._draining = False

    def drained(self) -> bool:
        with self._lock:
            return self._draining and self._inflight == 0

    def inflight(self) -> int:
        with self._lock:
            return self._inflight

    def stats(self) -> dict:
        s = self._get_json("/stats", timeout=2.0)
        if s is None:
            return {"remote": True, "unreachable": True}
        s["remote"] = True
        return s

    # -- KV-block transfer wire (disagg streaming / live migration) ---------
    def export_kv_blocks(self, tokens: List[int]) -> List[dict]:
        """POST /kv/export on the child; decoded to the same record list
        ServingEngine.export_kv_blocks returns. Best-effort: a dead or
        frozen child exports nothing (the receiver just re-prefils)."""
        if self.base_url is None:
            return []
        from .server import kv_wire_decode

        body = json.dumps({"tokens": [int(t) for t in tokens]}).encode()
        http_req = urllib.request.Request(
            self.base_url + "/kv/export", data=body,
            headers={"Content-Type": "application/json"})
        try:
            with urllib.request.urlopen(
                    http_req, timeout=self._HTTP_TIMEOUT_S) as resp:
                return kv_wire_decode(resp.read())
        except Exception:  # noqa: BLE001 — unreachable child
            return []

    def ingest_kv_blocks(self, records: List[dict]) -> dict:
        """POST /kv/ingest on the child; raises on an unreachable child
        so the router's transfer path falls back to plain re-prefill."""
        if self.base_url is None:
            raise RuntimeError("replica incarnation not ready")
        from .server import kv_wire_encode

        http_req = urllib.request.Request(
            self.base_url + "/kv/ingest", data=kv_wire_encode(records),
            headers={"Content-Type": "application/x-ndjson"})
        with urllib.request.urlopen(
                http_req, timeout=self._HTTP_TIMEOUT_S) as resp:
            return json.loads(resp.read().decode())


# ---------------------------------------------------------------------------
# supervisor side
# ---------------------------------------------------------------------------

class ProcessReplicaSpec:
    """Recipe FleetRouter.__init__ turns into a ProcessReplica (the
    router passes registry/breaker/clock; the spec carries everything
    process-specific). ``child_store_addr`` lets chaos tests route the
    CHILD's store client through a StorePartitionProxy while the
    supervisor keeps its direct connection.

    One process owns a chip: the child inherits the supervisor's
    environment plus ``extra_env`` and uses whatever platform jax finds
    there, so on a TPU host it claims the chip — and fails or hangs if the
    supervisor (or another replica) already holds it. Pass
    ``extra_env={"JAX_PLATFORMS": "cpu"}`` for CPU replicas.

    ``warmup_timeout_s``: seconds a spawned incarnation gets to print its
    ready line AND pass the /healthz warm-up probe before the supervisor
    kills it and tries again. ``respawn_max``: respawn attempts before the
    supervisor gives up and leaves the replica dead (the initial spawn is
    not counted). ``respawn_backoff_s``: base respawn backoff; actual
    delays follow the shared RetryPolicy schedule (exponential, capped at
    8x base, jittered). Doubles as the heal-grace window for a
    silent-but-alive child."""

    def __init__(self, store_addr: Tuple[str, int], *,
                 factory: str = "paddle_tpu.serving.fleet_proc:demo_model",
                 engine_kwargs: Optional[dict] = None,
                 child_store_addr: Optional[Tuple[str, int]] = None,
                 child_heartbeat_s: float = 0.2,
                 warmup_timeout_s: float = 60.0,
                 respawn_max: int = 3,
                 respawn_backoff_s: float = 0.5,
                 python: str = sys.executable,
                 extra_env: Optional[dict] = None):
        self.store_addr = (str(store_addr[0]), int(store_addr[1]))
        self.child_store_addr = (tuple(child_store_addr)
                                 if child_store_addr else self.store_addr)
        self.factory = str(factory)
        self.engine_kwargs = dict(engine_kwargs or {})
        self.child_heartbeat_s = float(child_heartbeat_s)
        self.warmup_timeout_s = float(warmup_timeout_s)
        self.respawn_max = int(respawn_max)
        self.respawn_backoff_s = float(respawn_backoff_s)
        self.python = str(python)
        self.extra_env = dict(extra_env or {})

    def build(self, rid: str, *, registry, heartbeat_s: float, breaker,
              clock=time.monotonic, idle_sleep_s: float = 0.002
              ) -> "ProcessReplica":
        return ProcessReplica(rid, self, registry=registry,
                              heartbeat_s=heartbeat_s, breaker=breaker,
                              clock=clock, idle_sleep_s=idle_sleep_s)


class ProcessReplica(Replica):
    """A fleet replica whose engine lives in a supervised subprocess.

    Lifecycle (all transitions happen in supervise(), which the router
    calls every poll; spawns run in a daemon thread because the child's
    jax import takes seconds and must not stall the monitor):

        spawning -> warming -> ready --(exit / lease death)--> suspect
          ^                                   |                   |
          |                          heal grace (alive +          |
          |                          lease revived: ready,        |
          |                          NO respawn/fence bump)       |
          +--- backoff deadline, fence bump, respawn <------------+

    ``kill()`` SIGKILLs the child (real chaos, supervisor respawns it);
    use ``retire()`` for the thread-replica "dead forever" semantics.
    """

    def __init__(self, rid: str, spec: ProcessReplicaSpec, *, registry,
                 heartbeat_s: float, breaker, clock=time.monotonic,
                 idle_sleep_s: float = 0.002):
        super().__init__(rid, _RemoteEngine(None), registry=registry,
                         heartbeat_s=heartbeat_s, breaker=breaker,
                         clock=clock, idle_sleep_s=idle_sleep_s)
        self.spec = spec
        self.pid = None                 # child pid once ready
        self._proc: Optional[subprocess.Popen] = None
        self._ready = False
        self._stopped = False
        self._exhausted = False
        self._spawning = False
        self._spawn_thread: Optional[threading.Thread] = None
        self._next_spawn_at: Optional[float] = 0.0   # spawn ASAP on start
        self._suspect_deadline: Optional[float] = None
        self._zombies: List[subprocess.Popen] = []   # orphaned incarnations
        self._sup_lock = threading.RLock()
        self._backoff = RetryPolicy(
            base_delay=spec.respawn_backoff_s,
            max_delay=spec.respawn_backoff_s * 8.0,
            multiplier=2.0, jitter=0.5, name=f"respawn-{rid}")

    # -- identity -----------------------------------------------------------
    def _lease_id(self) -> str:
        """Per-incarnation lease id: a zombie beating its OLD lease can
        never refresh the CURRENT incarnation's liveness."""
        return f"{self.rid}@{self.incarnation}"

    def _fence_key(self) -> str:
        return f"{self.registry.prefix}/fence/{self.rid}"

    # -- Replica surface overrides -------------------------------------------
    def start(self):
        # the spawn is asynchronous (child jax import takes seconds);
        # the replica stays `warming` until the warm-up probe passes
        with self._sup_lock:
            if self._stopped or self._proc is not None or self._spawning:
                return
            self._begin_spawn()

    def stop(self):
        with self._sup_lock:
            self._stopped = True
            procs = [p for p in [self._proc] + self._zombies if p is not None]
            self._proc = None
            self._zombies = []
        for p in procs:
            try:
                p.terminate()
            except OSError:
                pass
        deadline = time.monotonic() + 5.0
        for p in procs:
            try:
                p.wait(timeout=max(0.1, deadline - time.monotonic()))
            except (subprocess.TimeoutExpired, OSError):
                try:
                    p.kill()
                    p.wait(timeout=5.0)
                except OSError:
                    pass

    def kill(self):
        """Chaos hook: SIGKILL the live incarnation. Unlike the thread
        replica this is not terminal — the supervisor detects the exit
        and respawns under backoff."""
        with self._sup_lock:
            proc = self._proc
        if proc is not None:
            try:
                os.kill(proc.pid, signal.SIGKILL)
            except OSError:
                pass

    def retire(self):
        """Terminal kill: thread-replica kill() semantics (dead forever,
        no respawn)."""
        self._killed = True
        self.stop()

    def loop_alive(self) -> bool:
        with self._sup_lock:
            return (self._proc is not None and self._proc.poll() is None
                    and self._ready)

    def pause(self):  # pragma: no cover — chaos uses SIGSTOP directly
        raise NotImplementedError(
            "SIGSTOP replica.pid to hang a process replica")

    def dead(self, lease_ttl_s: float) -> bool:
        if self._killed or self._stopped or self._exhausted:
            return True
        with self._sup_lock:
            proc, ready = self._proc, self._ready
        if proc is None:
            # between incarnations (awaiting backoff), mid-spawn, or
            # never started: dead for routing/redispatch purposes
            return True
        if proc.poll() is not None:
            return True
        if not ready:
            return False                # warming: alive, just not routable
        return not self.registry.alive(self._lease_id(), float(lease_ttl_s))

    def warming(self) -> bool:
        return not self._ready and not self._stopped and not self._killed

    # -- routing probes (no remote round trip on the hot path) ---------------
    def load(self) -> int:
        return self.engine.inflight()

    def affinity(self, prompt: List[int]) -> int:
        # probing the child's prefix cache would cost an HTTP round trip
        # per candidate per placement; process replicas bid 0 and win on
        # least-load / id order instead
        return 0

    def queue_depth(self) -> int:
        return self.engine.inflight()

    # -- supervision state machine -------------------------------------------
    def supervise(self, router) -> None:
        now = self._clock()
        with self._sup_lock:
            self._reap_zombies()
            if self._stopped or self._killed or self._exhausted \
                    or self._spawning:
                return
            proc = self._proc
            if proc is None:
                if self._next_spawn_at is not None \
                        and now >= self._next_spawn_at:
                    self._begin_spawn()
                return
            code = proc.poll()
            if code is not None:
                self._on_exit(code, now)
                return
            if not self._ready:
                return
            if self.registry.alive(self._lease_id(), router.lease_ttl_s):
                if self._suspect_deadline is not None:
                    # silent spell healed before the respawn deadline
                    # (partition heal): revive with NO respawn, NO fence
                    self._suspect_deadline = None
                    self._note("fleet_replica_lease_revived",
                               replica=self.rid,
                               incarnation=self.incarnation)
                return
            # alive by waitpid, dead by lease: silent process
            if self._suspect_deadline is None:
                grace = self._backoff.jittered_delay(self.respawns + 1)
                self._suspect_deadline = now + grace
                self._note("fleet_replica_lease_expired", replica=self.rid,
                           incarnation=self.incarnation, pid=proc.pid,
                           heal_grace_s=round(grace, 3))
                return
            if now < self._suspect_deadline:
                return
            # grace over and still silent: orphan the incarnation (do NOT
            # kill it — if it ever wakes it must fence itself out) and
            # respawn under a fresh fence token
            self._suspect_deadline = None
            self._zombies.append(proc)
            self._proc = None
            self._ready = False
            self._record_exit(exit_code=None, reason="lease_expired",
                              pid=proc.pid)
            self._schedule_respawn(now, immediate=True)

    def _reap_zombies(self) -> None:
        """Poll orphaned incarnations (supervision lock held). A zombie
        that woke from SIGSTOP and found itself superseded exits with
        FENCED_EXIT — the proof it never served stale state."""
        for z in list(self._zombies):
            zc = z.poll()
            if zc is None:
                continue
            self._zombies.remove(z)
            if zc == FENCED_EXIT:
                _FENCED.inc()
                self._note("fleet_replica_fenced", replica=self.rid,
                           pid=z.pid, exit_code=zc)
                self.last_exit = dict(self.last_exit or {},
                                      fenced_pid=z.pid)
            else:
                self._note("fleet_replica_zombie_reaped", replica=self.rid,
                           pid=z.pid, exit_code=zc)

    def _on_exit(self, code: int, now: float) -> None:
        """Child exited (waitpid path). Classify, record, schedule."""
        proc, self._proc = self._proc, None
        self._ready = False
        self._suspect_deadline = None
        if code == FENCED_EXIT:
            # a superseded zombie draining out is bookkeeping, not a
            # fault: no respawn churn for it
            _FENCED.inc()
            self._note("fleet_replica_fenced", replica=self.rid,
                       pid=proc.pid if proc else None)
            self.last_exit = dict(self.last_exit or {},
                                  fenced_pid=proc.pid if proc else None)
            return
        self._record_exit(exit_code=code, reason="exit",
                          pid=proc.pid if proc else None)
        self._schedule_respawn(now)

    def _record_exit(self, *, exit_code, reason: str, pid) -> None:
        self.last_exit = {
            "incarnation": self.incarnation,
            "pid": pid,
            "exit_code": exit_code,
            "reason": reason,
        }
        self._note("fleet_replica_dead", replica=self.rid, **self.last_exit)

    def _schedule_respawn(self, now: float, immediate: bool = False) -> None:
        if self.respawns >= self.spec.respawn_max:
            self._exhausted = True
            if self.last_exit is not None:
                self.last_exit["respawn_budget_exhausted"] = True
            self._note("fleet_replica_respawn_exhausted", replica=self.rid,
                       respawns=self.respawns)
            return
        # the heal-grace window already consumed the backoff for the
        # silent-death path; crashes wait it out before respawning
        delay = (0.0 if immediate
                 else self._backoff.jittered_delay(self.respawns + 1))
        self._next_spawn_at = now + delay

    # -- spawn ----------------------------------------------------------------
    def _begin_spawn(self) -> None:
        """Arm a spawn (supervision lock held). The heavy lifting —
        fence bump, fork/exec, ready line, warm-up probe — runs in a
        daemon thread so a multi-second child cold start never stalls
        the router's poll loop."""
        self._spawning = True
        self._next_spawn_at = None
        respawn = self._proc is not None or self.incarnation > 0
        self._spawn_thread = threading.Thread(
            target=self._spawn, args=(respawn,),
            name=f"fleet-spawn-{self.rid}", daemon=True)
        self._spawn_thread.start()

    def _spawn(self, respawn: bool) -> None:
        try:
            # the fence bump is the point of no return for the previous
            # incarnation: from here any survivor of it must self-fence
            fence = int(self.registry.store.add(self._fence_key(), 1))
            if respawn:
                with self._sup_lock:
                    self.respawns += 1
                _RESPAWNS.inc(replica=self.rid)
                self._dump_respawn(fence)
            host, port = self.spec.child_store_addr
            cmd = [
                self.spec.python, "-m", "paddle_tpu.serving.fleet_proc",
                "--replica-id", self.rid,
                "--incarnation", str(fence),
                "--fence", str(fence),
                "--store", f"{host}:{port}",
                "--prefix", self.registry.prefix,
                "--factory", self.spec.factory,
                "--engine-kwargs", json.dumps(self.spec.engine_kwargs),
                "--heartbeat-s", str(self.spec.child_heartbeat_s),
                "--parent-pid", str(os.getpid()),
            ]
            env = dict(os.environ)
            env.update(self.spec.extra_env)
            proc = subprocess.Popen(cmd, stdout=subprocess.PIPE,
                                    stderr=subprocess.DEVNULL, env=env)
            deadline = time.monotonic() + self.spec.warmup_timeout_s
            # a child that hangs before its ready line would park
            # readline forever; the watchdog kills it at the deadline so
            # the pipe EOFs and the spawn fails over to the next attempt
            watchdog = threading.Timer(
                max(0.1, deadline - time.monotonic()), self._reap,
                args=(proc,))
            watchdog.daemon = True
            watchdog.start()
            try:
                ready = self._await_ready(proc, deadline)
            finally:
                watchdog.cancel()
            if ready is None:
                self._spawn_failed(proc, "warmup_timeout", fence)
                return
            engine = _RemoteEngine(f"http://127.0.0.1:{ready['port']}")
            if not self._probe(engine, deadline):
                self._spawn_failed(proc, "warmup_probe_failed", fence)
                return
            with self._sup_lock:
                if self._stopped or self._killed:
                    self._reap(proc)
                    return
                self.incarnation = fence
                self.pid = proc.pid
                self.engine = engine
                self._proc = proc
                self._ready = True
                self._suspect_deadline = None
                self._note("fleet_replica_ready", replica=self.rid,
                           incarnation=fence, pid=proc.pid,
                           port=ready["port"])
        except Exception as e:  # noqa: BLE001 — spawn machinery fault
            with self._sup_lock:
                self._record_exit(exit_code=None,
                                  reason=f"spawn_error: {e}", pid=None)
                self._schedule_respawn(self._clock())
        finally:
            with self._sup_lock:
                self._spawning = False

    def _await_ready(self, proc: subprocess.Popen,
                     deadline: float) -> Optional[dict]:
        """Block (spawn thread only) for the child's single ready line;
        afterwards a drain thread keeps the pipe from filling."""
        line = proc.stdout.readline() if proc.stdout else b""
        while line and time.monotonic() < deadline:
            line = line.strip()
            if line.startswith(b"{"):
                try:
                    msg = json.loads(line.decode())
                except ValueError:
                    msg = {}
                if msg.get("ready"):
                    threading.Thread(target=self._drain_stdout, args=(proc,),
                                     name=f"fleet-drain-{self.rid}",
                                     daemon=True).start()
                    return msg
            line = proc.stdout.readline()
        return None

    @staticmethod
    def _drain_stdout(proc: subprocess.Popen) -> None:
        try:
            while proc.stdout and proc.stdout.read(65536):
                pass
        except Exception:  # noqa: BLE001
            pass

    def _probe(self, engine: _RemoteEngine, deadline: float) -> bool:
        """Warm-up gate: the incarnation takes traffic only once its own
        /healthz agrees it is healthy."""
        while time.monotonic() < deadline:
            if self._stopped:
                return False
            snap = engine._get_json("/healthz", ok_codes=(200, 503),
                                    timeout=2.0)
            if snap is not None and snap.get("ok"):
                return True
            time.sleep(0.1)
        return False

    def _spawn_failed(self, proc: subprocess.Popen, reason: str,
                      fence: int) -> None:
        self._reap(proc)
        with self._sup_lock:
            self._record_exit(exit_code=proc.poll(), reason=reason,
                              pid=proc.pid)
            self._schedule_respawn(self._clock())

    @staticmethod
    def _reap(proc: subprocess.Popen) -> None:
        try:
            proc.kill()
            proc.wait(timeout=5.0)
        except OSError:
            pass

    # -- observability --------------------------------------------------------
    @staticmethod
    def _note(kind: str, **data) -> None:
        try:
            from ..observability.flight_recorder import get_flight_recorder
            get_flight_recorder().note(kind, **data)
        except Exception:  # noqa: BLE001 — observability must not wound
            pass

    def _dump_respawn(self, new_fence: int) -> None:
        """Flight-recorder dump on every respawn, embedding the dead
        incarnation's last recorded state (satellite 3)."""
        try:
            from ..observability.flight_recorder import get_flight_recorder
            get_flight_recorder().dump(
                "fleet_respawn",
                extra={"replica": self.rid,
                       "dead_incarnation": dict(self.last_exit or {}),
                       "new_incarnation": int(new_fence),
                       "respawns_so_far": self.respawns})
        except Exception:  # noqa: BLE001
            pass


def build_process_fleet(n_replicas: int = 2, *, store,
                        store_addr: Tuple[str, int],
                        spec_kwargs: Optional[dict] = None,
                        router_kwargs: Optional[dict] = None):
    """N supervised process replicas behind one FleetRouter sharing
    `store` (a native TCPStore master the caller owns; `store_addr` is
    the endpoint the CHILDREN dial — point it at a chaos proxy to
    partition them). Returns the router unstarted."""
    from .fleet import FleetRouter

    specs = [ProcessReplicaSpec(store_addr, **(spec_kwargs or {}))
             for _ in range(int(n_replicas))]
    kw = dict(router_kwargs or {})
    return FleetRouter(replica_specs=specs, store=store, **kw)


def wait_fleet_ready(router, timeout_s: float = 120.0) -> bool:
    """Poll until every process replica passed its warm-up probe (thread
    replicas count as ready immediately). Drives router.poll() itself so
    it also works on an unstarted router."""
    deadline = time.monotonic() + float(timeout_s)
    while time.monotonic() < deadline:
        router.poll()
        if all(not rep.warming() for rep in router.replicas.values()):
            return True
        time.sleep(0.05)
    return False


# ---------------------------------------------------------------------------
# child side (python -m paddle_tpu.serving.fleet_proc)
# ---------------------------------------------------------------------------

def _load_factory(spec: str):
    mod_name, _, fn_name = spec.rpartition(":")
    if not mod_name:
        raise ValueError(f"factory must be 'module:function', got {spec!r}")
    import importlib

    return getattr(importlib.import_module(mod_name), fn_name)


def _child_main(argv: Optional[List[str]] = None) -> int:
    import argparse

    p = argparse.ArgumentParser(prog="paddle_tpu.serving.fleet_proc")
    p.add_argument("--replica-id", required=True)
    p.add_argument("--incarnation", type=int, required=True)
    p.add_argument("--fence", type=int, required=True)
    p.add_argument("--store", required=True, help="host:port of the "
                   "fleet TCPStore (possibly via a partition proxy)")
    p.add_argument("--prefix", default="/pt/fleet")
    p.add_argument("--factory",
                   default="paddle_tpu.serving.fleet_proc:demo_model")
    p.add_argument("--engine-kwargs", default="{}")
    p.add_argument("--heartbeat-s", type=float, default=0.2)
    p.add_argument("--parent-pid", type=int, default=0)
    args = p.parse_args(argv)

    from .. import native
    from ..distributed.env import ReplicaRegistry
    from .engine import ServingEngine
    from .server import ServingServer

    host, _, port = args.store.rpartition(":")
    store = native.TCPStore(host, int(port), is_master=False, world_size=1,
                            timeout_s=30.0)
    registry = ReplicaRegistry(store, prefix=args.prefix)
    lease = f"{args.replica_id}@{args.incarnation}"
    fence_key = f"{args.prefix}/fence/{args.replica_id}"

    # refuse to even build the model when already superseded (a spawn
    # that lost a race with a faster supervisor decision)
    if int(store.add(fence_key, 0)) != args.fence:
        return FENCED_EXIT

    model = _load_factory(args.factory)()
    engine = ServingEngine(model, **json.loads(args.engine_kwargs))
    srv = ServingServer(engine, port=0)

    stop = threading.Event()
    for sig in (signal.SIGTERM, signal.SIGINT):
        signal.signal(sig, lambda *_: stop.set())

    print(json.dumps({"ready": True, "port": srv.port, "pid": os.getpid()}),
          flush=True)

    while not stop.is_set():
        # fence check FIRST: a zombie waking from SIGSTOP must exit
        # before it heartbeats or serves anything (os._exit: no atexit,
        # no socket flush — the process is gone like it was never woken)
        if int(store.add(fence_key, 0)) != args.fence:
            os._exit(FENCED_EXIT)
        if args.parent_pid and os.getppid() != args.parent_pid:
            break                        # supervisor died: no orphans
        registry.heartbeat(lease)
        stop.wait(args.heartbeat_s)

    srv.stop()
    try:
        store.close()
    except Exception:  # noqa: BLE001
        pass
    return 0


if __name__ == "__main__":
    sys.exit(_child_main())
