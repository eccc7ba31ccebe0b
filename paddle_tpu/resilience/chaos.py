"""Fault-injection harness ("chaos monkey") for the resilience subsystem.

Production code calls `crash_point("name")` at carefully chosen spots in
checkpoint writes and file commits; tests arm those
points with `inject_crash(...)` to simulate a process dying mid-save. The
harness also poisons training batches with NaNs (to exercise the compiled
NaN step-guard), kills DataLoader worker processes, and delivers fake
preemption signals — the machinery that lets tier-1 tests PROVE the
crash-consistency and auto-resume claims instead of asserting them.

Pure stdlib: imported by framework/io.py and forked workers; must not pull
in jax.
"""
from __future__ import annotations

import os
import signal as _signal
import threading
from typing import Dict, Iterable, Optional

__all__ = [
    "InjectedCrash", "inject_crash", "crash_point", "clear", "armed",
    "poison_steps", "should_poison", "note_poisoned", "kill_worker",
    "fake_preemption", "stats", "scope",
    "kill_rank", "should_kill_rank", "note_rank_killed",
    "slow_rank", "rank_delay",
    "StorePartitionProxy",
]


class InjectedCrash(RuntimeError):
    """Raised at an armed crash point; simulates the process dying there."""

    def __init__(self, point: str):
        super().__init__(f"injected crash at {point!r}")
        self.point = point


_lock = threading.Lock()
_crash_points: Dict[str, dict] = {}   # name -> {"after": int, "mode": str}
_poison_steps: set = set()
_rank_kills: Dict[int, int] = {}      # member id -> kill at global step
_rank_delays: Dict[int, float] = {}   # member id -> extra seconds per step

stats = {
    "crashes_injected": 0,
    "steps_poisoned": 0,
    "workers_killed": 0,
    "signals_sent": 0,
    "ranks_killed": 0,
    "partitions_started": 0,
}


def clear():
    """Disarm every crash point and poison schedule (stats are kept)."""
    with _lock:
        _crash_points.clear()
        _poison_steps.clear()
        _rank_kills.clear()
        _rank_delays.clear()


def armed(point: Optional[str] = None) -> bool:
    with _lock:
        if point is None:
            return bool(_crash_points)
        return point in _crash_points


def inject_crash(point: str, after: int = 0, mode: str = "raise"):
    """Arm `point`: the (after+1)-th hit fires. mode="raise" raises
    InjectedCrash (in-process crash simulation — the write path genuinely
    stops mid-flight); mode="exit" calls os._exit(23) for subprocess tests
    where not even finally-blocks may run."""
    if mode not in ("raise", "exit"):
        raise ValueError(f"unknown crash mode {mode!r}")
    with _lock:
        _crash_points[point] = {"after": int(after), "mode": mode}


def crash_point(name: str):
    """Instrumentation hook called by production code. No-op unless armed."""
    with _lock:
        entry = _crash_points.get(name)
        if entry is None:
            return
        if entry["after"] > 0:
            entry["after"] -= 1
            return
        del _crash_points[name]  # one-shot: the "process" died here once
        mode = entry["mode"]
        stats["crashes_injected"] += 1
    if mode == "exit":  # pragma: no cover — used by subprocess tests only
        os._exit(23)
    raise InjectedCrash(name)


# -- NaN poisoning ----------------------------------------------------------

def poison_steps(steps: Iterable[int]):
    """Schedule global step indices whose batch gets a NaN injected (the
    ResilientTrainer consults this before each compiled step)."""
    with _lock:
        _poison_steps.update(int(s) for s in steps)


def should_poison(step: int) -> bool:
    with _lock:
        return int(step) in _poison_steps


def note_poisoned(step: int):
    with _lock:
        _poison_steps.discard(int(step))
        stats["steps_poisoned"] += 1


# -- elastic rank faults ----------------------------------------------------

def kill_rank(member: int, at_step: int):
    """Arm a rank kill: the elastic trainer checks should_kill_rank() at
    the top of each global step and, once armed-and-reached, the member
    stops heartbeating and exits its loop WITHOUT a left marker — from the
    survivors' perspective an unannounced crash whose lease expires."""
    with _lock:
        _rank_kills[int(member)] = int(at_step)


def should_kill_rank(member: int, step: int) -> bool:
    with _lock:
        at = _rank_kills.get(int(member))
        return at is not None and int(step) >= at


def note_rank_killed(member: int):
    """The member died; disarm its kill (one-shot) and count it."""
    with _lock:
        _rank_kills.pop(int(member), None)
        stats["ranks_killed"] += 1


def slow_rank(member: int, delay_s: float):
    """Arm a per-step straggler delay for one member (rank_delay() is
    added to its step wall time by the elastic trainer) — exercises the
    micro-batch rebalancer without ejecting anyone. delay_s <= 0 disarms."""
    with _lock:
        if float(delay_s) <= 0:
            _rank_delays.pop(int(member), None)
        else:
            _rank_delays[int(member)] = float(delay_s)


def rank_delay(member: int) -> float:
    with _lock:
        return _rank_delays.get(int(member), 0.0)


# -- process-level faults ---------------------------------------------------

def kill_worker(pool, wid: int = 0, sig: int = _signal.SIGKILL):
    """Hard-kill one DataLoader worker process (io/worker.py WorkerPool)."""
    proc = pool.procs[wid]
    os.kill(proc.pid, sig)
    stats["workers_killed"] += 1


def fake_preemption(sig: int = _signal.SIGTERM):
    """Deliver a real signal to this process — exercises the installed
    PreemptionHandler exactly like a TPU maintenance-event SIGTERM."""
    stats["signals_sent"] += 1
    os.kill(os.getpid(), sig)


class StorePartitionProxy:
    """Network-partition shim for one store member: a real TCP forwarding
    proxy a victim's TCPStore client connects THROUGH, so its store
    traffic can be stalled (held, delivered after heal — the classic
    partition) or dropped (connections severed) for a window without
    touching the process itself. Lease expiry and the supervisor's
    heal-without-respawn grace path get exercised with everyone alive.

    Pure stdlib sockets + threads; forwarding is byte-level so it works
    for any store protocol."""

    def __init__(self, upstream_host: str, upstream_port: int,
                 listen_host: str = "127.0.0.1"):
        import socket

        self.upstream = (str(upstream_host), int(upstream_port))
        self._gate = threading.Event()   # set = traffic flows
        self._gate.set()
        self._mode = "stall"
        self._open = True
        self._conns = []                 # live socket pairs, for drop mode
        self._conns_lock = threading.Lock()
        self._srv = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._srv.bind((listen_host, 0))
        self._srv.listen(16)
        self.host, self.port = self._srv.getsockname()[:2]
        self._accept_thread = threading.Thread(
            target=self._accept_loop, name="chaos-partition-accept",
            daemon=True)
        self._accept_thread.start()

    # -- forwarding ---------------------------------------------------------
    def _accept_loop(self):
        import socket

        while self._open:
            try:
                cli, _ = self._srv.accept()
            except OSError:
                return
            if not self._open:
                cli.close()
                return
            try:
                up = socket.create_connection(self.upstream, timeout=10)
            except OSError:
                cli.close()
                continue
            with self._conns_lock:
                self._conns.append((cli, up))
            for a, b in ((cli, up), (up, cli)):
                threading.Thread(target=self._pump, args=(a, b),
                                 name="chaos-partition-pump",
                                 daemon=True).start()

    def _pump(self, src, dst):
        try:
            while True:
                data = src.recv(65536)
                if not data:
                    break
                # the partition gate: while down, bytes are HELD here
                # (stall mode) — delivered when the partition heals, like
                # a switch buffering across a link flap
                while not self._gate.wait(timeout=0.5):
                    if not self._open:
                        return
                dst.sendall(data)
        except OSError:
            pass
        finally:
            for s in (src, dst):
                try:
                    s.shutdown(2)
                except OSError:
                    pass

    # -- chaos controls -----------------------------------------------------
    def partition(self, duration_s: float = 0.0, mode: str = "stall"):
        """Cut the victim's store traffic. mode="stall" holds bytes until
        heal(); mode="drop" severs every live connection (a client with a
        single persistent socket sees hard errors). duration_s > 0 arms a
        timer that heals automatically."""
        if mode not in ("stall", "drop"):
            raise ValueError(f"unknown partition mode {mode!r}")
        self._mode = mode
        stats["partitions_started"] += 1
        self._gate.clear()
        if mode == "drop":
            with self._conns_lock:
                conns, self._conns = self._conns, []
            for cli, up in conns:
                for s in (cli, up):
                    try:
                        s.close()
                    except OSError:
                        pass
        if duration_s > 0:
            t = threading.Timer(float(duration_s), self.heal)
            t.daemon = True
            t.start()

    def heal(self):
        """Restore traffic (held bytes from a stall flush through)."""
        self._gate.set()

    @property
    def partitioned(self) -> bool:
        return not self._gate.is_set()

    def close(self):
        self._open = False
        self._gate.set()
        try:
            self._srv.close()
        except OSError:
            pass
        with self._conns_lock:
            conns, self._conns = self._conns, []
        for cli, up in conns:
            for s in (cli, up):
                try:
                    s.close()
                except OSError:
                    pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False


class scope:
    """Context manager: arm injections inside, guaranteed clear() on exit."""

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        clear()
        return False
