"""Elastic membership over the process-group store: heartbeats, leases,
and generation-numbered views.

The resilience layer (r6/r7) resumes a job only at a FIXED world size;
cluster observability (r10) detects stragglers but has no remediation. This
module closes that loop with the smallest protocol that lets surviving
ranks agree on a new world size WITHOUT a coordinator:

  * every member keeps a lease alive by rewriting its heartbeat key
    `<prefix>/hb/<id>` (a timestamp) every `FLAGS_elastic_heartbeat_s`;
    a member whose heartbeat is older than `FLAGS_elastic_lease_ttl_s`
    is presumed dead;
  * the agreed membership is a published VIEW at `<prefix>/view`:
    `{"gen": G, "members": [...]}` with a monotonically increasing
    generation number. Writers reject stale generations (publish_view
    re-reads the current view first), and because every survivor computes
    its proposal deterministically from the SAME store state (current
    view + leases + left markers + join log), concurrent proposers
    converge on the same view — the store is the coordinator, no rank is;
  * graceful departure sets `<prefix>/left/<id>` (observed immediately,
    no TTL wait); ejection sets the same marker on someone else's behalf
    (the r10 straggler remediation endgame); joiners append themselves to
    a join log (`/join_seq` counter + `/join/<n>` entries) and wait to
    appear in a published view.

The same store carries a tiny gradient "allreduce" (`StoreReducer`) for
thread-rank data-parallel training: each member publishes its shard's
gradients + metadata per step, collects everyone else's, and a collection
timeout names exactly which members never arrived (`PeerLostError`) so the
trainer can distinguish "rank 2 is dead, reform" from "the network is
slow". Works identically over InProcStore (tests) and a native
TCPStore (real multi-host).

resilience/elastic.py builds the training loop (mesh reformation,
checkpoint resharding, micro-batch rebalancing) on top of this layer.
"""
from __future__ import annotations

import io
import json
import struct
import threading
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..observability.registry import counter as _counter

_REFORMS = _counter("elastic_membership_changes_total",
                    "Membership views adopted, by kind of change.",
                    labelnames=("kind",), always=True)

__all__ = [
    "MembershipView", "ElasticMembership", "StoreReducer", "PeerLostError",
]


class PeerLostError(TimeoutError):
    """A collective over the store timed out with specific members'
    contributions missing — carries WHO so the caller can check their
    leases and reform instead of guessing."""

    def __init__(self, op: str, step: int, missing: Sequence[int],
                 present: Sequence[int], timeout_s: float):
        self.op = str(op)
        self.step = int(step)
        self.missing = tuple(sorted(int(m) for m in missing))
        self.present = tuple(sorted(int(m) for m in present))
        self.timeout_s = float(timeout_s)
        super().__init__(
            f"{op} at step {step} timed out after {timeout_s:g}s: "
            f"contributions from members {list(self.missing)} never "
            f"arrived (got {list(self.present)}) — check their "
            f"heartbeat leases and reform the membership view")


class MembershipView:
    """One agreed membership: a generation number + a sorted member set.
    dp_rank(member) is the member's index in the sorted set, so ranks are
    dense in [0, world_size) at every generation — exactly what the
    sharded checkpoint layout and batch slicing key on."""

    __slots__ = ("gen", "members")

    def __init__(self, gen: int, members: Sequence[int]):
        self.gen = int(gen)
        self.members: Tuple[int, ...] = tuple(
            sorted({int(m) for m in members}))
        if not self.members:
            raise ValueError("a membership view needs at least one member")

    @property
    def world_size(self) -> int:
        return len(self.members)

    def contains(self, member: int) -> bool:
        return int(member) in self.members

    def dp_rank(self, member: int) -> int:
        try:
            return self.members.index(int(member))
        except ValueError:
            raise ValueError(
                f"member {member} is not in membership view gen "
                f"{self.gen} {list(self.members)}") from None

    def to_json(self) -> str:
        return json.dumps({"gen": self.gen, "members": list(self.members)})

    @classmethod
    def from_json(cls, raw) -> "MembershipView":
        if isinstance(raw, (bytes, bytearray)):
            raw = raw.decode()
        d = json.loads(raw)
        return cls(d["gen"], d["members"])

    def __eq__(self, other):
        return (isinstance(other, MembershipView)
                and self.gen == other.gen and self.members == other.members)

    def __hash__(self):
        return hash((self.gen, self.members))

    def __repr__(self):
        return f"MembershipView(gen={self.gen}, members={list(self.members)})"


class ElasticMembership:
    """One member's handle on the shared membership protocol.

    `heartbeat_s` is the interval between heartbeat-key rewrites; a member
    whose heartbeat key is older than `lease_ttl_s` is presumed dead and
    reformed out of the membership view (keep it well above heartbeat_s,
    >= 4x). `clock` is injectable so lease-expiry unit tests don't sleep.
    The background heartbeat thread ONLY heartbeats; view adoption happens
    in `poll()` on the caller's thread (the training loop), so the view
    never changes under a step's feet.
    """

    def __init__(self, store, member_id: int,
                 members: Sequence[int], *,
                 lease_ttl_s: float = 1.5,
                 heartbeat_s: float = 0.25,
                 prefix: str = "/pt/elastic",
                 clock: Callable[[], float] = time.monotonic):
        self.store = store
        self.member_id = int(member_id)
        self.prefix = str(prefix).rstrip("/")
        self.lease_ttl_s = float(lease_ttl_s)
        self.heartbeat_s = float(heartbeat_s)
        self._clock = clock
        # observer-side lease state (same scheme as ReplicaRegistry):
        # heartbeat values are opaque change tokens aged on THIS member's
        # clock from last observed change — writer clocks never enter the
        # comparison, so leases survive real process boundaries and NTP
        # wall-clock steps alike.
        self._hb_lock = threading.Lock()
        self._hb_seen: Dict[int, tuple] = {}
        self._hb_seq = 0
        self._view_lock = threading.RLock()
        self.view = MembershipView(0, members)
        self.changes: List[dict] = []     # adopted views, newest last
        self._callbacks: List[Callable] = []
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        # adopt whatever view is already agreed (late joiners see the
        # incumbents' generation, not their own gen-0 guess); otherwise
        # publish gen 0 — identical concurrent writes are benign, every
        # initial member writes the same bytes
        pub = self.published_view()
        if pub is not None:
            self.view = pub
        else:
            self.store.set(self._k("view"), self.view.to_json())
        self.heartbeat()

    # -- store keys ---------------------------------------------------------
    def _k(self, *parts) -> str:
        return "/".join([self.prefix, *map(str, parts)])

    # -- liveness -----------------------------------------------------------
    def heartbeat(self) -> None:
        """Renew this member's lease. The "n" sequence makes the value
        change every beat (frozen test clocks included); "t" is kept for
        humans reading store dumps, not for age computation."""
        with self._hb_lock:
            self._hb_seq += 1
            raw = json.dumps({"m": self.member_id, "n": self._hb_seq,
                              "t": self._clock()}).encode()
            self._hb_seen[self.member_id] = (raw, self._clock())
        self.store.set(self._k("hb", self.member_id), raw)

    def heartbeat_age(self, member: int) -> float:
        """Local monotonic seconds since this member last saw `member`'s
        heartbeat value change (0.0 on first sight: a lease is granted
        from first observation); inf when it never heartbeat."""
        raw = self.store.get(self._k("hb", member), blocking=False)
        if raw is None:
            return float("inf")
        now = self._clock()
        with self._hb_lock:
            seen = self._hb_seen.get(int(member))
            if seen is None or seen[0] != bytes(raw):
                self._hb_seen[int(member)] = (bytes(raw), now)
                return 0.0
            return max(0.0, now - seen[1])

    def has_left(self, member: int) -> bool:
        return self.store.get(self._k("left", member),
                              blocking=False) is not None

    def is_alive(self, member: int) -> bool:
        if int(member) == self.member_id:
            return True
        return (not self.has_left(member)
                and self.heartbeat_age(member) <= self.lease_ttl_s)

    # -- the background heartbeat thread ------------------------------------
    def start(self) -> None:
        if self._thread is not None:
            return
        self._stop.clear()
        self._thread = threading.Thread(
            target=self._beat_loop, name=f"elastic-hb-{self.member_id}",
            daemon=True)
        self._thread.start()

    def _beat_loop(self) -> None:
        while not self._stop.wait(self.heartbeat_s):
            try:
                self.heartbeat()
            except Exception:  # noqa: BLE001 — store teardown race in tests
                return

    def stop(self) -> None:
        """Stop heartbeating WITHOUT a left marker — from the outside this
        is indistinguishable from a crash (chaos.kill_rank uses it; graceful
        departure is leave())."""
        self._stop.set()
        t, self._thread = self._thread, None
        if t is not None:
            t.join(timeout=5)

    # -- view agreement -----------------------------------------------------
    def published_view(self) -> Optional[MembershipView]:
        raw = self.store.get(self._k("view"), blocking=False)
        if raw is None:
            return None
        try:
            return MembershipView.from_json(raw)
        except (ValueError, KeyError):
            return None

    def publish_view(self, view: MembershipView) -> bool:
        """Publish iff `view.gen` is strictly newer than the current
        published generation — stale-generation writes are rejected, so a
        slow rank waking up with an old proposal cannot roll the
        membership back."""
        cur = self.published_view()
        if cur is not None and cur.gen >= view.gen:
            return False
        self.store.set(self._k("view"), view.to_json())
        return True

    def pending_joins(self) -> List[int]:
        """Members in the join log that are not in the current view and
        are heartbeating. The log is an append-only counter + entries, so
        no two joiners can clobber each other."""
        # add(key, 0) is the cross-store atomic counter read (the native
        # TCPStore packs counters as int64 — get() is not portable)
        seq = self.store.add(self._k("join_seq"), 0)
        out = []
        for i in range(1, seq + 1):
            raw = self.store.get(self._k("join", i), blocking=False)
            if raw is None:
                continue
            try:
                m = int(raw)
            except ValueError:
                continue
            if (not self.view.contains(m) and not self.has_left(m)
                    and self.heartbeat_age(m) <= self.lease_ttl_s):
                out.append(m)
        return sorted(set(out))

    def poll(self) -> Optional[MembershipView]:
        """One protocol turn. Adopt a newer published view if someone
        already reformed; otherwise diff the current view against liveness
        (leases + left markers + join log) and, if it changed, propose
        gen+1. Returns the newly adopted view, or None if nothing moved.

        Deterministic proposals: every survivor computes `desired` from
        the same store state, so whichever proposer wins the publish race
        wrote the view the losers would have written — they adopt it and
        the generation advances exactly once per membership change."""
        with self._view_lock:
            pub = self.published_view()
            if pub is not None and pub.gen > self.view.gen:
                self._adopt(pub, kind="adopted")
                return self.view
            desired = {m for m in self.view.members if self.is_alive(m)}
            desired.update(self.pending_joins())
            if not desired or desired == set(self.view.members):
                return None
            proposal = MembershipView(self.view.gen + 1, desired)
            if self.publish_view(proposal):
                self._adopt(proposal, kind="proposed")
            else:
                pub = self.published_view()
                if pub is None or pub.gen <= self.view.gen:
                    return None
                self._adopt(pub, kind="adopted")
            return self.view

    def _adopt(self, view: MembershipView, kind: str) -> None:
        prev = self.view
        self.view = view
        lost = sorted(set(prev.members) - set(view.members))
        joined = sorted(set(view.members) - set(prev.members))
        info = {"gen": view.gen, "prev_gen": prev.gen,
                "members": list(view.members), "lost": lost,
                "joined": joined, "world_size": view.world_size,
                "kind": kind}
        self.changes.append(info)
        _REFORMS.inc(kind=("shrink" if lost else
                           "grow" if joined else "noop"))
        from ..observability import flight_recorder as _fr
        try:
            _fr.on_membership_change(info)
        except Exception:  # noqa: BLE001 — forensics must not kill training
            pass
        for cb in list(self._callbacks):
            try:
                cb(info)
            except Exception:  # noqa: BLE001
                pass

    def add_watch_callback(self, cb: Callable) -> None:
        """PreemptionHandler.attach_elastic plugs in here: called with the
        change-info dict on every adopted view."""
        self._callbacks.append(cb)

    # -- departures / arrivals ---------------------------------------------
    def leave(self) -> None:
        """Graceful departure: left marker (observed immediately) + stop
        heartbeating. Survivors reform on their next poll()."""
        self.store.set(self._k("left", self.member_id), b"leave")
        self.stop()

    def eject(self, member: int) -> Optional[MembershipView]:
        """Forcibly mark another member as departed (straggler
        remediation past the rebalancing bound) and reform."""
        self.store.set(self._k("left", member), b"ejected")
        return self.poll()

    def request_join(self, timeout_s: float = 30.0) -> MembershipView:
        """Announce this member in the join log, heartbeat, and wait until
        a published view contains it. Incumbent members fold pending
        joiners in on their next poll(); a lone joiner (everyone else
        gone) folds itself in."""
        self.heartbeat()
        n = self.store.add(self._k("join_seq"), 1)
        self.store.set(self._k("join", n), str(self.member_id))
        deadline = time.monotonic() + float(timeout_s)
        while time.monotonic() < deadline:
            with self._view_lock:
                pub = self.published_view()
                if pub is not None and pub.gen > self.view.gen:
                    self._adopt(pub, kind="adopted")
                if self.view.contains(self.member_id):
                    return self.view
                # no incumbent alive to sponsor us -> self-sponsor
                if not any(self.is_alive(m) for m in self.view.members):
                    self.poll()
                    if self.view.contains(self.member_id):
                        return self.view
            time.sleep(min(0.01, self.heartbeat_s / 4))
        raise TimeoutError(
            f"member {self.member_id} was not admitted into a membership "
            f"view within {timeout_s:g}s (current view gen "
            f"{self.view.gen}, members {list(self.view.members)})")


# -- store-backed gradient exchange -----------------------------------------

_HDR = struct.Struct(">I")


def _pack(meta: dict, arrays: Sequence[np.ndarray]) -> bytes:
    bio = io.BytesIO()
    np.savez(bio, **{f"a{i}": np.ascontiguousarray(a)
                     for i, a in enumerate(arrays)})
    header = json.dumps(meta).encode()
    return _HDR.pack(len(header)) + header + bio.getvalue()


def _unpack(raw: bytes) -> Tuple[dict, List[np.ndarray]]:
    (hlen,) = _HDR.unpack_from(raw, 0)
    meta = json.loads(raw[_HDR.size:_HDR.size + hlen].decode())
    with np.load(io.BytesIO(raw[_HDR.size + hlen:])) as z:
        arrays = [z[f"a{i}"] for i in range(len(z.files))]
    return meta, arrays


class StoreReducer:
    """Per-step gradient exchange over the store: publish mine, collect
    everyone's, name whoever never showed up. Keys are namespaced by
    membership generation so a reformed view can never consume a dead
    generation's leftovers, and each member GCs its own old keys two
    steps behind (every peer has consumed them by then — the exchange is
    lockstep)."""

    def __init__(self, store, member_id: int, prefix: str = "/pt/elastic/ar"):
        self.store = store
        self.member_id = int(member_id)
        self.prefix = str(prefix).rstrip("/")
        self._published: List[str] = []

    def _key(self, gen: int, step: int, member: int) -> str:
        return f"{self.prefix}/g{int(gen)}/s{int(step)}/m{int(member)}"

    def publish(self, gen: int, step: int, meta: dict,
                arrays: Sequence[np.ndarray]) -> None:
        key = self._key(gen, step, self.member_id)
        self.store.set(key, _pack(meta, arrays))
        self._published.append(key)
        # GC: anything this member published 2+ steps ago is consumed
        while len(self._published) > 2:
            self.store.delete(self._published.pop(0))

    def collect(self, gen: int, step: int, members: Sequence[int], *,
                timeout_s: float = 10.0
                ) -> Dict[int, Tuple[dict, List[np.ndarray]]]:
        deadline = time.monotonic() + float(timeout_s)
        out: Dict[int, Tuple[dict, List[np.ndarray]]] = {}
        pending = [int(m) for m in members]
        while pending:
            m = pending[0]
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                raise PeerLostError("store allreduce", step,
                                    missing=pending,
                                    present=sorted(out), timeout_s=timeout_s)
            try:
                raw = self.store.get(self._key(gen, step, m),
                                     blocking=True,
                                     timeout_s=min(remaining, 0.25))
            except TimeoutError:
                continue  # re-check the global deadline, try again
            if raw is None:
                continue
            out[m] = _unpack(raw)
            pending.pop(0)
        return out

    def reset(self) -> None:
        """Forget publish history (after a reform the old generation's
        keys are garbage the next save's namespace never touches)."""
        self._published.clear()
