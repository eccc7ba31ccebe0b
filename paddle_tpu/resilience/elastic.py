"""Elastic data-parallel training: survive rank loss by reforming the mesh.

`ElasticTrainer` runs true data parallelism over the process-group store
(distributed/elastic.py): every member computes gradients on its slice of
the global batch, publishes them, and applies the batch-size-weighted
average — so the parameter trajectory is a deterministic function of the
GLOBAL batch, independent of how many members split it. That invariance is
what makes the elastic guarantees testable: after a rank dies, the
survivors reform at N−1 and the loss trajectory must continue within
floating-point reassociation noise of the no-failure run.

The loop per global step:

    1. chaos check — an armed rank-kill stops heartbeating and exits
       (an unannounced crash as far as the survivors can tell);
    2. membership poll — adopt/propose a new generation view if leases
       expired, someone left, or a joiner announced itself;
    3. shard the global batch by the rebalancer's shares (equal split
       unless the r10 straggler signal shifted them within the bounded
       skew), fwd+bwd on this member's shard (jitted);
    4. store allreduce: publish grads + {shard size, loss, wall time},
       collect every member's, weighted-average in sorted member order
       (identical floats on every member — params stay bitwise-replicated);
    5. a collection timeout names the missing members (PeerLostError):
       wait for their leases to expire, adopt the reformed view, and
       REFORM — rebuild the CheckpointManager for the new rank/world,
       invalidate the jitted executables traced for the old world size,
       restore the full state from the last committed rank-sharded
       checkpoint (load_sharded target_world_size=1), and resume from
       its step;
    6. every `save_every` steps, a synchronized rank-sharded checkpoint
       (CheckpointManager backend="sharded", commit keys namespaced by
       the membership generation so a failed pre-reform save can never
       satisfy the reformed world's barrier).

Step 0 always commits a checkpoint (the initial rendezvous), so "the last
committed sharded checkpoint" exists from the first possible failure on.

Buffers (e.g. BN stats) are carried per-member, not averaged — models with
running statistics will diverge across members; the elastic path targets
buffer-free (or frozen-buffer) training. Gradient clipping is not applied
on this path.

Threads-as-ranks (tests): N threads share
one InProcStore, each owning its own model/optimizer/trainer. The same
code runs one-process-per-rank over a native TCPStore.
"""
from __future__ import annotations

import statistics
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from . import chaos
from .checkpoint_manager import CheckpointManager
from ..core.flags import define_flag, get_flag
from ..distributed.checkpoint import split_bounds
from ..distributed.elastic import (ElasticMembership, MembershipView,
                                   PeerLostError, StoreReducer)
from ..observability.cluster import STRAGGLER_K, STRAGGLER_M
from ..observability import flight_recorder as _flight
from ..observability.registry import counter as _counter

define_flag("elastic_eject_patience", 0,
            "Auto-eject chronically slow ranks: when the rebalancer has "
            "pinned a member at the (1 - skew) share clamp for this many "
            "consecutive observation windows, the lowest-id non-straggler "
            "member ejects it from the view and training reforms at N-1 "
            "(membership_ejections_total counts it; the flight recorder "
            "dumps the evidence). 0 (default) disables auto-ejection — "
            "eject() stays a manual operation.")

_REBALANCES = _counter("elastic_rebalance_events_total",
                       "Steps whose batch shares deviated from the equal "
                       "split due to the straggler signal.", always=True)
_REFORM_STEPS = _counter("elastic_reforms_total",
                         "Mesh reformations performed by ElasticTrainer.",
                         always=True)
_EJECTIONS = _counter("membership_ejections_total",
                      "Members auto-ejected by ElasticTrainer for chronic "
                      "straggling pinned past the rebalance clamp.",
                      always=True)

__all__ = ["ElasticTrainer", "MicroBatchRebalancer"]


class MicroBatchRebalancer:
    """Deterministic straggler-aware batch-share policy, short of ejection.

    Fed the per-member wall times every member saw in the SAME allreduce
    records, so every member computes identical shares — replication of
    the parameter state never depends on who computed what. Straggler
    detection reuses ClusterTelemetry's thresholds: a member whose smoothed
    wall time exceeds `k` x median for `m` consecutive steps gets its share
    scaled by median/ema, floored at (1 - skew) of its equal share, the
    slack spread over the others; skew 0 disables rebalancing (always
    equal split). The weighted gradient average keeps the update math
    exact under ANY share split, so rebalancing never perturbs the loss
    trajectory — only who computes how much of it."""

    def __init__(self, *, skew: float = 0.0, k: float = STRAGGLER_K,
                 m: int = STRAGGLER_M, ema_alpha: float = 0.5):
        self.skew = float(skew)
        self.k = float(k)
        self.m = int(m)
        self.ema_alpha = float(ema_alpha)
        self._ema: Dict[int, float] = {}
        self._streak: Dict[int, int] = {}
        self._pinned: Dict[int, int] = {}
        self.weights: Dict[int, float] = {}

    def reset(self) -> None:
        self._ema.clear()
        self._streak.clear()
        self._pinned.clear()
        self.weights.clear()

    def pinned_streak(self, member: int) -> int:
        """Consecutive observation windows this member's weight sat AT
        the (1 - skew) clamp — i.e. it is slower than the rebalance bound
        can compensate for. Deterministic across members (same walls in,
        same streak out), so the auto-eject decision built on it needs no
        extra coordination."""
        return self._pinned.get(member, 0)

    def observe(self, step: int, walls: Dict[int, float]) -> None:
        """Fold one step's per-member wall times (from the allreduce
        metadata — identical on every member) into the straggler state.

        Each member is judged against the median of the OTHERS (including
        itself would make k=2 detection impossible at world 2, where the
        straggler drags the median to the midpoint). The streak counts
        consecutive slow RAW walls — one fast step resets it — while the
        weight magnitude uses the smoothed EMA ratio."""
        a = self.ema_alpha
        for m in list(self._ema):
            if m not in walls:  # member reformed away
                self._ema.pop(m, None)
                self._streak.pop(m, None)
                self._pinned.pop(m, None)
                self.weights.pop(m, None)
        for m, w in walls.items():
            prev = self._ema.get(m)
            self._ema[m] = float(w) if prev is None \
                else a * float(w) + (1 - a) * prev
        self.weights = {}
        for m in sorted(walls):
            others_w = [float(walls[o]) for o in walls if o != m]
            base_w = statistics.median(others_w) if others_w else 0.0
            if base_w > 0 and float(walls[m]) > self.k * base_w:
                self._streak[m] = self._streak.get(m, 0) + 1
            else:
                self._streak[m] = 0
            if self.skew > 0 and self._streak[m] >= self.m:
                others_e = [self._ema[o] for o in walls if o != m]
                base_e = statistics.median(others_e) if others_e else 0.0
                ema = self._ema[m]
                ratio = base_e / ema if ema > 0 else 1.0
                self.weights[m] = max(1.0 - self.skew, ratio)
                if ratio <= 1.0 - self.skew:
                    self._pinned[m] = self._pinned.get(m, 0) + 1
                else:
                    self._pinned[m] = 0
            else:
                self.weights[m] = 1.0
                self._pinned[m] = 0

    def shares(self, batch_size: int, members: Sequence[int]) -> List[int]:
        """Per-member item counts summing to batch_size, in member order.
        Equal split (split_bounds — matches the checkpoint slicing rule)
        unless a straggler weight is active; then largest-remainder
        apportionment of the weighted shares, every member keeping at
        least one item."""
        B, n = int(batch_size), len(members)
        if B < n:
            raise ValueError(f"global batch of {B} cannot feed {n} members")
        w = [self.weights.get(m, 1.0) for m in members]
        if self.skew <= 0 or all(abs(x - 1.0) < 1e-12 for x in w):
            return [b - a for a, b in split_bounds(B, n)]
        _REBALANCES.inc()
        total_w = sum(w)
        raw = [B * x / total_w for x in w]
        out = [max(1, int(r)) for r in raw]
        # largest-remainder correction to land exactly on B, deterministic
        # tie-break by position
        while sum(out) > B:
            i = max(range(n), key=lambda j: (out[j] - raw[j], j))
            if out[i] <= 1:
                break
            out[i] -= 1
        while sum(out) < B:
            i = max(range(n), key=lambda j: (raw[j] - out[j], -j))
            out[i] += 1
        return out


class ElasticTrainer:
    """Data-parallel training loop that survives rank loss via mesh
    reformation and checkpoint resharding (see module docstring).

    Args:
        model / loss_fn / optimizer: as for jit.trainer.TrainStep — every
            member builds its OWN identically-initialized copy.
        root: checkpoint root shared by all members (rank-sharded layout).
        store: the process-group store all members share.
        member_id: this member's id (any ints; dp ranks are their sorted
            order within the current view).
        members: the initial membership.
        save_every: sharded-checkpoint cadence in global steps.
        heartbeat_s / lease_ttl_s: liveness knobs (ElasticMembership's).
        allreduce_timeout_s: how long collect() waits before naming the
            missing members (default: a few lease TTLs).
        rebalance_skew: bound for straggler rebalancing (0 disables).
        eject_patience: consecutive windows a member may sit pinned at
            the rebalance clamp before it is auto-ejected (default:
            FLAGS_elastic_eject_patience; 0 disables).
        clock: injectable monotonic clock for the membership layer.
    """

    def __init__(self, model, loss_fn, optimizer, root: str, *,
                 store, member_id: int, members: Sequence[int],
                 save_every: int = 5, keep_last_n: int = 3,
                 heartbeat_s: float = 0.25,
                 lease_ttl_s: float = 1.5,
                 allreduce_timeout_s: Optional[float] = None,
                 sync_timeout_s: float = 20.0,
                 rebalance_skew: float = 0.0,
                 eject_patience: Optional[int] = None,
                 clock: Callable[[], float] = time.monotonic):
        from ..jit.trainer import TrainStep

        self.model = model
        self.optimizer = optimizer
        self.root = str(root)
        self.store = store
        self.member_id = int(member_id)
        self.save_every = int(save_every)
        self.keep_last_n = int(keep_last_n)
        self.sync_timeout_s = float(sync_timeout_s)
        # TrainStep is the state container + pure fwd/bwd provider; its
        # fused executable is not used (the update must see the STORE-
        # averaged grads), donation off for the same UAF reason as
        # ResilientTrainer
        self.step = TrainStep(model, loss_fn, optimizer, donate=False,
                              nan_guard=False, telemetry=False)
        self.membership = ElasticMembership(
            store, member_id, members, lease_ttl_s=lease_ttl_s,
            heartbeat_s=heartbeat_s, clock=clock)
        self.reducer = StoreReducer(store, member_id)
        self.rebalancer = MicroBatchRebalancer(skew=rebalance_skew)
        self.eject_patience = int(
            get_flag("elastic_eject_patience")
            if eject_patience is None else eject_patience)
        self.allreduce_timeout_s = float(
            allreduce_timeout_s if allreduce_timeout_s is not None
            else max(3.0 * self.membership.lease_ttl_s, 2.0))
        self._gstep = 0
        self.losses: Dict[int, float] = {}     # step -> global loss (the
                                               # final value after replays)
        self.step_walls: List[Tuple[int, float, int, int]] = []
        # (step, this member's wall_s, gen, world) — every recorded step
        self.reforms: List[dict] = []
        self.manager = self._make_manager()
        self._build_executables()

    # -- compiled pieces ----------------------------------------------------
    def _build_executables(self) -> None:
        """(Re)build the jitted fwd/bwd and optimizer apply as FRESH
        closures — on reform this drops every trace/executable keyed on
        the old world's shard shapes (jax's caches key on callable
        identity), alongside TrainStep.invalidate_executables() for the
        step program itself."""
        import jax

        fwd = self.step._fwd_bwd_fn
        apply_ = self.optimizer.functional_update

        def fresh_fwd(p_vals, b_vals, batch):
            return fwd(p_vals, b_vals, batch)

        def fresh_apply(p_vals, g_vals, states, lr):
            return apply_(p_vals, g_vals, states, lr)

        self._fwd = jax.jit(fresh_fwd)
        self._apply = jax.jit(fresh_apply)

    # -- checkpoint plumbing ------------------------------------------------
    def _make_manager(self) -> CheckpointManager:
        v = self.membership.view
        return CheckpointManager(
            self.root, keep_last_n=self.keep_last_n, backend="sharded",
            store=self.store if v.world_size > 1 else None,
            rank=v.dp_rank(self.member_id), world_size=v.world_size,
            sync_timeout_s=self.sync_timeout_s,
            commit_namespace=f"g{v.gen}")

    def _state(self) -> Dict[str, Any]:
        return {
            "params": [p._value for p in self.step.params],
            "buffers": [b._value for b in self.step.buffers],
            "opt_state": self.step.opt_state,
        }

    def _meta(self) -> Dict[str, Any]:
        v = self.membership.view
        return {
            "step": int(self._gstep),
            "opt_step_count": int(self.optimizer._step_count),
            "gen": int(v.gen),
            "world_size": int(v.world_size),
            "members": list(v.members),
        }

    def _save(self) -> None:
        self.manager.save(self._gstep, self._state(), meta=self._meta())

    def _restore(self):
        """Gather the FULL state from the newest committed rank-sharded
        checkpoint — regardless of the world size that wrote it — and
        resume from its step. This is the resharding path: load_sharded
        re-slices at target_world_size=1."""
        import jax.numpy as jnp

        restored = self.manager.restore_latest(
            template=self._state(), target_world_size=1, target_rank=0)
        if restored is None:
            return None
        state, meta = restored.state, restored.meta
        for p, v in zip(self.step.params, state["params"]):
            p._value = jnp.asarray(v)
        for b, v in zip(self.step.buffers, state["buffers"]):
            b._value = jnp.asarray(v)
        import jax

        self.step.opt_state = jax.tree_util.tree_map(
            jnp.asarray, state["opt_state"])
        self._gstep = int(meta.get("step", restored.step))
        self.optimizer._step_count = int(
            meta.get("opt_step_count", self._gstep))
        return restored

    # -- reformation --------------------------------------------------------
    def _reform(self, view: MembershipView) -> None:
        """Membership changed: rebuild everything keyed on rank/world —
        checkpoint manager, jitted executables, rebalancer, reducer —
        then re-seed the full state from the last committed checkpoint."""
        _REFORM_STEPS.inc()
        self.manager = self._make_manager()
        self.step.invalidate_executables()
        self._build_executables()
        self.rebalancer.reset()
        self.reducer.reset()
        at_step = self._gstep
        restored = self._restore()
        if restored is None:
            raise RuntimeError(
                f"member {self.member_id}: no committed checkpoint to "
                f"reform from at gen {view.gen} (root {self.root!r}) — "
                f"the initial step-0 save should have guaranteed one")
        self.reforms.append({
            "gen": int(view.gen), "members": list(view.members),
            "world_size": int(view.world_size),
            "detected_at_step": int(at_step),
            "resumed_step": int(self._gstep),
            "dp_rank": self.membership.view.dp_rank(self.member_id),
        })

    def _await_reform(self) -> Optional[MembershipView]:
        """After a PeerLostError (or a failed synchronized save): keep
        polling until the missing members' leases expire and a new view is
        agreed. None if the deadline passes with membership unchanged
        (peers alive but slow — the caller retries the step)."""
        m = self.membership
        deadline = time.monotonic() + m.lease_ttl_s \
            + 4 * m.heartbeat_s + 2.0
        while time.monotonic() < deadline:
            changed = m.poll()
            if changed is not None:
                return changed
            time.sleep(max(m.heartbeat_s / 2, 0.01))
        return None

    # -- one global step ----------------------------------------------------
    @staticmethod
    def _batch_leading_dim(batch) -> int:
        import jax

        leaves = jax.tree_util.tree_leaves(batch)
        if not leaves:
            raise ValueError("empty batch")
        return int(np.asarray(leaves[0]).shape[0])

    @staticmethod
    def _slice_batch(batch, lo: int, hi: int):
        import jax

        return jax.tree_util.tree_map(
            lambda leaf: np.asarray(leaf)[lo:hi], batch)

    def _train_step(self, batch) -> None:
        view = self.membership.view
        members = view.members
        idx = view.dp_rank(self.member_id)
        t0 = time.perf_counter()
        delay = chaos.rank_delay(self.member_id)
        if delay > 0:  # injected straggler
            time.sleep(delay)
        B = self._batch_leading_dim(batch)
        shares = self.rebalancer.shares(B, members)
        lo = sum(shares[:idx])
        hi = lo + shares[idx]
        shard = self._slice_batch(batch, lo, hi)
        param_vals = [p._value for p in self.step.params]
        buffer_vals = [b._value for b in self.step.buffers]
        loss, g_vals, new_buf = self._fwd(param_vals, buffer_vals, shard)
        g_np = [np.asarray(g) for g in g_vals]
        wall = time.perf_counter() - t0
        meta = {"n": int(hi - lo), "loss": float(loss),
                "wall_s": float(wall), "member": self.member_id}
        self.reducer.publish(view.gen, self._gstep, meta, g_np)
        contrib = self.reducer.collect(
            view.gen, self._gstep, members,
            timeout_s=self.allreduce_timeout_s)
        # weighted average in sorted member order: identical float ops on
        # every member, so params stay bitwise-replicated — and the result
        # equals the full-batch gradient no matter how shares were split
        total_n = sum(contrib[m][0]["n"] for m in members)
        g_avg: Optional[List[np.ndarray]] = None
        global_loss = 0.0
        for m in members:
            c_meta, arrs = contrib[m]
            w = c_meta["n"] / total_n
            global_loss += c_meta["loss"] * w
            if g_avg is None:
                g_avg = [a * np.asarray(w, a.dtype) for a in arrs]
            else:
                for i, a in enumerate(arrs):
                    g_avg[i] = g_avg[i] + a * np.asarray(w, a.dtype)
        import jax.numpy as jnp

        lr = self.optimizer.get_lr() if hasattr(self.optimizer, "get_lr") \
            else float(self.optimizer._learning_rate)
        new_p, new_s = self._apply(
            param_vals, [jnp.asarray(g) for g in g_avg],
            self.step.opt_state, lr)
        for p, v in zip(self.step.params, new_p):
            p._value = v
        for b, v in zip(self.step.buffers, new_buf):
            b._value = v
        self.step.opt_state = new_s
        self.optimizer._step_count += 1
        self.rebalancer.observe(
            self._gstep, {m: float(contrib[m][0]["wall_s"])
                          for m in members})
        self.losses[self._gstep] = float(global_loss)
        self.step_walls.append((self._gstep,
                                float(time.perf_counter() - t0),
                                int(view.gen), int(view.world_size)))

    # -- the loop -----------------------------------------------------------
    def run(self, batches: Sequence, *, total_steps: Optional[int] = None,
            resume: bool = True) -> Dict[str, Any]:
        """Train for `total_steps` global steps (default: len(batches)),
        cycling through `batches`. Returns a report dict whose "status" is
        "completed", "killed" (this member died to an armed chaos kill),
        or "ejected" (reformed out of the view). Survivors keep running
        through any number of membership changes."""
        batches = list(batches)
        total = int(total_steps) if total_steps is not None \
            else len(batches)
        me = self.member_id
        report: Dict[str, Any] = {
            "member": me, "status": "completed", "steps_run": 0,
            "retries": 0,
        }
        self.membership.start()
        try:
            restored = self._restore() if resume else None
            if restored is None:
                self._save()  # the step-0 rendezvous: a committed
                              # checkpoint exists before any failure can
            step_retries = 0
            while self._gstep < total:
                if chaos.should_kill_rank(me, self._gstep):
                    chaos.note_rank_killed(me)
                    self.membership.stop()  # heartbeat dies unannounced
                    report["status"] = "killed"
                    report["killed_at_step"] = int(self._gstep)
                    return report
                changed = self.membership.poll()
                if changed is not None:
                    if not changed.contains(me):
                        report["status"] = "ejected"
                        return report
                    self._reform(changed)
                    continue
                try:
                    self._train_step(batches[self._gstep % len(batches)])
                except PeerLostError as e:
                    view = self._await_reform()
                    if view is not None:
                        if not view.contains(me):
                            report["status"] = "ejected"
                            return report
                        self._reform(view)
                        step_retries = 0
                        continue
                    if all(self.membership.is_alive(m) for m in e.missing) \
                            and step_retries < 10:
                        # peers are heartbeating, just slow (compile storm,
                        # loaded host): retry the same step — republishing
                        # the same key is an idempotent overwrite
                        step_retries += 1
                        report["retries"] += 1
                        continue
                    raise
                step_retries = 0
                self._gstep += 1
                report["steps_run"] += 1
                if self._maybe_auto_eject(report):
                    continue            # reformed at N-1 inside
                if self.save_every and self._gstep < total \
                        and self._gstep % self.save_every == 0:
                    if not self._checked_save(report):
                        return report   # ejected while saving
            self._checked_save(report)
            return report
        finally:
            self.membership.stop()
            self._finalize_report(report)

    def _maybe_auto_eject(self, report: Dict[str, Any]) -> bool:
        """Flag-gated auto-ejection of a chronically slow member: once
        the rebalancer has pinned someone at the (1 - skew) clamp for
        `eject_patience` consecutive windows, rebalancing has hit its
        bound and the straggler is still throttling every step — remove
        it. The pinned streak is computed from allreduce metadata that is
        identical on every member, so all survivors agree on the victim;
        the lowest-id non-straggler acts (eject is an idempotent store
        tombstone — a racing duplicate would be harmless, but a single
        deterministic actor keeps the counters honest) and everyone else
        adopts the new view through their own poll(). Returns True when
        THIS member ejected someone and reformed."""
        patience = self.eject_patience
        if patience <= 0:
            return False
        view = self.membership.view
        if view.world_size <= 1:
            return False
        me = self.member_id
        victims = [m for m in view.members
                   if self.rebalancer.pinned_streak(m) >= patience]
        victims = [m for m in victims if m != me]
        if not victims:
            return False
        actor = min(m for m in view.members if m not in victims)
        if me != actor:
            return False                # the actor's tombstone reaches us
        victim = min(victims)           # one per window; streaks persist
        info = {
            "member": int(victim), "by": int(me),
            "step": int(self._gstep), "gen": int(view.gen),
            "pinned_windows": int(self.rebalancer.pinned_streak(victim)),
            "weight": float(self.rebalancer.weights.get(victim, 1.0)),
        }
        _EJECTIONS.inc()
        _flight.on_member_ejected(info)
        report.setdefault("ejections", []).append(info)
        new_view = self.membership.eject(victim)
        if new_view is not None and new_view.contains(me):
            self._reform(new_view)
            return True
        return False

    def _checked_save(self, report: Dict[str, Any]) -> bool:
        """A synchronized save can be the first place a death is noticed
        (the barrier times out instead of the allreduce): treat that like
        a peer loss — reform and carry on; the failed attempt never
        committed, and its coordination keys are namespaced to the dead
        generation. It can equally be where THIS member first learns it
        was ejected (the others reformed to a new generation mid-save and
        will never join the old one's commit) — then the report flips to
        "ejected" and False comes back so the loop exits cleanly."""
        try:
            self._save()
        except TimeoutError:
            view = self._await_reform()
            if view is None:
                raise
            if not view.contains(self.member_id):
                report["status"] = "ejected"
                return False
            self._reform(view)
        return True

    def _finalize_report(self, report: Dict[str, Any]) -> None:
        v = self.membership.view
        report["step"] = int(self._gstep)
        report["final_gen"] = int(v.gen)
        report["final_world_size"] = int(v.world_size)
        report["final_members"] = list(v.members)
        report["reforms"] = list(self.reforms)
        report["losses"] = {int(k): float(self.losses[k])
                            for k in sorted(self.losses)}
        report["step_walls"] = [list(t) for t in self.step_walls]
        if report.get("status") == "completed" and self.step.params:
            # settle + rematerialize (same donation-UAF hygiene as
            # ResilientTrainer._finish, though donation is off here)
            import jax
            import jax.numpy as jnp

            for p in self.step.params:
                p._value = jnp.array(jax.block_until_ready(p._value))
