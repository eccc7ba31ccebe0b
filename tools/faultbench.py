"""Fault-injection benchmark for the resilience runtime (ISSUE r6 + r17).

Scripted chaos run over paddle_tpu/resilience/: kills checkpoint saves at
every instrumented crash point, corrupts committed checkpoints on disk,
poisons gradients with NaNs, delivers fake preemption signals, and kills a
live data-parallel rank mid-run — then verifies the runtime recovers
exactly as the crash-consistency and elastic-training designs promise, and
writes one JSON artifact summarizing the outcome.

Scenarios (all CPU, deterministic, a few seconds total):
  * crash_sweep     — inject a crash at each of the four checkpoint-commit
                      crash points mid-training; a fresh trainer must resume
                      from the last COMMITTED step (never a torn one).
  * corruption      — truncate / bit-flip / delete pieces of the newest
                      committed checkpoint; restore_latest() must detect it
                      and fall back to the previous valid step.
  * nan_guard       — poison specific global steps; the compiled guard must
                      skip exactly those steps and training must end at the
                      same params as a run that never saw the poisoned
                      batches.
  * preemption      — deliver SIGTERM mid-epoch; the run must commit a final
                      checkpoint, report "preempted", and a restarted
                      trainer must finish the epoch from where it left off.
  * elastic         — four thread-ranks train data-parallel over one
                      InProcStore; one rank is killed mid-run (heartbeat
                      stops, no goodbye). HARD GATES: the survivors must
                      complete every step at N-1, the per-step loss
                      trajectory must stay within tolerance of the
                      no-failure run (fp reassociation only), recovery
                      must replay at most save_every steps, post-reform
                      step time must settle near the pre-kill baseline,
                      and survivor params must be bitwise identical.
                      A second pass slows (not kills) a rank and requires
                      the straggler-aware rebalancer to shrink its batch
                      share within the configured bound.
  * proc            — process-granularity fault isolation (r20): serving
                      replicas and elastic ranks as REAL supervised OS
                      processes over a socket TCPStore. HARD GATES:
                      SIGKILL a replica child mid-request -> bitwise
                      re-dispatch + capped-backoff respawn; SIGSTOP a
                      child past its lease -> replacement spawns, and on
                      SIGCONT the zombie fences itself out (exit 43,
                      never a stale response); stall the child's store
                      traffic through a partition proxy -> declared dead,
                      then heals inside the grace window with NO respawn
                      and NO fence bump; elastic rank processes where a
                      spawned joiner request_join()s in (grow reform) and
                      a SIGKILLed incumbent's survivors reform to N-1
                      from the last committed checkpoint with the clean
                      run's loss trajectory. Skips gracefully where
                      SIGSTOP semantics or the native store are missing.

Usage: python tools/faultbench.py [--out FAULTBENCH_r20.json] [--only proc]
"""
import argparse
import json
import os
import signal
import subprocess
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# a CPU tool: pin the platform (and the 8-device host mesh) before jax loads
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import numpy as np

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CRASH_POINTS = ["ckpt.begin", "ckpt.array", "ckpt.before_manifest",
                "ckpt.before_commit"]


def _build():
    import paddle_tpu as paddle
    from paddle_tpu import nn

    paddle.seed(3)
    return nn.Sequential(nn.Linear(4, 8), nn.Tanh(), nn.Linear(8, 1))


def _batches(n=12, seed=0):
    rng = np.random.RandomState(seed)
    return [(rng.randn(8, 4).astype(np.float32),
             rng.randn(8, 1).astype(np.float32)) for _ in range(n)]


def _trainer(root, save_every=3, **kw):
    from paddle_tpu import nn, optimizer
    from paddle_tpu.resilience import CheckpointManager
    from paddle_tpu.resilience.trainer import ResilientTrainer

    m = _build()
    opt = optimizer.SGD(0.1, parameters=m.parameters())
    loss_fn = nn.MSELoss()
    return ResilientTrainer(m, lambda a, b: loss_fn(m(a), b), opt,
                            CheckpointManager(root), save_every=save_every,
                            **kw)


def _params(tr):
    return [np.asarray(p._value) for p in tr.step.params]


def bench_crash_sweep(tmp):
    """Crash every commit stage once; resume must land on a committed step."""
    from paddle_tpu.resilience import chaos
    from paddle_tpu.resilience.chaos import InjectedCrash

    rows = []
    for point in CRASH_POINTS:
        chaos.clear()
        root = os.path.join(tmp, "sweep_" + point.replace(".", "_"))
        tr = _trainer(root)
        batches = _batches()
        # survive the save at step 3, die inside the save at step 6 —
        # "ckpt.array" fires once per leaf, the others once per save
        import jax

        n_leaves = len(jax.tree_util.tree_leaves(tr._state()))
        chaos.inject_crash(point,
                           after=n_leaves if point == "ckpt.array" else 1)
        crashed = False
        try:
            tr.run(batches)
        except InjectedCrash:
            crashed = True
        chaos.clear()
        tr2 = _trainer(root)
        rep = tr2.run(batches)
        rows.append({
            "crash_point": point,
            "crashed": crashed,
            "resumed_from": tr2.resumed_from,
            "resume_on_committed_step": tr2.resumed_from == 3,
            "finished_step": rep["step"],
            "torn_dirs_left": sum(
                d.endswith((".tmp", ".replaced")) for d in os.listdir(root)),
        })
    ok = all(r["crashed"] and r["resume_on_committed_step"]
             and r["finished_step"] == len(_batches())
             and r["torn_dirs_left"] == 0 for r in rows)
    return {"ok": ok, "saves_survived": sum(r["crashed"] for r in rows),
            "rows": rows}


def bench_corruption(tmp):
    """Damage the newest committed checkpoint three ways; restore_latest
    must catch each and fall back to the previous valid step."""
    from paddle_tpu.resilience import CheckpointManager

    rows = []
    for kind in ("truncate_array", "flip_bytes", "drop_manifest"):
        root = os.path.join(tmp, "corrupt_" + kind)
        tr = _trainer(root)
        tr.run(_batches())  # commits steps 3, 6, 9, 12
        mgr = CheckpointManager(root)
        newest = sorted(d for d in os.listdir(root) if d.startswith("step_"))[-1]
        victim = os.path.join(root, newest)
        arrs = sorted(f for f in os.listdir(victim) if f.startswith("arr_"))
        if kind == "truncate_array":
            with open(os.path.join(victim, arrs[0]), "r+b") as f:
                f.truncate(max(os.path.getsize(f.name) // 2, 1))
        elif kind == "flip_bytes":
            with open(os.path.join(victim, arrs[-1]), "r+b") as f:
                f.seek(0)
                f.write(b"\xff\xff\xff\xff")
        else:
            os.remove(os.path.join(victim, "manifest.json"))
        tr2 = _trainer(root)
        restored = tr2.restore()
        caught = [r for r in mgr.last_scan_report]  # noqa: F841 (per-manager)
        rows.append({
            "kind": kind,
            "fallback_step": restored.step if restored else None,
            "caught": [(os.path.basename(p), reason)
                       for p, reason in tr2.manager.last_scan_report],
        })
    ok = all(r["fallback_step"] == 9 and len(r["caught"]) == 1 for r in rows)
    return {"ok": ok, "corrupt_restores_caught": sum(
        len(r["caught"]) for r in rows), "rows": rows}


def bench_nan_guard(tmp):
    """Poisoned steps must be skipped in-program, bit-identically to a run
    that never saw those batches."""
    from paddle_tpu.resilience import chaos

    poisoned = {2, 5, 9}
    batches = _batches()
    chaos.poison_steps(poisoned)
    tr = _trainer(os.path.join(tmp, "nan_guarded"), save_every=0)
    rep = tr.run(batches, resume=False)
    chaos.clear()
    clean = [b for i, b in enumerate(batches) if i not in poisoned]
    ref = _trainer(os.path.join(tmp, "nan_ref"), save_every=0)
    ref.run(clean, resume=False)
    identical = all(np.array_equal(a, b)
                    for a, b in zip(_params(tr), _params(ref)))
    return {"ok": rep["steps_skipped"] == len(poisoned) and identical,
            "steps_poisoned": len(poisoned),
            "steps_skipped": rep["steps_skipped"],
            "bit_identical_to_clean_run": identical}


def bench_preemption(tmp):
    """SIGTERM mid-epoch → committed final save → restarted run finishes."""
    from paddle_tpu.resilience import chaos

    root = os.path.join(tmp, "preempt")
    batches = _batches()
    tr = _trainer(root, save_every=0)

    def feed():
        for i, b in enumerate(batches):
            if i == 5:
                chaos.fake_preemption(signal.SIGTERM)
            yield b

    rep1 = tr.run(feed)
    tr2 = _trainer(root, save_every=0)
    rep2 = tr2.run(batches)
    ok = (rep1["status"] == "preempted" and rep2["status"] == "completed"
          and tr2.resumed_from == rep1["step"]
          and rep1["steps_run"] + rep2["steps_run"] == len(batches))
    return {"ok": ok, "first_run": {k: rep1[k] for k in
                                    ("status", "step", "steps_run")},
            "resumed_from": tr2.resumed_from,
            "second_run": {k: rep2[k] for k in
                           ("status", "step", "steps_run")},
            "preemption_resumes": int(ok)}


def _elastic_world(root, members, batches, nsteps, kill=None, slow=None,
                   rebalance_skew=0.0):
    """Run one thread-per-member elastic world to completion; returns
    (trainers, reports, wall_s)."""
    import threading

    from paddle_tpu import nn, optimizer
    from paddle_tpu.distributed.env import InProcStore
    from paddle_tpu.resilience import chaos
    from paddle_tpu.resilience.elastic import ElasticTrainer

    store = InProcStore()
    trainers = []
    for mid in members:
        m = _build()
        opt = optimizer.SGD(0.1, parameters=m.parameters())
        loss_fn = nn.MSELoss()
        trainers.append(ElasticTrainer(
            m, (lambda mm: lambda a, b: loss_fn(mm(a), b))(m), opt, root,
            store=store, member_id=mid, members=members, save_every=3,
            lease_ttl_s=1.0, heartbeat_s=0.2, allreduce_timeout_s=6.0,
            rebalance_skew=rebalance_skew))
    if kill:
        chaos.kill_rank(*kill)
    if slow:
        chaos.slow_rank(*slow)
    reports = [None] * len(members)

    def go(i):
        reports[i] = trainers[i].run(batches, total_steps=nsteps)

    threads = [threading.Thread(target=go, args=(i,))
               for i in range(len(members))]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
    wall = time.perf_counter() - t0
    chaos.clear()
    return trainers, reports, wall


LOSS_CONTINUITY_TOL = 5e-3   # fp reassociation across reshard, nothing more
RECOVERY_STEPS_MAX = 3       # == save_every: worst-case replay window
STEP_TIME_RECOVERY_X = 5.0   # post-reform median step vs pre-kill median


def bench_elastic(tmp):
    """Kill a rank mid-run: survivors must reform at N-1 and the loss
    trajectory must continue as if nothing happened (hard gates); then a
    slow-rank pass must rebalance, not eject."""
    members, nsteps, kill_step = [0, 1, 2, 3], 12, 7
    batches = [(b[0].repeat(2, axis=0), b[1].repeat(2, axis=0))
               for b in _batches(nsteps)]  # 16 rows: divisible work at 4->1

    _, clean_reps, _ = _elastic_world(
        os.path.join(tmp, "elastic_clean"), members, batches, nsteps)
    clean_losses = clean_reps[0]["losses"]

    trainers, reps, wall = _elastic_world(
        os.path.join(tmp, "elastic_kill"), members, batches, nsteps,
        kill=(2, kill_step))
    by = {r["member"]: r for r in reps}
    survivors = [by[m] for m in (0, 1, 3)]

    completed_at_n1 = (
        by[2]["status"] == "killed"
        and all(r["status"] == "completed" and r["final_world_size"] == 3
                and r["step"] == nsteps for r in survivors))
    reforms = survivors[0]["reforms"]
    recovery_steps = (reforms[0]["detected_at_step"]
                      - reforms[0]["resumed_step"]) if reforms else None
    losses = survivors[0]["losses"]
    loss_dev = max(abs(losses[s] - clean_losses[s])
                   for s in clean_losses) if completed_at_n1 else None

    # step-time recovery: median wall AFTER the reform (excluding the
    # detection step itself) vs the pre-kill median
    walls = survivors[0]["step_walls"]  # (step, wall_s, gen, world)
    pre = sorted(w for _, w, g, _ in walls if g == 0)
    post = sorted(w for s, w, g, _ in walls
                  if g > 0 and s > reforms[0]["resumed_step"]) if reforms \
        else []
    med = lambda xs: xs[len(xs) // 2] if xs else None  # noqa: E731
    step_time_ratio = (med(post) / med(pre)
                       if pre and post and med(pre) > 0 else None)

    import numpy as _np
    p0 = [_np.asarray(p._value) for p in trainers[0].step.params]
    p3 = [_np.asarray(p._value) for p in trainers[3].step.params]
    survivors_bitwise = all(_np.array_equal(a, b) for a, b in zip(p0, p3))

    gates = {
        "completes_at_n_minus_1": bool(completed_at_n1),
        "loss_continuity": (loss_dev is not None
                            and loss_dev <= LOSS_CONTINUITY_TOL),
        "recovery_within_k_steps": (recovery_steps is not None
                                    and recovery_steps
                                    <= RECOVERY_STEPS_MAX),
        "step_time_recovered": (step_time_ratio is not None
                                and step_time_ratio
                                <= STEP_TIME_RECOVERY_X),
        "survivor_params_bitwise": bool(survivors_bitwise),
    }

    # slow-rank pass: rebalanced within the bound, nobody ejected
    skew = 0.5
    slow_tr, slow_reps, _ = _elastic_world(
        os.path.join(tmp, "elastic_slow"), [0, 1],
        batches, 8, slow=(1, 0.25), rebalance_skew=skew)
    rb = slow_tr[0].rebalancer
    w1 = rb.weights.get(1, 1.0)
    shares = rb.shares(16, [0, 1])
    gates["straggler_rebalanced_not_ejected"] = bool(
        all(r["status"] == "completed" and r["final_world_size"] == 2
            for r in slow_reps)
        and w1 < 1.0 and w1 >= 1.0 - skew
        and sum(shares) == 16 and shares[1] < 8 and shares[1] >= 1)

    return {
        "ok": all(gates.values()),
        "gates": gates,
        "killed_member": 2,
        "kill_step": kill_step,
        "reforms": reforms,
        "recovery_steps": recovery_steps,
        "loss_continuity_dev": loss_dev,
        "loss_continuity_tol": LOSS_CONTINUITY_TOL,
        "step_time_ratio": step_time_ratio,
        "rebalanced_weight": w1,
        "rebalanced_shares": shares,
        "wall_clock_kill_run_s": round(wall, 3),
    }


# ---------------------------------------------------------------------------
# proc — process-granularity fault isolation (ISSUE r20)
# ---------------------------------------------------------------------------

PROC_PROMPT = [5, 6, 7, 8]
PROC_ENGINE_KW = {"max_slots": 3, "block_size": 16, "prefill_chunk": 16}
_ELASTIC_VIEW_KEY = "/pt/elastic/view"


def _wait_for(cond, timeout_s, poll_s=0.05):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(poll_s)
    return False


def _pid_alive(pid):
    try:
        os.kill(pid, 0)
        return True
    except (ProcessLookupError, PermissionError):
        return False


def _rank_child_main(spec_json):
    """Hidden entry point (--_rank-child): ONE elastic data-parallel rank
    as a real OS process. Connects a TCPStore client, builds the seeded
    model, optionally request_join()s as a late joiner, runs the
    ElasticTrainer to completion and prints its report as one JSON line
    the parent scrapes off stdout."""
    import hashlib

    from paddle_tpu import native, nn, optimizer
    from paddle_tpu.distributed.elastic import ElasticMembership
    from paddle_tpu.resilience.elastic import ElasticTrainer

    spec = json.loads(spec_json)
    host, port = spec["store"]
    store = native.TCPStore(host, int(port), is_master=False,
                            world_size=1, timeout_s=30.0)
    mid = int(spec["member_id"])
    m = _build()
    opt = optimizer.SGD(0.1, parameters=m.parameters())
    loss_fn = nn.MSELoss()
    batches = [(b[0].repeat(2, axis=0), b[1].repeat(2, axis=0))
               for b in _batches(spec["n_batches"])]

    pre = None
    if spec.get("join"):
        # joiner choreography (mirrors tests/test_elastic.py): wait for
        # the incumbents' published view — constructing a membership
        # before ANY view exists would publish a solo gen-0 view and
        # fork the world — then announce the join with a pre-trainer
        # membership that keeps heartbeating until the trainer's own
        # membership takes over.
        if not _wait_for(lambda: store.get(_ELASTIC_VIEW_KEY,
                                           blocking=False) is not None,
                         60.0, poll_s=0.05):
            print("FAULTBENCH_RANK_REPORT "
                  + json.dumps({"member": mid, "status": "no_view"}),
                  flush=True)
            return 1
        pre = ElasticMembership(store, mid, [mid],
                                lease_ttl_s=spec["lease_ttl_s"],
                                heartbeat_s=spec["heartbeat_s"])
        pre.start()
        pre.request_join(timeout_s=60)

    tr = ElasticTrainer(
        m, lambda a, b: loss_fn(m(a), b), opt, spec["root"],
        store=store, member_id=mid, members=spec["members"],
        save_every=spec["save_every"], lease_ttl_s=spec["lease_ttl_s"],
        heartbeat_s=spec["heartbeat_s"],
        allreduce_timeout_s=spec["allreduce_timeout_s"],
        sync_timeout_s=spec.get("sync_timeout_s", 10.0))
    try:
        rep = tr.run(batches, total_steps=spec["nsteps"])
    finally:
        if pre is not None:
            pre.stop()
    sha = hashlib.sha256()
    for p in tr.step.params:
        sha.update(np.ascontiguousarray(np.asarray(p._value)).tobytes())
    rep["params_sha"] = sha.hexdigest()
    print("FAULTBENCH_RANK_REPORT " + json.dumps(rep), flush=True)
    return 0


def _spawn_rank(spec):
    # CPU rank processes: this module pins JAX_PLATFORMS=cpu at import, and
    # several ranks could not share one chip anyway
    env = dict(os.environ)
    return subprocess.Popen(
        [sys.executable, os.path.abspath(__file__),
         "--_rank-child", json.dumps(spec)],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, env=env)


def _scrape_rank_report(proc, timeout_s):
    out, _ = proc.communicate(timeout=timeout_s)
    for line in out.decode(errors="replace").splitlines():
        if line.startswith("FAULTBENCH_RANK_REPORT "):
            rep = json.loads(line.split(" ", 1)[1])
            if "losses" in rep:
                rep["losses"] = {int(k): float(v)
                                 for k, v in rep["losses"].items()}
            return rep
    return None


def _proc_fleet_gates(gates, detail, chaos):
    """Gates 1+2: SIGKILL a serving replica child mid-request (bitwise
    re-dispatch + capped respawn) and SIGSTOP/SIGCONT a zombie (lease
    death -> replacement -> fence-token exit, never a stale response)."""
    from paddle_tpu import native
    from paddle_tpu.observability import registry as _oreg
    from paddle_tpu.serving import build_process_fleet, wait_fleet_ready

    store = native.TCPStore("127.0.0.1", 0, is_master=True, world_size=1)
    router = build_process_fleet(
        2, store=store, store_addr=("127.0.0.1", store.port),
        spec_kwargs=dict(engine_kwargs=PROC_ENGINE_KW,
                         child_heartbeat_s=0.2, respawn_backoff_s=0.5,
                         respawn_max=5),
        router_kwargs=dict(heartbeat_s=0.05, lease_ttl_s=1.0,
                           prefix="/fb/fleet"))
    router.start()
    try:
        ready = wait_fleet_ready(router, 120)
        oracle = None
        if ready:
            r0 = router.submit(PROC_PROMPT, max_new_tokens=48)
            if r0.wait(60) and r0.finish_reason in ("stop", "length"):
                oracle = list(r0.output_tokens)

        # -- SIGKILL with the request in flight ------------------------------
        kill_ok, victim, vinc = False, None, 0
        if oracle:
            r1 = router.submit(PROC_PROMPT, max_new_tokens=48)
            victim = r1.attempts[0].replica
            vinc = victim.incarnation
            chaos.kill_process(victim.pid)
            kill_ok = (r1.wait(90) and r1.redispatches >= 1
                       and list(r1.output_tokens) == oracle)
            detail["kill_redispatches"] = getattr(r1, "redispatches", None)
        gates["fleet_kill_redispatch_bitwise"] = bool(kill_ok)

        # -- respawn under backoff, then parity on the new incarnation -------
        respawned = victim is not None and _wait_for(
            lambda: (victim.incarnation > vinc and not victim.warming()
                     and not victim.dead(router.lease_ttl_s)), 90)
        parity = False
        if respawned:
            r2 = router.submit(PROC_PROMPT, max_new_tokens=48)
            parity = r2.wait(60) and list(r2.output_tokens) == oracle
        gates["fleet_respawn_and_parity"] = bool(
            respawned and parity and victim.respawns >= 1)
        detail["victim_last_exit"] = victim.last_exit if victim else None
        detail["respawns_total"] = _oreg.REGISTRY.get(
            "fleet_replica_respawns_total").total()

        # -- zombie fencing --------------------------------------------------
        if not chaos.sigstop_supported():
            gates["fleet_zombie_fenced"] = True
            detail["zombie_skipped"] = "no SIGSTOP/SIGCONT on this platform"
            return
        z = next(rep for rep in router.replicas.values()
                 if rep is not victim)
        zpid, zinc = z.pid, z.incarnation
        chaos.hang_process(zpid)
        replaced = _wait_for(
            lambda: (z.incarnation > zinc and not z.warming()
                     and not z.dead(router.lease_ttl_s)), 90)
        served = False
        if replaced and oracle:
            # the frozen incarnation is orphaned, not routed: answers
            # keep coming from live incarnations and stay bitwise
            r3 = router.submit(PROC_PROMPT, max_new_tokens=48)
            served = r3.wait(60) and list(r3.output_tokens) == oracle
        chaos.resume_process(zpid)
        fenced = _wait_for(
            lambda: (not _pid_alive(zpid) and z.last_exit is not None
                     and z.last_exit.get("fenced_pid") == zpid), 30)
        gates["fleet_zombie_fenced"] = bool(replaced and served and fenced)
        detail["zombie_last_exit"] = z.last_exit
        detail["fenced_total"] = _oreg.REGISTRY.get(
            "fleet_replica_fenced_total").total()
    finally:
        router.stop()
        store.close()


def _proc_partition_gate(gates, detail, chaos):
    """Gate 3: stall the child's store traffic through a partition proxy
    past the lease TTL — the supervisor must declare it dead, then heal
    inside the grace window with NO respawn and NO fence bump."""
    from paddle_tpu import native
    from paddle_tpu.serving import build_process_fleet, wait_fleet_ready

    store = native.TCPStore("127.0.0.1", 0, is_master=True, world_size=1)
    proxy = chaos.StorePartitionProxy("127.0.0.1", store.port)
    router = build_process_fleet(
        1, store=store, store_addr=(proxy.host, proxy.port),
        spec_kwargs=dict(engine_kwargs=PROC_ENGINE_KW,
                         child_heartbeat_s=0.2, respawn_backoff_s=5.0,
                         respawn_max=3),
        router_kwargs=dict(heartbeat_s=0.05, lease_ttl_s=1.0,
                           prefix="/fb/part"))
    router.start()
    try:
        ready = wait_fleet_ready(router, 120)
        rep = router.replicas["replica-0"]
        inc0, respawns0 = rep.incarnation, rep.respawns
        oracle = None
        if ready:
            r0 = router.submit(PROC_PROMPT, max_new_tokens=16)
            if r0.wait(60):
                oracle = list(r0.output_tokens)
        proxy.partition(duration_s=2.0, mode="stall")
        declared_dead = _wait_for(lambda: rep.dead(router.lease_ttl_s), 10)
        revived = _wait_for(
            lambda: not rep.dead(router.lease_ttl_s) and not rep.warming(),
            20)
        healed_serves = False
        if revived and oracle:
            r1 = router.submit(PROC_PROMPT, max_new_tokens=16)
            healed_serves = r1.wait(60) and list(r1.output_tokens) == oracle
        gates["partition_heals_without_respawn"] = bool(
            ready and declared_dead and revived and healed_serves
            and rep.incarnation == inc0 and rep.respawns == respawns0)
        detail["partition"] = {
            "declared_dead": declared_dead, "revived": revived,
            "incarnation": rep.incarnation, "respawns": rep.respawns,
        }
    finally:
        router.stop()
        store.close()
        proxy.close()


def _proc_elastic_gates(tmp, gates, detail, chaos):
    """Gate 4: elastic ranks as real processes over a socket TCPStore — a
    spawned rank request_join()s into the running world (grow reform),
    then one incumbent is SIGKILLed and the survivors reform to N-1 from
    the last committed checkpoint, finishing every step with the loss
    trajectory of an undisturbed run."""
    from paddle_tpu import native

    nsteps, save_every, n_batches = 40, 3, 12
    batches = [(b[0].repeat(2, axis=0), b[1].repeat(2, axis=0))
               for b in _batches(n_batches)]
    # clean oracle: the loss trajectory is a function of the global batch
    # alone (world-size independent), so a cheap thread world stands in
    _, clean_reps, _ = _elastic_world(os.path.join(tmp, "proc_clean"),
                                      [0, 1], batches, nsteps)
    clean_losses = clean_reps[0]["losses"]

    store = native.TCPStore("127.0.0.1", 0, is_master=True, world_size=1)
    root = os.path.join(tmp, "proc_elastic")
    base = dict(store=["127.0.0.1", store.port], root=root,
                members=[0, 1], nsteps=nsteps, n_batches=n_batches,
                save_every=save_every, lease_ttl_s=2.0, heartbeat_s=0.25,
                allreduce_timeout_s=8.0, sync_timeout_s=10.0)
    procs, reports = {}, {}
    joined = False
    try:
        for mid in (0, 1):
            procs[mid] = _spawn_rank(dict(base, member_id=mid))
        procs[2] = _spawn_rank(dict(base, member_id=2,
                                    members=[0, 1, 2], join=True))

        def _members():
            raw = store.get(_ELASTIC_VIEW_KEY, blocking=False)
            if raw is None:
                return set()
            try:
                return set(json.loads(raw.decode()).get("members") or [])
            except ValueError:
                return set()

        joined = _wait_for(lambda: 2 in _members(), 180)
        detail["elastic_joined"] = joined
        if joined:
            time.sleep(1.2)     # let the grown world commit a checkpoint
            chaos.kill_process(procs[1].pid)
        for mid in (0, 2):
            try:
                reports[mid] = _scrape_rank_report(procs[mid], 300)
            except subprocess.TimeoutExpired:
                procs[mid].kill()
                reports[mid] = None
        try:
            procs[1].wait(timeout=10)
        except subprocess.TimeoutExpired:
            procs[1].kill()
    finally:
        for p in procs.values():
            if p.poll() is None:
                p.kill()
        store.close()

    r0, r2 = reports.get(0), reports.get(2)
    survivors_done = bool(
        joined and r0 and r2
        and r0["status"] == "completed" and r2["status"] == "completed"
        and r0["step"] == nsteps and r2["step"] == nsteps
        and r0["final_world_size"] == 2 and r2["final_world_size"] == 2
        and sorted(r0["final_members"]) == [0, 2]
        and r2["steps_run"] > 0)
    grew = bool(r0 and any(sorted(f["members"]) == [0, 1, 2]
                           for f in r0.get("reforms", [])))
    shrank = bool(r0 and any(sorted(f["members"]) == [0, 2]
                             for f in r0.get("reforms", [])))
    loss_dev = None
    if survivors_done and set(r0["losses"]) >= set(clean_losses):
        loss_dev = max(abs(r0["losses"][s] - clean_losses[s])
                       for s in clean_losses)
    gates["elastic_proc_join_then_survive_kill"] = bool(
        survivors_done and grew and shrank)
    gates["elastic_proc_loss_continuity"] = (
        loss_dev is not None and loss_dev <= LOSS_CONTINUITY_TOL)
    gates["elastic_proc_survivors_bitwise"] = bool(
        survivors_done and r0.get("params_sha")
        and r0["params_sha"] == r2["params_sha"])
    detail["elastic_proc"] = {
        "loss_continuity_dev": loss_dev,
        "reforms": (r0 or {}).get("reforms"),
        "survivor_reports": {m: (r and {k: r[k] for k in
                                        ("status", "step", "steps_run",
                                         "final_world_size",
                                         "final_members")})
                             for m, r in ((0, r0), (2, r2))},
    }


def bench_proc(tmp):
    """Replicas and ranks as supervised OS processes: crash, hang/zombie,
    store partition, and elastic join/leave survival — every fault is the
    genuine OS article (SIGKILL/SIGSTOP/TCP stall), every gate hard."""
    from paddle_tpu import native
    from paddle_tpu.resilience import chaos

    if not native.available():
        return {"ok": True, "gates": {},
                "skipped": "native TCPStore unavailable on this platform"}
    # respawn flight dumps follow FLAGS_metrics_dir — keep them in the
    # bench tmp dir instead of ./flight_recorder under the repo
    from paddle_tpu.core import flags
    flags.set_flags({"metrics_dir": os.path.join(tmp, "flight")})
    gates, detail = {}, {}
    _proc_fleet_gates(gates, detail, chaos)
    _proc_partition_gate(gates, detail, chaos)
    _proc_elastic_gates(tmp, gates, detail, chaos)
    return {"ok": all(gates.values()), "gates": gates, **detail}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=os.path.join(_REPO,
                                                  "FAULTBENCH_r20.json"))
    ap.add_argument("--only", default=None,
                    help="run a single scenario by name")
    ap.add_argument("--_rank-child", dest="rank_child", default=None,
                    help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.rank_child is not None:
        return _rank_child_main(args.rank_child)

    import jax

    from paddle_tpu.resilience import chaos

    out = {"backend": jax.default_backend(),
           "ts": time.strftime("%Y-%m-%dT%H:%M:%S"),
           "scenarios": {}}
    with tempfile.TemporaryDirectory() as tmp:
        for name, fn in [("crash_sweep", bench_crash_sweep),
                         ("corruption", bench_corruption),
                         ("nan_guard", bench_nan_guard),
                         ("preemption", bench_preemption),
                         ("elastic", bench_elastic),
                         ("proc", bench_proc)]:
            if args.only and name != args.only:
                continue
            chaos.clear()
            chaos.reset_stats()
            t0 = time.perf_counter()
            res = fn(tmp)
            res["wall_s"] = round(time.perf_counter() - t0, 3)
            res["chaos_stats"] = dict(chaos.stats)
            out["scenarios"][name] = res
            print(f"[faultbench] {name}: {'PASS' if res['ok'] else 'FAIL'} "
                  f"({res['wall_s']}s)")
    out["all_ok"] = all(s["ok"] for s in out["scenarios"].values())
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    print(f"[faultbench] wrote {args.out} (all_ok={out['all_ok']})")
    return 0 if out["all_ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
