"""Observability benchmark + gate (ISSUE r9, extended r10 + r11).

Six checks, all CPU-safe:

  * overhead — steps/s of an identical TrainStep loop with FLAGS_metrics on
               vs off; the acceptance bar is ON within OVERHEAD_TOLERANCE
               (3%) of OFF. Run in child subprocesses so the flag state,
               metric registrations, and jit caches of one mode cannot leak
               into the other's clock.
  * flight   — a chaos-poisoned NaN step inside ResilientTrainer.run must
               produce exactly one atomic flight-recorder dump that parses
               as JSON and contains the poisoned step in its ring.
  * sinks    — the same run's events.jsonl must parse line-by-line with
               per-step phase timings, and the Prometheus textfile must
               round-trip through parse_prometheus_text with the autotune
               and compile-cache counters present.
  * straggler — 4 simulated ranks (threads over an InProcStore) publish
               through ClusterTelemetry; one rank's compute phase is delayed
               3x mid-run and must be flagged within M+2 steps of the
               injection — and never before it.
  * anomaly  — steady synthetic telemetry through the AnomalyEngine must
               stay silent; an injected loss spike must produce exactly one
               anomaly-tagged flight dump that parses with the anomaly and
               the step ring inside.
  * fleet_trace — (r11) fleet-wide distributed tracing gates: every
               finished request's merged cross-replica chrome trace covers
               >= 99% of its wall window with zero unparented spans (clean,
               kill->re-dispatch, and hedge scenarios); the four fleet
               detectors each fire on their injected fault and stay silent
               on the clean run; an injected breaker flap produces a flight
               dump embedding the router state AND merged traces; and
               fleet serving with metrics+tracing ON keeps >= 97% of the
               OFF throughput (best-of-5, interleaved arms, identical
               outputs).

Writes one JSON artifact (default OBSBENCH_r11.json at the repo root) and
exits nonzero when any check fails, so the verify pipeline can gate on it.

Usage: python tools/obsbench.py [--steps N] [--out OBSBENCH_r11.json]
"""
import argparse
import json
import os
import subprocess
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# a CPU tool: pin the platform (and the 8-device host mesh) before jax loads
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

OVERHEAD_TOLERANCE = 0.03  # metrics ON must keep >= 97% of OFF steps/s


def log(*a):
    print(*a, file=sys.stderr, flush=True)


# --------------------------------------------------------------------------
# overhead half: identical loop, metrics on vs off, one child process each
# --------------------------------------------------------------------------

def child_overhead(metrics_on: bool, steps: int) -> int:
    """Subprocess body: time a warm TrainStep loop; print steps/s JSON."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu import optimizer
    from paddle_tpu.core import flags
    from paddle_tpu.models import GPTConfig, GPTForCausalLM

    if metrics_on:
        flags.set_flags({"metrics": "on",
                         "metrics_dir": tempfile.mkdtemp(prefix="ob_m_")})
    cfg = GPTConfig(vocab_size=1024, hidden_size=128, num_layers=2,
                    num_heads=4, hidden_dropout_prob=0.0,
                    attention_dropout_prob=0.0)
    paddle.seed(0)
    model = GPTForCausalLM(cfg)
    opt = optimizer.AdamW(1e-4, parameters=model.parameters())
    from paddle_tpu.jit.trainer import TrainStep

    step = TrainStep(model, lambda ids: model(ids, labels=ids), opt,
                     nan_guard=True)
    ids = paddle.to_tensor(np.random.RandomState(0).randint(
        0, cfg.vocab_size, (8, 128)).astype(np.int32))
    float(step(ids).item())  # compile
    float(step(ids).item())  # warm
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = step(ids)
    float(loss.item())
    dt = time.perf_counter() - t0
    print(json.dumps({"steps_per_sec": steps / dt,
                      "metrics": "on" if metrics_on else "off"}), flush=True)
    return 0


def bench_overhead(steps: int, repeats: int = 3) -> dict:
    """Best-of-`repeats` per mode, modes interleaved so slow host drift hits
    both equally; best-of is the standard noise-rejecting statistic for a
    fixed workload."""
    best = {"off": 0.0, "on": 0.0}
    for _ in range(repeats):
        for mode in ("off", "on"):
            # a CPU measurement: the child is pinned to the CPU
            env = dict(os.environ, JAX_PLATFORMS="cpu")
            env.pop("FLAGS_metrics", None)
            env.pop("FLAGS_metrics_dir", None)
            res = subprocess.run(
                [sys.executable, os.path.abspath(__file__),
                 "--child-overhead", mode, str(steps)],
                env=env, capture_output=True, text=True, timeout=900)
            if res.returncode != 0:
                log(f"overhead child ({mode}) failed:\n" + res.stderr[-2000:])
                return {"error": f"{mode} child rc={res.returncode}"}
            sps = json.loads(
                res.stdout.strip().splitlines()[-1])["steps_per_sec"]
            best[mode] = max(best[mode], sps)
    off, on = best["off"], best["on"]
    overhead = 1.0 - on / off
    return {
        "steps": steps,
        "repeats": repeats,
        "steps_per_sec_off": round(off, 3),
        "steps_per_sec_on": round(on, 3),
        "overhead_frac": round(overhead, 4),
        "tolerance": OVERHEAD_TOLERANCE,
        "ok": overhead <= OVERHEAD_TOLERANCE,
    }


# --------------------------------------------------------------------------
# flight + sinks half: chaos NaN inside a real ResilientTrainer run
# --------------------------------------------------------------------------

def bench_flight_and_sinks(steps: int) -> dict:
    import glob

    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu import optimizer
    from paddle_tpu.core import flags
    from paddle_tpu.models import GPTConfig, GPTForCausalLM
    from paddle_tpu.observability import parse_prometheus_text, reset_all
    from paddle_tpu.resilience import ResilientTrainer, chaos

    mdir = tempfile.mkdtemp(prefix="ob_flight_")
    reset_all()
    flags.set_flags({"metrics": "on", "metrics_dir": mdir})
    try:
        cfg = GPTConfig(vocab_size=256, hidden_size=64, num_layers=2,
                        num_heads=2, hidden_dropout_prob=0.0,
                        attention_dropout_prob=0.0)
        paddle.seed(0)
        model = GPTForCausalLM(cfg)
        opt = optimizer.AdamW(1e-4, parameters=model.parameters())

        # the GPT batch is integer token ids; chaos poisons the first FLOAT
        # leaf, so ride a no-op float scale alongside the ids (0*NaN = NaN
        # poisons the loss, which the step-guard checks)
        def loss_fn(ids, scale):
            return model(ids, labels=ids) + 0.0 * paddle.mean(scale)

        trainer = ResilientTrainer(
            model, loss_fn, opt,
            tempfile.mkdtemp(prefix="ob_ckpt_"), save_every=2,
            nan_guard=True)
        ids_np = np.random.RandomState(0).randint(
            0, cfg.vocab_size, (4, 32)).astype(np.int32)
        scale_np = np.ones((4,), dtype=np.float32)
        n = max(steps, 4)
        poisoned = 1
        with chaos.scope():
            chaos.poison_steps([poisoned])
            report = trainer.run(
                [(paddle.to_tensor(ids_np), paddle.to_tensor(scale_np))] * n,
                epochs=1, resume=False)
        result = {"steps_run": report["steps_run"],
                  "steps_skipped": report["steps_skipped"]}

        # flight dump: exists, valid JSON, poisoned step in the ring
        dumps = glob.glob(os.path.join(mdir, "flight", "*.json"))
        result["flight_dumps"] = len(dumps)
        result["flight_ok"] = False
        if dumps:
            with open(dumps[0]) as f:
                payload = json.load(f)  # a torn file raises here
            ring_steps = [s.get("step") for s in payload.get("steps", [])]
            result["flight_reason"] = payload.get("reason")
            result["flight_ring"] = len(ring_steps)
            result["flight_ok"] = (
                payload.get("reason") == "nan_guard"
                and poisoned in ring_steps
                and not glob.glob(os.path.join(mdir, "flight", "*.tmp")))

        # events.jsonl: parses, every step record carries phase timings
        with open(os.path.join(mdir, "events.jsonl")) as f:
            records = [json.loads(line) for line in f]
        srecs = [r for r in records if r.get("kind") == "step"]
        result["event_records"] = len(records)
        result["step_records"] = len(srecs)
        result["events_ok"] = (
            len(srecs) == report["steps_run"]
            and all(set(r["phases"]) >= {"data", "compute", "reduce", "save"}
                    for r in srecs)
            and any(r["phases"]["save"] > 0 for r in srecs))

        # prometheus textfile: round-trips, registry counters present
        with open(os.path.join(mdir, "paddle_tpu.prom")) as f:
            parsed = parse_prometheus_text(f.read())
        series = {k[0] for k in parsed}
        wanted = {"training_steps_total", "training_steps_skipped_total",
                  "autotune_cache_events_total",
                  "jit_compile_cache_events_total",
                  "checkpoint_saves_total"}
        result["prom_series"] = len(series)
        result["prom_missing"] = sorted(wanted - series)
        result["prom_ok"] = not (wanted - series)

        result["ok"] = bool(result["flight_ok"] and result["events_ok"]
                            and result["prom_ok"]
                            and report["steps_skipped"] == 1)
        return result
    finally:
        flags.set_flags({"metrics": "off", "metrics_dir": ""})
        reset_all()


# --------------------------------------------------------------------------
# straggler half (r10): 4 thread-ranks over an InProcStore, one delayed
# --------------------------------------------------------------------------

def bench_straggler(world: int = 4, steps: int = 12, inject_at: int = 5,
                    victim: int = 2) -> dict:
    import threading

    from paddle_tpu.core import flags
    from paddle_tpu.distributed.env import InProcStore
    from paddle_tpu.observability import reset_all
    from paddle_tpu.observability.cluster import ClusterTelemetry

    reset_all()
    flags.set_flags({"metrics": "on"})
    try:
        store = InProcStore()
        m = 3
        cts = [ClusterTelemetry(store, r, world, k=2.0, m=m, timeout_s=30.0)
               for r in range(world)]
        base, slow = 0.01, 0.05

        def run_rank(r):
            for s in range(steps):
                compute = slow if (r == victim and s >= inject_at) else base
                cts[r].publish({
                    "step": s, "loss": 1.0 + 0.01 * s,
                    "step_wall_s": compute + 0.002,
                    "phases": {"data": 0.001, "compute": compute,
                               "reduce": 0.0, "save": 0.0},
                })

        threads = [threading.Thread(target=run_rank, args=(r,))
                   for r in range(1, world)]
        for t in threads:
            t.start()
        run_rank(0)  # rank 0 aggregates inline; blocking gets pace the run
        for t in threads:
            t.join(timeout=60)

        events = cts[0].straggler_events
        first_flag = min((e["step"] for e in events
                          if e["rank"] == victim), default=None)
        wrong = [e for e in events if e["rank"] != victim]
        return {
            "world": world, "steps": steps, "inject_at": inject_at,
            "victim": victim, "m": m,
            "aggregated": len(cts[0].aggregates),
            "straggler_events": len(events),
            "first_flag_step": first_flag,
            "false_flags": len(wrong),
            # gate: flagged within M+2 of injection (the detector needs M
            # consecutive steps by construction), never before, no one else
            "ok": (len(cts[0].aggregates) == steps
                   and first_flag is not None
                   and inject_at + m - 1 <= first_flag <= inject_at + m + 2
                   and not wrong),
        }
    finally:
        flags.set_flags({"metrics": "off"})
        reset_all()


# --------------------------------------------------------------------------
# anomaly half (r10): steady telemetry silent; loss spike -> tagged dump
# --------------------------------------------------------------------------

def bench_anomaly_dump() -> dict:
    import glob

    from paddle_tpu.core import flags
    from paddle_tpu.observability import reset_all
    from paddle_tpu.observability.anomaly import AnomalyEngine

    mdir = tempfile.mkdtemp(prefix="ob_anom_")
    reset_all()
    flags.set_flags({"metrics": "on", "metrics_dir": mdir,
                     "anomaly": "on"})
    try:
        def rec(step, loss):
            return {"step": step, "loss": loss, "grad_norm": 1.0,
                    "step_wall_s": 0.01, "tokens_per_s": 1000.0,
                    "phases": {"compute": 0.01}}

        engine = AnomalyEngine()
        steady = 0
        for s in range(20):
            steady += len(engine.observe(rec(s, 2.0 + 0.001 * s)))
        spiked = engine.observe(rec(20, 50.0))  # 25x the steady loss

        dumps = glob.glob(os.path.join(mdir, "flight", "*.json"))
        result = {
            "steady_anomalies": steady,
            "spike_kinds": [e["kind"] for e in spiked],
            "dumps": len(dumps),
        }
        dump_ok = False
        if dumps:
            with open(dumps[0]) as f:
                payload = json.load(f)  # a torn file raises here
            anomaly = payload.get("anomaly") or {}
            result["dump_reason"] = payload.get("reason")
            result["dump_anomaly_kind"] = anomaly.get("kind")
            dump_ok = (anomaly.get("kind") == "loss_spike"
                       and anomaly.get("step") == 20
                       and payload.get("anomalies")
                       and not glob.glob(
                           os.path.join(mdir, "flight", "*.tmp")))
        result["ok"] = bool(steady == 0
                            and any(e["kind"] == "loss_spike"
                                    for e in spiked)
                            and len(dumps) == 1 and dump_ok)
        return result
    finally:
        flags.set_flags({"metrics": "off", "metrics_dir": "",
                         "anomaly": "off"})
        reset_all()


# --------------------------------------------------------------------------
# fleet tracing half (r11): merged-trace completeness, fleet detectors,
# breaker-flap flight dump, and serve-path tracing overhead
# --------------------------------------------------------------------------

FLEET_COVERAGE_MIN = 0.99      # merged trace must cover >= 99% of wall time
FLEET_OVERHEAD_RATIO = 0.97    # tracing ON keeps >= 97% of OFF throughput


def bench_fleet_trace() -> dict:
    import glob

    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.core import flags
    from paddle_tpu.models import GPTConfig, GPTForCausalLM
    from paddle_tpu.observability import reset_all
    from paddle_tpu.serving import FleetRouter, ServingEngine
    from paddle_tpu.serving.fleet_observability import (
        coverage_of,
        unparented_spans,
    )

    mdir = tempfile.mkdtemp(prefix="ob_fleet_")
    reset_all()
    flags.set_flags({"metrics": "on", "metrics_dir": mdir,
                     "fleet_anomaly": "on"})
    cfg = GPTConfig(vocab_size=256, hidden_size=64, num_layers=2,
                    num_heads=2, hidden_dropout_prob=0.0,
                    attention_dropout_prob=0.0)

    def engine():
        paddle.seed(0)
        m = GPTForCausalLM(cfg)
        m.eval()
        return ServingEngine(m, max_slots=3, block_size=16,
                             prefill_chunk=16)

    def drive(router, freqs, skip_dead=True, max_iters=20000):
        for _ in range(max_iters):
            if all(f.done for f in freqs):
                return
            for rep in router.replicas.values():
                if (not (skip_dead and rep._killed)
                        and rep.engine.sched.has_work()):
                    rep.engine.step()
            router.poll()
        raise AssertionError("fleet requests did not settle")

    def prompts(seed, n, lo=4, hi=10):
        rng = np.random.RandomState(seed)
        return [[int(t) for t in rng.randint(0, cfg.vocab_size,
                                             rng.randint(lo, hi))]
                for _ in range(n)]

    def trace_gate(router, freqs):
        """Coverage + attribution for every finished request's merged
        trace; returns (min_coverage, total_unparented)."""
        cov, unp = 1.0, 0
        for f in freqs:
            payload = router.obs.trace_payload(f.request_id)
            if payload is None:
                return 0.0, -1
            evs = payload["traceEvents"]
            cov = min(cov, coverage_of(evs))
            unp += len(unparented_spans(evs, f.request_id))
        return cov, unp

    result = {}
    fired = set()
    try:
        # ---- clean run: full coverage, zero unparented, detectors silent
        router = FleetRouter([engine(), engine()], lease_ttl_s=1000.0)
        freqs = [router.submit(p, max_new_tokens=4)
                 for p in prompts(0, 4)]
        drive(router, freqs)
        cov, unp = trace_gate(router, freqs)
        result["clean"] = {
            "requests": len(freqs), "min_coverage": round(cov, 4),
            "unparented": unp,
            "anomalies": len(router.obs.anomalies_recent(100)),
        }
        result["clean"]["ok"] = (cov >= FLEET_COVERAGE_MIN and unp == 0
                                 and not router.obs.anomalies_recent(100))

        # ---- kill -> re-dispatch: one merged waterfall across replicas
        fake = [0.0]
        router = FleetRouter([engine(), engine()], clock=lambda: fake[0],
                             lease_ttl_s=1000.0)
        freq = router.submit(prompts(1, 1)[0], max_new_tokens=6)
        victim = freq.attempts[0].replica.rid
        for _ in range(3):
            router.replicas[victim].engine.step()
        router.kill_replica(victim)
        router.poll()
        drive(router, [freq])
        cov, unp = trace_gate(router, [freq])
        causes = [a.kind for a in freq.attempts]
        fired |= {e["kind"] for e in router.obs.anomalies_recent(100)}
        result["redispatch"] = {
            "causes": causes, "min_coverage": round(cov, 4),
            "unparented": unp,
            "ok": (causes == ["primary", "redispatch"]
                   and cov >= FLEET_COVERAGE_MIN and unp == 0),
        }

        # ---- hedge: losing arm present + cancelled in the merged trace
        fake = [0.0]
        router = FleetRouter([engine(), engine()], clock=lambda: fake[0],
                             lease_ttl_s=1000.0, hedge_ttft_ms=50.0)
        freq = router.submit(prompts(2, 1)[0], max_new_tokens=6)
        primary = freq.attempts[0].replica.rid
        router.replicas[primary].engine.step()   # admitted, no token yet
        fake[0] = 0.1                            # past the deadline
        router.poll()                            # fires the hedge
        hedge_rep = [r for r in router.replicas.values()
                     if r.rid != primary][0]
        for _ in range(20000):                   # only the hedge progresses
            if freq.done:
                break
            if hedge_rep.engine.sched.has_work():
                hedge_rep.engine.step()
            router.poll()
        cov, unp = trace_gate(router, [freq])
        evs = router.obs.trace_payload(freq.request_id)["traceEvents"]
        cancelled = [e for e in evs if e.get("ph") == "X"
                     and (e.get("args") or {}).get("cancelled")]
        fired |= {e["kind"] for e in router.obs.anomalies_recent(100)}
        result["hedge"] = {
            "hedged": freq.hedged, "min_coverage": round(cov, 4),
            "unparented": unp, "cancelled_spans": len(cancelled),
            "ok": (freq.hedged and freq.done and len(cancelled) > 0
                   and cov >= FLEET_COVERAGE_MIN and unp == 0),
        }

        # ---- breaker flap: injected submit faults + cooldown cycling;
        # the detector must fire AND dump a flight record embedding the
        # router state and the recent requests' merged traces
        fake = [0.0]
        router = FleetRouter([engine(), engine()], clock=lambda: fake[0],
                             lease_ttl_s=1000.0, breaker_errors=1,
                             breaker_cooldown_s=0.1)
        warm = [router.submit(p, max_new_tokens=3) for p in prompts(3, 2)]
        drive(router, warm)                      # traces into the ring
        r0 = router.replicas["replica-0"]
        real_submit = r0.engine.submit

        def bad_submit(*a, **kw):
            raise RuntimeError("injected flap fault")

        r0.engine.submit = bad_submit
        flapping = []
        for cycle in range(2):                   # open/half_open/open ...
            flapping.append(router.submit(prompts(10 + cycle, 1)[0],
                                          max_new_tokens=3))
            fake[0] += 0.2                       # past the cooldown
            router.poll()                        # open -> half_open event
            flapping.append(router.submit(prompts(20 + cycle, 1)[0],
                                          max_new_tokens=3))  # probe fails
        r0.engine.submit = real_submit
        drive(router, flapping)                  # detector fires mid-drive
        fired |= {e["kind"] for e in router.obs.anomalies_recent(100)}
        flap_dumps = sorted(glob.glob(
            os.path.join(mdir, "flight", "*fleet_breaker_flap.json")))
        flap = {"dumps": len(flap_dumps),
                "transitions": len(router.obs._breaker_log)}
        dump_ok = False
        if flap_dumps:
            with open(flap_dumps[0]) as f:
                payload = json.load(f)           # a torn file raises here
            rstate = payload.get("router") or {}
            reqs = payload.get("fleet_requests") or []
            flap["dump_replicas"] = sorted(rstate.get("replicas") or {})
            dump_ok = (
                payload.get("anomaly", {}).get("kind") == "breaker_flap"
                and {"breaker", "load", "lease_age_s"} <= set(
                    next(iter(rstate.get("replicas", {}).values()), {}))
                and any(r.get("trace") for r in reqs)
                and not glob.glob(os.path.join(mdir, "flight", "*.tmp")))
        flap["ok"] = bool(flap_dumps) and dump_ok
        result["breaker_flap"] = flap

        # ---- replica skew: sustained p95-TTFT imbalance through the
        # public record seam (the same path tick() feeds)
        router = FleetRouter([engine(), engine()], lease_ttl_s=1000.0)
        skew_fired = []
        for s in range(12):
            skew = 1.0 if s < 8 else 5.0
            skew_fired += router.obs.observe_record({
                "kind": "fleet_tick", "step": s, "hedge_rate": 0.0,
                "redispatch_rate": 0.0, "breaker_flaps": 0.0,
                "ttft_skew": skew})
        fired |= {e["kind"] for e in skew_fired}
        result["skew"] = {"fired": sorted({e["kind"] for e in skew_fired}),
                          "ok": any(e["kind"] == "replica_skew"
                                    for e in skew_fired)}
        result["detectors_fired"] = sorted(fired)
        result["detectors_ok"] = {
            "hedge_rate_spike", "redispatch_storm", "breaker_flap",
            "replica_skew"} <= fired

        # ---- serve-path overhead: metrics+tracing ON vs OFF, best-of-5
        # interleaved arms on the SAME warm fleet (jit caches shared), and
        # the outputs must be bitwise identical across arms. The overhead
        # fleet uses a wider model than the scenario fleets so each decode
        # tick carries realistic compute — on a toy step the fixed cost of
        # span recording would swamp the ratio with timer noise.
        ocfg = GPTConfig(vocab_size=256, hidden_size=128, num_layers=3,
                         num_heads=4, hidden_dropout_prob=0.0,
                         attention_dropout_prob=0.0)

        def overhead_engine():
            paddle.seed(0)
            m = GPTForCausalLM(ocfg)
            m.eval()
            return ServingEngine(m, max_slots=4, block_size=16,
                                 prefill_chunk=16)

        router = FleetRouter([overhead_engine(), overhead_engine()],
                             lease_ttl_s=1000.0)
        bench_prompts = prompts(4, 8, lo=6, hi=12)

        def arm(metrics_on):
            flags.set_flags({"metrics": "on" if metrics_on else "off"})
            t0 = time.perf_counter()
            fs = [router.submit(p, max_new_tokens=16)
                  for p in bench_prompts]
            drive(router, fs)
            dt = time.perf_counter() - t0
            return dt, [f.output_tokens for f in fs]

        arm(True)                                # warm both paths
        arm(False)
        best = {"on": float("inf"), "off": float("inf")}
        outs = {}
        for _ in range(5):
            for mode in ("on", "off"):
                dt, toks = arm(mode == "on")
                best[mode] = min(best[mode], dt)
                outs.setdefault(mode, toks)
        flags.set_flags({"metrics": "on"})
        ratio = best["off"] / best["on"]         # ON throughput / OFF
        result["overhead"] = {
            "best_on_s": round(best["on"], 4),
            "best_off_s": round(best["off"], 4),
            "throughput_ratio": round(ratio, 4),
            "floor": FLEET_OVERHEAD_RATIO,
            "outputs_identical": outs["on"] == outs["off"],
            "ok": (ratio >= FLEET_OVERHEAD_RATIO
                   and outs["on"] == outs["off"]),
        }

        result["ok"] = bool(result["clean"]["ok"]
                            and result["redispatch"]["ok"]
                            and result["hedge"]["ok"]
                            and result["breaker_flap"]["ok"]
                            and result["skew"]["ok"]
                            and result["detectors_ok"]
                            and result["overhead"]["ok"])
        return result
    finally:
        flags.set_flags({"metrics": "off", "metrics_dir": "",
                         "fleet_anomaly": "auto"})
        reset_all()


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--out", default=os.path.join(_REPO, "OBSBENCH_r11.json"))
    args = ap.parse_args()

    result = {"tool": "obsbench",
              "ts": time.strftime("%Y-%m-%dT%H:%M:%S")}
    log("--- overhead (metrics on vs off)")
    result["overhead"] = bench_overhead(args.steps)
    log(json.dumps(result["overhead"]))
    log("--- flight recorder + sinks (chaos NaN)")
    try:
        result["flight_sinks"] = bench_flight_and_sinks(min(args.steps, 6))
    except Exception as e:
        import traceback

        traceback.print_exc()
        result["flight_sinks"] = {"ok": False,
                                  "error": f"{type(e).__name__}: {e}"}
    log(json.dumps(result["flight_sinks"]))
    log("--- straggler injection (4 thread-ranks)")
    try:
        result["straggler"] = bench_straggler()
    except Exception as e:
        import traceback

        traceback.print_exc()
        result["straggler"] = {"ok": False,
                               "error": f"{type(e).__name__}: {e}"}
    log(json.dumps(result["straggler"]))
    log("--- anomaly engine (steady silence + loss-spike dump)")
    try:
        result["anomaly"] = bench_anomaly_dump()
    except Exception as e:
        import traceback

        traceback.print_exc()
        result["anomaly"] = {"ok": False,
                             "error": f"{type(e).__name__}: {e}"}
    log(json.dumps(result["anomaly"]))
    log("--- fleet tracing (merge completeness, detectors, overhead)")
    try:
        result["fleet_trace"] = bench_fleet_trace()
    except Exception as e:
        import traceback

        traceback.print_exc()
        result["fleet_trace"] = {"ok": False,
                                 "error": f"{type(e).__name__}: {e}"}
    log(json.dumps(result["fleet_trace"]))

    result["ok"] = bool(result["overhead"].get("ok")
                        and result["flight_sinks"].get("ok")
                        and result["straggler"].get("ok")
                        and result["anomaly"].get("ok")
                        and result["fleet_trace"].get("ok"))
    with open(args.out, "w") as f:
        json.dump(result, f, indent=2)
    print(json.dumps(result), flush=True)
    return 0 if result["ok"] else 1


if __name__ == "__main__":
    if len(sys.argv) >= 3 and sys.argv[1] == "--child-overhead":
        sys.exit(child_overhead(sys.argv[2] == "on", int(sys.argv[3])))
    sys.exit(main())
