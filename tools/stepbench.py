"""Per-phase step-time benchmark for the PR-2 optimization layer.

Breaks one training step into its overlappable phases and measures each
optimization on/off on the CPU-mesh GPT preset (8 virtual devices):

  data    — host batch wait + host->device transfer, with and without the
            double-buffered DevicePrefetcher (io/prefetch.py) hiding a
            deliberately slow host loader;
  compute — the compiled TrainStep itself, with and without AOT fast
            dispatch (FLAGS_jit_fast_dispatch);
  reduce  — explicit data-parallel gradient all-reduce, single coalesced
            pmean vs fixed-byte buckets XLA can overlap with the backward
            (distributed/grad_buckets.py);
  overlap — reduction schedules on a comm-dominated config: single-flush vs
            bucketed vs the fine-grained decomposed ring schedule
            (distributed/overlap.py), with trace-time schedule stats and
            the deterministic interleave verifier;
  save    — crash-consistent checkpoint commit, synchronous vs async
            (resilience/checkpoint_manager.py background write);
  compile — cold vs warm process start with the persistent XLA compilation
            cache (jit/compile_cache.py), measured in child subprocesses
            sharing one cache dir;
  autotune— flash-attention block tuning, cold (times every candidate) vs
            warm (persistent winner cache hit, core/autotune.py).

Prints ONE JSON line on stdout and appends it to STEPBENCH.jsonl. Sections
with a recorded gate (GATES) fail the run — nonzero exit — when their
metric regresses below the floor; --no-gate restores report-only mode.

Usage: python tools/stepbench.py [--steps N] [--quick] [--no-gate]
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

# must happen before jax import: CPU mesh with 8 virtual devices
if "--child-compile" not in sys.argv:
    _xla = os.environ.get("XLA_FLAGS", "")
    if "host_platform_device_count" not in _xla:
        os.environ["XLA_FLAGS"] = (
            _xla + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def _gpt_pieces(batch=8, seq=128):
    import paddle_tpu as paddle
    from paddle_tpu.models import GPTConfig, GPTForCausalLM

    cfg = GPTConfig(vocab_size=1024, hidden_size=128, num_layers=2,
                    num_heads=4, max_position_embeddings=max(seq, 128),
                    hidden_dropout_prob=0.0, attention_dropout_prob=0.0)
    paddle.seed(0)
    model = GPTForCausalLM(cfg)
    ids_np = np.random.RandomState(0).randint(
        0, cfg.vocab_size, (batch, seq)).astype(np.int32)
    return cfg, model, ids_np


def _make_step(model, mesh=None, dp_axis=None, grad_bucket_mb=None):
    from paddle_tpu import optimizer
    from paddle_tpu.jit.trainer import TrainStep

    opt = optimizer.AdamW(1e-4, parameters=model.parameters())
    return TrainStep(model, lambda ids: model(ids, labels=ids), opt,
                     mesh=mesh, dp_axis=dp_axis, grad_bucket_mb=grad_bucket_mb)


def _steps_per_sec(step, ids, n):
    import paddle_tpu as paddle

    t = paddle.to_tensor(ids)
    float(step(t).item())  # compile
    float(step(t).item())  # warm
    t0 = time.perf_counter()
    for _ in range(n):
        loss = step(t)
    float(loss.item())
    return n / (time.perf_counter() - t0)


# -- data phase: slow host loader, prefetch off/on ---------------------------
def bench_data_phase(n_steps: int):
    import paddle_tpu as paddle
    from paddle_tpu.io import DevicePrefetcher

    _, model, ids_np = _gpt_pieces()
    step = _make_step(model)
    float(step(paddle.to_tensor(ids_np)).item())  # compile outside the clock
    delay_s = 0.01  # deliberate host-loader cost per batch

    def loader(n):
        for _ in range(n):
            time.sleep(delay_s)
            yield ids_np

    # OFF: data wait serializes with compute
    t_data = t_compute = 0.0
    t0 = time.perf_counter()
    it = loader(n_steps)
    for _ in range(n_steps):
        d0 = time.perf_counter()
        host = next(it)
        t = paddle.to_tensor(host)
        t_data += time.perf_counter() - d0
        c0 = time.perf_counter()
        float(step(t).item())
        t_compute += time.perf_counter() - c0
    off_sps = n_steps / (time.perf_counter() - t0)

    # ON: prefetcher overlaps loader + transfer with compute
    pf = DevicePrefetcher(loader(n_steps), depth=2)
    t_data_on = 0.0
    t0 = time.perf_counter()
    for dev in pf:
        d0 = time.perf_counter()
        t = paddle.Tensor(dev)
        t_data_on += time.perf_counter() - d0
        float(step(t).item())
    on_sps = n_steps / (time.perf_counter() - t0)
    return {
        "loader_delay_ms": delay_s * 1000,
        "data_ms_per_step_off": round(t_data / n_steps * 1000, 3),
        "data_ms_per_step_on": round(
            (t_data_on + pf.stats["wait_s"]) / n_steps * 1000, 3),
        "compute_ms_per_step": round(t_compute / n_steps * 1000, 3),
        "steps_per_sec_off": round(off_sps, 3),
        "steps_per_sec_on": round(on_sps, 3),
        "speedup": round(on_sps / off_sps, 3),
    }


# -- reduce phase: explicit DP, single vs bucketed all-reduce ----------------
def bench_reduce_phase(n_steps: int):
    import jax
    from jax.sharding import Mesh

    mesh = Mesh(np.array(jax.devices()), ("dp",))
    _, model_single, ids_np = _gpt_pieces()
    single = _make_step(model_single, mesh=mesh, dp_axis="dp",
                        grad_bucket_mb=-1)
    sps_single = _steps_per_sec(single, ids_np, n_steps)
    _, model_bucketed, _ = _gpt_pieces()
    bucketed = _make_step(model_bucketed, mesh=mesh, dp_axis="dp",
                          grad_bucket_mb=1)
    sps_bucketed = _steps_per_sec(bucketed, ids_np, n_steps)
    return {
        "mesh": "dp=8 (cpu virtual)",
        "reduce_ms_per_step_single": round(1000 / sps_single, 3),
        "reduce_ms_per_step_bucketed": round(1000 / sps_bucketed, 3),
        "steps_per_sec_single": round(sps_single, 3),
        "steps_per_sec_bucketed": round(sps_bucketed, 3),
        "speedup": round(sps_bucketed / sps_single, 3),
    }


# -- overlap: single-flush vs bucketed vs fine decomposed schedule -----------
def _mlp_pieces(width=768, depth=4, batch=8):
    """Comm-dominated config: fat square layers (≈9.4 MB of f32 grads at
    width 768) against a tiny batch, so the gradient all-reduce dominates
    the step and schedule differences are visible."""
    import paddle_tpu as paddle
    from paddle_tpu import nn

    paddle.seed(0)
    layers = []
    for _ in range(depth):
        layers += [nn.Linear(width, width), nn.GELU()]
    model = nn.Sequential(*layers)
    x = np.random.RandomState(0).rand(batch, width).astype(np.float32)
    return model, x


def bench_overlap(n_steps: int):
    """Explicit-DP reduction schedules on the comm-dominated MLP: single
    coalesced all-reduce vs fixed-byte pmean buckets vs the fine-grained
    decomposed ring schedule (distributed/overlap.py), best-of-3 runs each,
    plus the trace-time schedule stats and the deterministic interleave
    verifier (analysis.verify_overlap_schedule)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    import paddle_tpu as paddle
    from paddle_tpu import analysis, optimizer
    from paddle_tpu.distributed import overlap
    from paddle_tpu.jit.trainer import TrainStep

    mesh = Mesh(np.array(jax.devices()), ("dp",))
    inner = max(2, min(n_steps // 4, 5))

    def run(**kw):
        model, x = _mlp_pieces()
        opt = optimizer.Momentum(1e-3, momentum=0.9,
                                 parameters=model.parameters())
        step = TrainStep(model, lambda a: ((model(a)) ** 2).mean(), opt,
                         mesh=mesh, dp_axis="dp", **kw)
        t = paddle.to_tensor(x)
        float(step(t).item())  # compile
        float(step(t).item())  # warm
        best = 0.0
        for _ in range(3):  # best-of-3
            t0 = time.perf_counter()
            for _ in range(inner):
                loss = step(t)
            float(loss.item())
            best = max(best, inner / (time.perf_counter() - t0))
        return step, best

    _, sps_single = run(grad_bucket_mb=-1)
    _, sps_bucketed = run(grad_bucket_mb=1, dp_overlap="bucketed")
    step_f, sps_fine = run(grad_bucket_mb=1, dp_overlap="fine")
    sched = overlap.last_schedule() or {}
    sched.pop("buckets", None)

    model, x = _mlp_pieces()  # fresh abstract trace for the verifier
    closed = jax.make_jaxpr(step_f._base_callable)(
        [p._value for p in step_f.params],
        [b._value for b in step_f.buffers],
        step_f.opt_state, jnp.float32(1e-3), jnp.int32(0), (x,))
    report = analysis.verify_overlap_schedule(closed)
    return {
        "mesh": "dp=8 (cpu virtual)",
        "config": "mlp 4x768 batch 8 (comm-dominated)",
        "steps_per_sec_single": round(sps_single, 3),
        "steps_per_sec_bucketed": round(sps_bucketed, 3),
        "steps_per_sec_fine": round(sps_fine, 3),
        "speedup_bucketed_vs_single": round(sps_bucketed / sps_single, 3),
        "speedup_fine_vs_single": round(sps_fine / sps_single, 3),
        "speedup": round(sps_fine / sps_single, 3),
        "schedule": sched,
        "verifier": report,
    }


# -- compute phase: jit dispatch vs AOT fast dispatch ------------------------
def bench_dispatch(n_steps: int):
    from paddle_tpu.core import flags

    _, model, ids_np = _gpt_pieces()
    step = _make_step(model)
    flags.set_flags({"jit_fast_dispatch": False})
    sps_jit = _steps_per_sec(step, ids_np, n_steps)
    flags.set_flags({"jit_fast_dispatch": True})
    sps_aot = _steps_per_sec(step, ids_np, n_steps)
    flags.set_flags({"jit_fast_dispatch": False})
    return {
        "compute_ms_per_step_jit": round(1000 / sps_jit, 3),
        "compute_ms_per_step_aot": round(1000 / sps_aot, 3),
        "steps_per_sec_jit": round(sps_jit, 3),
        "steps_per_sec_aot": round(sps_aot, 3),
        "speedup": round(sps_aot / sps_jit, 3),
    }


# -- save phase: sync vs async checkpoint ------------------------------------
def bench_save_phase(n_saves: int):
    from paddle_tpu.resilience.checkpoint_manager import CheckpointManager

    state = {"params": [np.random.RandomState(i).rand(256, 256).astype(
        np.float32) for i in range(8)]}

    sync = CheckpointManager(tempfile.mkdtemp(prefix="sb_sync_"))
    t0 = time.perf_counter()
    for i in range(n_saves):
        sync.save(i, state)
    sync_s = (time.perf_counter() - t0) / n_saves

    asy = CheckpointManager(tempfile.mkdtemp(prefix="sb_async_"),
                            async_save=True)
    lat = 0.0
    t0 = time.perf_counter()
    for i in range(n_saves):
        s0 = time.perf_counter()
        asy.save(i, state)  # returns after snapshot; commit in background
        lat += time.perf_counter() - s0
    asy.wait()
    total_s = (time.perf_counter() - t0) / n_saves
    return {
        "state_mb": round(sum(a.nbytes for a in state["params"]) / 2**20, 1),
        "save_ms_sync": round(sync_s * 1000, 3),
        "save_ms_async_caller": round(lat / n_saves * 1000, 3),
        "save_ms_async_total": round(total_s * 1000, 3),
        "caller_latency_reduction": round(
            1 - (lat / n_saves) / sync_s, 3),
    }


# -- compile cache: cold vs warm process start -------------------------------
def bench_compile_cache():
    cache_dir = tempfile.mkdtemp(prefix="sb_xla_")
    times = []
    for label in ("cold", "warm"):
        # a CPU measurement: each child is pinned to the CPU (this parent
        # has imported jax, and one process owns a chip) and gets a
        # throwaway cache directory so that "cold" really is cold
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   FLAGS_jit_compile_cache_dir=cache_dir)
        env.pop("XLA_FLAGS", None)  # single device is enough for this probe
        res = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--child-compile",
             cache_dir],
            env=env, capture_output=True, text=True, timeout=900)
        if res.returncode != 0:
            log(f"compile-cache child ({label}) failed:\n" + res.stderr[-2000:])
            return {"error": f"{label} child rc={res.returncode}"}
        times.append(json.loads(res.stdout.strip().splitlines()[-1]))
    cold, warm = times
    return {
        "cache_dir_entries": len(os.listdir(cache_dir)),
        "compile_s_cold": cold["compile_s"],
        "compile_s_warm": warm["compile_s"],
        "warm_start_reduction": round(
            1 - warm["compile_s"] / cold["compile_s"], 3)
        if cold["compile_s"] > 0 else None,
    }


def child_compile(cache_dir: str) -> int:
    """Subprocess body: enable the persistent cache, build the GPT TrainStep,
    report time-to-first-step (trace + XLA compile + run)."""
    import paddle_tpu as paddle
    from paddle_tpu.jit import enable_persistent_cache

    enable_persistent_cache(cache_dir)
    _, model, ids_np = _gpt_pieces()
    step = _make_step(model)
    t0 = time.perf_counter()
    float(step(paddle.to_tensor(ids_np)).item())
    print(json.dumps({"compile_s": round(time.perf_counter() - t0, 3)}),
          flush=True)
    return 0


# -- runtime telemetry: phases from the live runtime (observability/) --------
def bench_runtime_telemetry(n_steps: int):
    """PR r9: instead of re-timing phases externally (the benches above),
    read them from the per-step telemetry the runtime itself emits — one
    ResilientTrainer run with FLAGS_metrics=on, phases averaged straight out
    of events.jsonl."""
    import paddle_tpu as paddle
    from paddle_tpu import optimizer
    from paddle_tpu.core import flags
    from paddle_tpu.observability import reset_all
    from paddle_tpu.resilience import ResilientTrainer

    import jax
    from jax.sharding import Mesh

    mdir = tempfile.mkdtemp(prefix="sb_obs_")
    reset_all()
    flags.set_flags({"metrics": "on", "metrics_dir": mdir})
    try:
        _, model, ids_np = _gpt_pieces()
        opt = optimizer.AdamW(1e-4, parameters=model.parameters())
        # explicit-DP step so the reduce phase exists to attribute: the
        # runtime probes the comm-only cost and carves it out of compute
        # (jit/trainer._probe_reduce_s) — reduce_ms_avg must be nonzero
        mesh = Mesh(np.array(jax.devices()), ("dp",))
        trainer = ResilientTrainer(
            model, lambda ids: model(ids, labels=ids), opt,
            tempfile.mkdtemp(prefix="sb_obs_ckpt_"),
            save_every=max(n_steps // 2, 1), nan_guard=True,
            mesh=mesh, dp_axis="dp")
        batches = [(paddle.to_tensor(ids_np),)] * n_steps
        report = trainer.run(batches, epochs=1, resume=False)
        with open(os.path.join(mdir, "events.jsonl")) as f:
            records = [json.loads(line) for line in f]
        steps = [r for r in records if r.get("kind") == "step"]
        phases = {}
        for p in ("data", "compute", "reduce", "save"):
            phases[f"{p}_ms_avg"] = round(
                sum(s["phases"].get(p, 0.0) for s in steps)
                / max(len(steps), 1) * 1000, 3)
        return {
            "metrics_dir": mdir,
            "step_records": len(steps),
            "compile_events": sum(
                1 for r in records if r.get("kind") in ("compile",
                                                        "recompile")),
            **phases,
            "last_grad_norm": steps[-1].get("grad_norm") if steps else None,
            "samples_per_s_last": steps[-1].get("samples_per_s")
            if steps else None,
            "summary": report.get("telemetry"),
        }
    finally:
        flags.set_flags({"metrics": "off", "metrics_dir": ""})
        reset_all()


# -- autotune: cold tuning vs persistent-cache warm start --------------------
def bench_autotune():
    import jax.numpy as jnp

    from paddle_tpu.core import autotune, flags
    from paddle_tpu.ops.pallas.flash_attention import flash_attention_tuned

    cache_dir = tempfile.mkdtemp(prefix="sb_at_")
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.rand(1, 512, 4, 32).astype(np.float32))
    out = {}
    for label in ("cold", "warm"):
        autotune.clear_cache()  # drop in-memory winners; disk persists
        flags.set_flags({"use_autotune": True,
                         "autotune_cache_dir": cache_dir})
        t0 = time.perf_counter()
        flash_attention_tuned(q, q, q, causal=False, interpret=True)
        out[f"first_call_s_{label}"] = round(time.perf_counter() - t0, 3)
        out[f"info_{label}"] = {
            k: v for k, v in autotune.cache_info().items() if k != "keys"}
    flags.set_flags({"use_autotune": False, "autotune_cache_dir": ""})
    out["warm_start_reduction"] = round(
        1 - out["first_call_s_warm"] / out["first_call_s_cold"], 3)
    return out


# recorded per-section gates: the promise each optimization must keep.
# A section whose metric lands below its floor (or which fails to run)
# makes stepbench exit nonzero so the verify pipeline catches the
# regression; --no-gate keeps the old report-only behavior.
GATES = {
    # floors sit below the measured steady-state wins (README table) by a
    # noise margin: CPU-mesh timings on a shared machine jitter +-15-20%,
    # and a gate that cries wolf gets --no-gate'd into uselessness
    "data_prefetch": ("speedup", 0.8),
    "reduce_bucketing": ("speedup", 0.8),
    "overlap": ("speedup_fine_vs_single", 1.15),
    "save_async": ("caller_latency_reduction", 0.2),
}


def check_gates(result: dict) -> list:
    failures = []
    for section, (metric, floor) in GATES.items():
        sec = result.get(section)
        if not isinstance(sec, dict) or "error" in sec:
            failures.append(f"{section}: section failed to run "
                            f"({(sec or {}).get('error', 'missing')})")
            continue
        val = sec.get(metric)
        if val is None or float(val) < floor:
            failures.append(f"{section}: {metric}={val} below gate {floor}")
    return failures


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--saves", type=int, default=5)
    ap.add_argument("--quick", action="store_true",
                    help="skip the subprocess compile-cache probe")
    ap.add_argument("--no-gate", action="store_true",
                    help="report only; do not fail on per-section gates")
    args = ap.parse_args()

    import jax

    result = {"tool": "stepbench", "backend": jax.default_backend(),
              "devices": len(jax.devices()),
              "ts": time.strftime("%Y-%m-%dT%H:%M:%S")}
    for name, fn in [
        ("data_prefetch", lambda: bench_data_phase(args.steps)),
        ("reduce_bucketing", lambda: bench_reduce_phase(args.steps)),
        ("overlap", lambda: bench_overlap(args.steps)),
        ("compute_dispatch", lambda: bench_dispatch(args.steps)),
        ("save_async", lambda: bench_save_phase(args.saves)),
        ("runtime_telemetry", lambda: bench_runtime_telemetry(args.steps)),
        ("autotune_cache", bench_autotune),
    ] + ([] if args.quick else [("compile_cache", bench_compile_cache)]):
        log(f"--- {name}")
        try:
            result[name] = fn()
            log(json.dumps(result[name]))
        except Exception as e:  # a broken phase must not erase the others
            import traceback

            traceback.print_exc()
            result[name] = {"error": f"{type(e).__name__}: {str(e)[:300]}"}
    failures = check_gates(result)
    result["gates"] = {s: {"metric": m, "floor": f}
                      for s, (m, f) in GATES.items()}
    result["gate_failures"] = failures
    print(json.dumps(result), flush=True)
    with open(os.path.join(_REPO, "STEPBENCH.jsonl"), "a") as f:
        f.write(json.dumps(result) + "\n")
    if failures and not args.no_gate:
        for msg in failures:
            log(f"GATE FAIL: {msg}")
        return 1
    return 0


if __name__ == "__main__":
    if len(sys.argv) >= 3 and sys.argv[1] == "--child-compile":
        sys.exit(child_compile(sys.argv[2]))
    sys.exit(main())
