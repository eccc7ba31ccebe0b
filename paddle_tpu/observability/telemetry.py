"""Per-step training telemetry.

The runtime itself emits one record per optimizer step — loss, grad
global-norm, learning rate, throughput (samples/s, tokens/s), estimated
MFU, per-phase wall times (data / compute / reduce / save), and compile /
recompile events — so benches and dashboards read phases from the live run
instead of re-timing them externally (the T3 / Gemma-on-TPU accounting).

Assembly protocol (who knows what, when):

  * the training loop times the DATA phase before the step and calls
    `pre_phase("data", dt)` — it lands on the NEXT record;
  * jit.TrainStep calls `on_step(core)` with loss / grad-norm / lr /
    compute time measured around its own dispatch; this STAGES the record
    (and pushes it, by reference, into the flight-recorder ring);
  * the loop times the SAVE phase after the step and calls
    `post_phase("save", dt)` — merged into the staged record;
  * the NEXT `on_step` (or `finalize()`) flushes the completed record to
    the JSONL event log, so late phases are never lost to the sink.

On the single-compiled-program path the gradient all-reduce is fused into
the step executable (XLA overlaps it with the backward — see
distributed/grad_buckets.py and distributed/overlap.py), so no
host-observable reduce wait exists. The `reduce` phase is instead the comm
cost jit.TrainStep ATTRIBUTES from inside the step: a standalone probe of
the step's own reduction schedule, carved out of `compute` so the phases
still sum to the measured step time; `reduce_overlapped` stays True to say
the time was attributed, not waited on.

Everything is inert while FLAGS_metrics is off: `enabled()` is one flag
read, and TrainStep checks it before building any record.
"""
from __future__ import annotations

import os
import threading
import time
from typing import Any, Dict, Optional

from . import flight_recorder, sinks
from .registry import (counter, default_registry, gauge, histogram,
                       metrics_enabled)
from ..core.flags import get_flag

PHASES = ("data", "compute", "reduce", "save")

_STEPS = counter("training_steps_total", "Optimizer steps executed.")
_SKIPPED = counter("training_steps_skipped_total",
                   "Steps skipped by the NaN/Inf step-guard.")
_LOSS = gauge("training_loss", "Loss of the most recent step.")
_GNORM = gauge("training_grad_norm",
               "Gradient global-norm of the most recent step (pre-clip).")
_LR = gauge("training_lr", "Learning rate of the most recent step.")
_SPS = gauge("training_samples_per_second", "Recent-step throughput.")
_TPS = gauge("training_tokens_per_second", "Recent-step token throughput.")
_MFU = gauge("training_mfu",
             "Estimated model FLOPs utilization of the most recent step.")
_PHASE_S = counter("training_phase_seconds_total",
                   "Cumulative wall time per step phase.",
                   labelnames=("phase",))
_PHASE_H = histogram("training_phase_seconds",
                     "Per-step wall time by phase.", labelnames=("phase",))
_COMPILES = counter("training_compile_events_total",
                    "Compile/recompile events observed by telemetry.",
                    labelnames=("kind",))

_PROM_EVERY = 50  # steps between Prometheus textfile rewrites (finalize()
                  # always writes one, so short runs still get a file)
_MEM_EVERY = 20   # steps between device/host memory-gauge refreshes (one
                  # C call per device + two procfs reads; see memory.py)


def enabled() -> bool:
    return metrics_enabled()


# Published per-chip peaks, keyed by jax's `device_kind`. A device that is
# not here has no MFU, not a default one.
#   "TPU v5 lite": Google Cloud documentation, "TPU v5e" — 197 TFLOP/s bf16,
#   16 GB HBM at 819 GB/s.
DEVICE_PEAKS = {
    "TPU v5 lite": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
}


def peak_flops(device_kind: Optional[str] = None) -> float:
    """Peak bf16 FLOP/s of `device_kind` (default: the first device jax
    reports). Raises for a device that is not in DEVICE_PEAKS."""
    if device_kind is None:
        import jax

        device_kind = jax.devices()[0].device_kind
    try:
        return DEVICE_PEAKS[device_kind]["flops_per_s"]
    except KeyError:
        raise ValueError(
            f"no published peak for device kind {device_kind!r}: MFU is "
            f"defined only for {sorted(DEVICE_PEAKS)}") from None


class StepTelemetry:
    """Process-wide per-step record assembler (get_telemetry() singleton)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._staged: Optional[Dict[str, Any]] = None
        self._pending_phases: Dict[str, float] = {}
        self._last_step_t: Optional[float] = None
        self._jsonl: Optional[sinks.JsonlEventLog] = None
        self._jsonl_dir: Optional[str] = None
        self._flushed = 0
        self.records_emitted = 0
        self._totals: Dict[str, float] = {p: 0.0 for p in PHASES}
        self._last: Dict[str, Any] = {}

    # -- sinks -------------------------------------------------------------
    def _metrics_dir(self) -> str:
        return str(get_flag("metrics_dir") or "")

    def _sink(self) -> Optional[sinks.JsonlEventLog]:
        d = self._metrics_dir()
        if not d:
            return None
        if self._jsonl is None or self._jsonl_dir != d:
            if self._jsonl is not None:
                self._jsonl.close()
            self._jsonl = sinks.JsonlEventLog(
                os.path.join(d, sinks.EVENTS_FILENAME))
            self._jsonl_dir = d
        return self._jsonl

    def export_prometheus(self) -> Optional[str]:
        d = self._metrics_dir()
        if not d:
            return None
        return sinks.write_prometheus_textfile(
            os.path.join(d, sinks.PROM_FILENAME), default_registry())

    # -- phase accounting --------------------------------------------------
    def pre_phase(self, name: str, seconds: float) -> None:
        """Phase time measured BEFORE the step it belongs to (data wait)."""
        if not enabled():
            return
        with self._lock:
            self._pending_phases[name] = \
                self._pending_phases.get(name, 0.0) + float(seconds)

    def post_phase(self, name: str, seconds: float) -> None:
        """Phase time measured AFTER its step (checkpoint save): merged into
        the staged record so it ships with the right step."""
        if not enabled():
            return
        s = float(seconds)
        with self._lock:
            staged = self._staged
            if staged is not None:
                staged["phases"][name] = staged["phases"].get(name, 0.0) + s
        _PHASE_S.inc(s, phase=name)
        _PHASE_H.observe(s, phase=name)
        self._totals[name] = self._totals.get(name, 0.0) + s

    # -- per-step core (called by jit.TrainStep) ---------------------------
    def on_step(self, core: Dict[str, Any]) -> Dict[str, Any]:
        """Stage the record for one completed step; flush the previous one.
        `core` must carry: step, loss, lr, compute_s; optional grad_norm,
        skipped, samples, tokens, flops."""
        now = time.perf_counter()
        with self._lock:
            prev, self._staged = self._staged, None
            phases = {p: 0.0 for p in PHASES}
            phases.update(self._pending_phases)
            self._pending_phases = {}
        if prev is not None:
            self._write(prev)

        compute_s = float(core.get("compute_s", 0.0))
        # `reduce_s` is the comm time the step ATTRIBUTES out of its own
        # measured wall (jit.TrainStep's reduce probe): the collective is
        # fused into the step program, so it is carved out of compute rather
        # than added on top — phases keep summing to the measured step time
        reduce_s = min(float(core.get("reduce_s", 0.0) or 0.0), compute_s)
        if reduce_s > 0.0:
            phases["reduce"] = phases.get("reduce", 0.0) + reduce_s
            compute_s -= reduce_s
        phases["compute"] = phases.get("compute", 0.0) + compute_s
        # wall time step->step covers data+compute+save of the interleave;
        # throughput/MFU use it when available (first step: compute only)
        step_wall = (now - self._last_step_t) if self._last_step_t else \
            max(compute_s, 1e-9)
        self._last_step_t = now

        rec: Dict[str, Any] = {
            "kind": "step",
            "ts": time.time(),
            "step": int(core["step"]),
            "loss": _f(core.get("loss")),
            "grad_norm": _f(core.get("grad_norm")),
            "lr": _f(core.get("lr")),
            "skipped": bool(core.get("skipped", False)),
            "phases": phases,
            "step_wall_s": round(step_wall, 6),
            "reduce_overlapped": bool(core.get("reduce_overlapped", True)),
        }
        samples = core.get("samples")
        tokens = core.get("tokens")
        if samples:
            rec["samples"] = int(samples)
            rec["samples_per_s"] = round(samples / step_wall, 3)
        if tokens:
            rec["tokens"] = int(tokens)
            rec["tokens_per_s"] = round(tokens / step_wall, 3)
        flops = core.get("flops")
        if flops:
            try:
                rec["mfu"] = round(float(flops) / step_wall / peak_flops(), 6)
            except ValueError:
                pass    # e.g. the CPU: the record carries no MFU
        for extra in ("autotune", "compile_cache", "prefetch"):
            if extra in core:
                rec[extra] = core[extra]

        # registry mirrors
        _STEPS.inc()
        if rec["skipped"]:
            _SKIPPED.inc()
        if rec["loss"] is not None:
            _LOSS.set(rec["loss"])
        if rec["grad_norm"] is not None:
            _GNORM.set(rec["grad_norm"])
        if rec["lr"] is not None:
            _LR.set(rec["lr"])
        if "samples_per_s" in rec:
            _SPS.set(rec["samples_per_s"])
        if "tokens_per_s" in rec:
            _TPS.set(rec["tokens_per_s"])
        if "mfu" in rec:
            _MFU.set(rec["mfu"])
        for p in ("data", "compute", "reduce"):
            if phases.get(p):
                _PHASE_S.inc(phases[p], phase=p)
                _PHASE_H.observe(phases[p], phase=p)
                self._totals[p] = self._totals.get(p, 0.0) + phases[p]

        with self._lock:
            self._staged = rec
            self._last = rec
        flight_recorder.get_flight_recorder().record_step(rec)
        if rec["step"] % _MEM_EVERY == 0:
            try:
                from . import memory as _memory

                _memory.update_memory_gauges()
            except Exception:  # noqa: BLE001 — gauges must not break steps
                pass
        return rec

    def last_record(self) -> Optional[Dict[str, Any]]:
        """The most recent staged step record (what the anomaly engine and
        cluster publisher read right after TrainStep returns). Late phase
        merges (save) mutate this dict in place."""
        with self._lock:
            return self._last or None

    def event(self, kind: str, **data) -> None:
        """Irregular event (compile, recompile, preemption...): written to
        the event log immediately and noted in the flight recorder."""
        if not enabled():
            return
        if kind in ("compile", "recompile"):
            _COMPILES.inc(kind=data.get("what", kind))
        rec = {"kind": str(kind), "ts": time.time()}
        rec.update(data)
        sink = self._sink()
        if sink is not None:
            sink.emit(rec)
        flight_recorder.get_flight_recorder().note(kind, **data)

    # -- flushing ----------------------------------------------------------
    def _write(self, rec: Dict[str, Any]) -> None:
        sink = self._sink()
        if sink is not None:
            sink.emit(rec)
        self.records_emitted += 1
        self._flushed += 1
        if self._flushed % _PROM_EVERY == 0:
            try:
                self.export_prometheus()
            except OSError:
                pass

    def finalize(self) -> None:
        """Flush the staged record and rewrite the Prometheus textfile —
        call at end of run (ResilientTrainer does)."""
        with self._lock:
            staged, self._staged = self._staged, None
        if staged is not None:
            self._write(staged)
        try:
            self.export_prometheus()
        except OSError:
            pass

    flush = finalize

    # -- summaries ---------------------------------------------------------
    def summary(self) -> Dict[str, Any]:
        """Aggregate view for bench outputs: mean per-phase ms + last-step
        throughput figures."""
        n = max(self.records_emitted +
                (1 if self._staged is not None else 0), 1)
        out: Dict[str, Any] = {
            "records": self.records_emitted,
            "phase_ms_avg": {p: round(self._totals.get(p, 0.0) / n * 1e3, 3)
                             for p in PHASES},
        }
        last = dict(self._last)
        for k in ("step", "loss", "grad_norm", "samples_per_s",
                  "tokens_per_s", "mfu"):
            if last.get(k) is not None:
                out[f"last_{k}"] = last[k]
        return out


def _f(v) -> Optional[float]:
    if v is None:
        return None
    try:
        return float(v)
    except (TypeError, ValueError):
        return None


_telemetry: Optional[StepTelemetry] = None
_telemetry_lock = threading.Lock()


def get_telemetry() -> StepTelemetry:
    global _telemetry
    with _telemetry_lock:
        if _telemetry is None:
            _telemetry = StepTelemetry()
        return _telemetry


def reset() -> None:
    """Fresh singleton (tests / new runs); closes the open event log."""
    global _telemetry
    with _telemetry_lock:
        if _telemetry is not None and _telemetry._jsonl is not None:
            _telemetry._jsonl.close()
        _telemetry = None
