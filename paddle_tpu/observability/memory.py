"""Memory observability: per-device HBM gauges + per-executable XLA cost
accounting.

Two vantage points, both production signals in the Gemma-on-TPU report
(arXiv 2605.25645 — per-device HBM and compiled-memory budgets are watched
live, not post-mortem):

  * runtime — `jax.Device.memory_stats()` per local device: live bytes,
    peak bytes, allocator limit. TPU/GPU runtimes report these; the CPU
    backend returns None, so the host process's RSS (live, from
    /proc/self/statm) and peak RSS (ru_maxrss) stand in — the gauges always
    exist, whatever the backend, so dashboards and tests are
    backend-agnostic. "Are we about to OOM" is
    `device_memory_bytes{kind="bytes_in_use"}` vs `{kind="bytes_limit"}`.
  * compile time — every AOT-compiled TrainStep executable reports its XLA
    cost analysis (flops, bytes accessed) and memory analysis (argument /
    output / temp / generated-code bytes). jit.trainer calls
    `note_executable` right after `.compile()`, so a recompile that doubles
    temp memory shows up as a gauge step BEFORE the OOM, and the telemetry
    event log records which compile did it.

`memory_report()` renders both into one report.
"""
from __future__ import annotations

import os
import time
from typing import Any, Dict, List, Optional

from .registry import gauge

_DEV_G = gauge("device_memory_bytes",
               "Per-device allocator stats (live/peak/limit bytes) from "
               "jax.Device.memory_stats().",
               labelnames=("device", "kind"))
_HOST_G = gauge("host_memory_bytes",
                "Host process memory (rss = live, peak_rss = high water).",
                labelnames=("kind",))
_EXE_B = gauge("executable_bytes",
               "Compiled-executable memory budget from XLA memory analysis.",
               labelnames=("what", "kind"))
_EXE_F = gauge("executable_flops",
               "FLOPs per invocation from XLA cost analysis.",
               labelnames=("what",))
_EXE_BA = gauge("executable_bytes_accessed",
                "Bytes accessed per invocation from XLA cost analysis.",
                labelnames=("what",))

# memory_stats() key -> our stable gauge label (runtimes vary slightly)
_STAT_KEYS = ("bytes_in_use", "peak_bytes_in_use", "bytes_limit",
              "largest_alloc_size", "pool_bytes")
_MEM_KINDS = ("argument", "output", "temp", "alias", "generated_code")


def host_memory_bytes() -> Dict[str, int]:
    """Live RSS + peak RSS of this process, portable-ish (Linux /proc for
    live, getrusage for peak; zeros where unsupported)."""
    out = {"rss": 0, "peak_rss": 0}
    try:
        with open("/proc/self/statm") as f:
            out["rss"] = int(f.read().split()[1]) * os.sysconf("SC_PAGE_SIZE")
    except (OSError, ValueError, IndexError):
        pass
    try:
        import resource

        # ru_maxrss is KiB on Linux, bytes on macOS
        peak = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
        out["peak_rss"] = peak * (1 if peak > 1 << 32 else 1024)
    except Exception:  # noqa: BLE001 — no resource module
        pass
    return out


def device_memory_stats() -> List[Dict[str, Any]]:
    """One entry per local device: raw memory_stats() (may be None on CPU)
    plus identifying fields."""
    out = []
    try:
        import jax

        for d in jax.local_devices():
            try:
                stats = d.memory_stats()
            except Exception:  # noqa: BLE001 — backend without support
                stats = None
            out.append({
                "device": str(d.id),
                "platform": getattr(d, "platform", "?"),
                "kind": getattr(d, "device_kind", "?"),
                "stats": stats,
            })
    except Exception:  # noqa: BLE001 — jax not importable in odd contexts
        pass
    return out


def update_memory_gauges() -> Dict[str, Any]:
    """Refresh `device_memory_bytes` / `host_memory_bytes` gauges; returns
    the summary dict. Cheap: one C call per device
    plus two procfs reads."""
    summary: Dict[str, Any] = {"ts": time.time(), "devices": [], "host": {}}
    for entry in device_memory_stats():
        stats = entry["stats"] or {}
        row = {"device": entry["device"], "platform": entry["platform"],
               "kind": entry["kind"]}
        for key in _STAT_KEYS:
            if key in stats:
                v = int(stats[key])
                row[key] = v
                _DEV_G.set(v, device=entry["device"], kind=key)
        summary["devices"].append(row)
    host = host_memory_bytes()
    for k, v in host.items():
        _HOST_G.set(v, kind=k)
    summary["host"] = host
    return summary


def _cost_dict(compiled) -> Dict[str, float]:
    """Normalize compiled.cost_analysis() across jax versions (dict, or a
    one-element list of dicts) down to the two portable figures."""
    try:
        ca = compiled.cost_analysis()
    except Exception:  # noqa: BLE001 — analysis unsupported on backend
        return {}
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    if not isinstance(ca, dict):
        return {}
    out = {}
    for key in ("flops", "bytes accessed"):
        try:
            v = float(ca.get(key, -1.0))
        except (TypeError, ValueError):
            continue
        if v >= 0:
            out[key.replace(" ", "_")] = v
    return out


def executable_analysis(compiled) -> Dict[str, Any]:
    """flops / bytes-accessed / memory budget of one compiled executable."""
    out: Dict[str, Any] = dict(_cost_dict(compiled))
    try:
        ma = compiled.memory_analysis()
    except Exception:  # noqa: BLE001
        ma = None
    if ma is not None:
        for kind in _MEM_KINDS:
            v = getattr(ma, f"{kind}_size_in_bytes", None)
            if v is not None:
                out[f"{kind}_bytes"] = int(v)
        total = sum(out.get(f"{k}_bytes", 0)
                    for k in ("argument", "output", "temp"))
        if total:
            out["total_bytes"] = total
    return out


def note_executable(what: str, compiled) -> Dict[str, Any]:
    """Record one compiled executable's budget into gauges + the event log.
    Called by jit.trainer right after AOT compile; never raises (a cost
    analysis must not break a compile that already succeeded)."""
    try:
        info = executable_analysis(compiled)
    except Exception:  # noqa: BLE001
        return {}
    if not info:
        return {}
    for kind in _MEM_KINDS + ("total",):
        v = info.get(f"{kind}_bytes")
        if v is not None:
            _EXE_B.set(v, what=what, kind=kind)
    if "flops" in info:
        _EXE_F.set(info["flops"], what=what)
    if "bytes_accessed" in info:
        _EXE_BA.set(info["bytes_accessed"], what=what)
    from . import telemetry  # late: telemetry refreshes gauges through us

    telemetry.get_telemetry().event("executable", what=what, **info)
    return info


def memory_report() -> Dict[str, Any]:
    """The full memory picture: device + host gauges
    refreshed now, plus every executable budget currently registered."""
    report = update_memory_gauges()
    exes: Dict[str, Dict[str, float]] = {}
    for metric, key_label in ((_EXE_B, "kind"), ):
        for labels, v in metric.samples():
            exes.setdefault(labels["what"], {})[labels[key_label]] = v
    for labels, v in _EXE_F.samples():
        exes.setdefault(labels["what"], {})["flops"] = v
    for labels, v in _EXE_BA.samples():
        exes.setdefault(labels["what"], {})["bytes_accessed"] = v
    report["executables"] = exes
    return report
