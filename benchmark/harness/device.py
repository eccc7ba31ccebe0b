"""The device this process runs on: refuse anything but the chips the cell
asks for, name it in every result, look its peaks up (a device that is not
in peaks.json is an error, never a default)."""
from __future__ import annotations

import json
import os

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")


def peaks_for(kind: str) -> dict:
    with open(PEAKS_FILE) as f:
        table = json.load(f)
    if kind not in table:
        raise SystemExit(f"device kind {kind!r} is not in {PEAKS_FILE}; "
                         "add it with its source, there is no default")
    return table[kind]


def require_chips(chips: int, allow_cpu: bool = False):
    """jax's devices, or exit nonzero: no accelerator, or fewer chips than
    the cell asks for. allow_cpu is for the tests' rehearsal only; its
    result names the CPU."""
    import jax

    devs = jax.devices()
    plat = devs[0].platform
    if plat != "tpu" and not allow_cpu:
        raise SystemExit(f"the benchmark measures on a TPU and jax found "
                         f"{plat!r} ({devs[0].device_kind}); nothing measured")
    if plat == "tpu" and len(devs) < chips:
        raise SystemExit(f"the cell asks for {chips} chip(s) and jax found "
                         f"{len(devs)}; nothing measured")
    return devs[:chips] if plat == "tpu" else devs[:1]


def describe(devs, extra=None) -> dict:
    peak = 0
    for d in devs:
        st = d.memory_stats() or {}
        peak = max(peak, int(st.get("peak_bytes_in_use", 0)))
    out = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs), "memory_peak_bytes": peak}
    out.update(extra or {})
    return out
