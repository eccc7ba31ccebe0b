"""From a profiler trace (.xplane.pb) to numbers: device busy and idle time,
the durations of named XLA modules, the time of named device operations, the
top operations and the longest idle gaps. Read with jax.profiler.ProfileData
and nothing else. The benchmark brackets the traced period with a host
annotation, WINDOW, so the window is the period asked for and not the span
between the first and the last device operation."""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

WINDOW = "benchmark_traced_window"
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def find_xplane(trace_dir: str):
    hits = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                         "*", "*.xplane.pb")))
    return hits[-1] if hits else None


def union_seconds(intervals, lo=None, hi=None):
    """Total length of the union of [start, end) intervals (ns), clipped to
    [lo, hi], in seconds; and the gaps between them as (start, end) ns."""
    ivs = []
    for s, e in intervals:
        if lo is not None:
            s, e = max(s, lo), max(e, lo)
        if hi is not None:
            s, e = min(s, hi), min(e, hi)
        if e > s:
            ivs.append((s, e))
    ivs.sort()
    busy, gaps = 0.0, []
    cur_s = cur_e = None
    for s, e in ivs:
        if cur_e is None:
            cur_s, cur_e = s, e
        elif s <= cur_e:
            cur_e = max(cur_e, e)
        else:
            busy += cur_e - cur_s
            gaps.append((cur_e, s))
            cur_s, cur_e = s, e
    if cur_e is not None:
        busy += cur_e - cur_s
        if lo is not None and ivs[0][0] > lo:
            gaps.insert(0, (lo, ivs[0][0]))
        if hi is not None and cur_e < hi:
            gaps.append((cur_e, hi))
    return busy / 1e9, gaps


def _events(line):
    return [(e.name, float(e.start_ns), float(e.start_ns) + float(e.duration_ns))
            for e in line.events]


def load(path: str) -> dict:
    """{"devices": [{"name", "ops": [(name, s, e)], "modules": [...]}],
    "window": (s, e) or None}: the raw events the reductions work on."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices, window = [], None
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {ln.name: ln for ln in plane.lines}
            ops = _events(lines[OPS_LINE]) if OPS_LINE in lines else []
            mods = _events(lines[MODULES_LINE]) if MODULES_LINE in lines else []
            devices.append({"name": plane.name, "ops": ops, "modules": mods})
        else:
            for ln in plane.lines:
                for e in ln.events:
                    if e.name == WINDOW:
                        window = (float(e.start_ns),
                                  float(e.start_ns) + float(e.duration_ns))
    return {"devices": devices, "window": window}


def read_and_remove(trace_dir: str, describe_to=None) -> dict:
    """Reduce the trace under trace_dir, then delete it (traces are large
    and the host keeps every block once written)."""
    import shutil

    path = find_xplane(trace_dir)
    red = {}
    if path:
        if describe_to:
            os.makedirs(os.path.dirname(os.path.abspath(describe_to)), exist_ok=True)
            with open(describe_to, "w") as f:
                f.write(describe(path))
        red = reduce(load(path))
    shutil.rmtree(trace_dir, ignore_errors=True)
    return red


def reduce(raw: dict, top=10) -> dict:
    """busy_s (mean over devices of the union of operation intervals inside
    the window), window_s, per-module durations, per-operation totals, the
    top operations and the idle gaps grouped by the modules around them."""
    devs = raw["devices"]
    if not devs or not any(d["ops"] for d in devs):
        return {}
    win = raw.get("window")
    if win is None:
        starts = [s for d in devs for _, s, _ in d["ops"]]
        ends = [e for d in devs for _, _, e in d["ops"]]
        win = (min(starts), max(ends))
    lo, hi = win
    busy, op_tot, mod_dur = [], defaultdict(float), defaultdict(list)
    gap_tot = defaultdict(float)
    for d in devs:
        b, gaps = union_seconds([(s, e) for _, s, e in d["ops"]], lo, hi)
        busy.append(b)
        for name, s, e in d["ops"]:
            if e > lo and s < hi:
                op_tot[name] += (min(e, hi) - max(s, lo)) / 1e9
        mods = sorted((s, e, n) for n, s, e in d["modules"])
        for s, e, n in mods:
            if s >= lo and e <= hi:
                mod_dur[n].append((e - s) / 1e9)
        for gs, ge in gaps:
            before = next((n for s, e, n in reversed(mods) if e <= gs + 1), None)
            after = next((n for s, e, n in mods if s >= ge - 1), None)
            inside = next((n for s, e, n in mods if s <= gs and e >= ge), None)
            label = (f"inside {short(inside)}" if inside else
                     f"after {short(before)} before {short(after)}")
            gap_tot[label] += (ge - gs) / 1e9
    n = len(devs)
    return {
        "busy_s": sum(busy) / n,
        "window_s": (hi - lo) / 1e9,
        "modules": dict(mod_dur),
        "ops": dict(op_tot),
        "device_ops": [[k, v / n] for k, v in sorted(
            op_tot.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[k, v / n] for k, v in sorted(
            gap_tot.items(), key=lambda kv: -kv[1])[:top]],
    }


def short(name):
    """'jit_step(1234567)' -> 'jit_step'."""
    return re.sub(r"\(\d+\)$", "", name) if name else "the window's edge"


def module_durations(red: dict, pattern: str) -> list:
    rx = re.compile(pattern)
    return [d for name, ds in red.get("modules", {}).items()
            if rx.search(name) for d in ds]


def op_seconds(red: dict, pattern: str) -> float:
    rx = re.compile(pattern)
    return sum(v for k, v in red.get("ops", {}).items() if rx.search(k))


def describe(path: str, top=40) -> str:
    """What a trace holds, for a look by hand: planes, lines, most frequent
    and longest event names with a sample of their stats."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        out.append(f"PLANE {plane.name}")
        for ln in plane.lines:
            tot, cnt, stats = defaultdict(float), defaultdict(int), {}
            for e in ln.events:
                tot[e.name] += float(e.duration_ns)
                cnt[e.name] += 1
                if e.name not in stats:
                    stats[e.name] = [(k, str(v)[:80]) for k, v in list(e.stats)[:8]]
            out.append(f"  LINE {ln.name}: {sum(cnt.values())} events, "
                       f"{len(cnt)} names")
            for name, t in sorted(tot.items(), key=lambda kv: -kv[1])[:top]:
                out.append(f"    {t / 1e6:12.3f} ms  x{cnt[name]:<6d} {name[:100]}"
                           f"  {stats[name]}")
    return "\n".join(out)
