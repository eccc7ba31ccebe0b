"""Readers of the per-layer metrics that stand on the program's own names:
its span ring (paddle_tpu.observability.spans: every span with an `id` and
the `parent` that enclosed it), and the names it gives its XLA modules and
Pallas kernels in the device trace. A metric's layer_metrics/<name>.py
takes its `read` from here.

Span metrics read the ring in-process after a traced window: with
FLAGS_metrics off, as in every benchmark run, the program records spans
only while a jax profiler session is live, so the ring holds the spans of
the traced session and no others. A program that has no ring, or spans
without `id` and `parent`, or modules and kernels under other names (the
parent of the PR that brought these) gives None, never 0 and never an
error."""
from __future__ import annotations

from . import stats, trace


def ring(ctx) -> list:
    """The program's spans of the traced session; [] in a run that traced
    nothing, or over a program without a ring."""
    if not (ctx.get("trace") or {}).get("window_s"):
        return []
    try:
        from paddle_tpu.observability import spans
    except ImportError:
        return []
    return [s for s in spans.since(0) if s.get("id") is not None]


def _ms(span) -> float:
    return (span["end_ns"] - span["begin_ns"]) / 1e6


def _children_ms(spans, parent_name, child_name):
    """[(parent's ms, its children's ms summed)] for every `parent_name`
    span, children being the `child_name` spans whose `parent` it is."""
    inside = {}
    for s in spans:
        if s["name"] == child_name and s.get("parent") is not None:
            inside[s["parent"]] = inside.get(s["parent"], 0.0) + _ms(s)
    return [(_ms(s), inside.get(s["id"], 0.0))
            for s in spans if s["name"] == parent_name]


def self_ms(ctx, spec):
    """Median over the `span` spans of duration minus the time of their
    `minus` children: the span's own time on the host."""
    pairs = _children_ms(ring(ctx), spec["span"], spec["minus"])
    return stats.median([whole - part for whole, part in pairs])


def child_share(ctx, spec):
    """Time of the `child` spans inside `span` spans over the time of
    those spans, in percent."""
    pairs = _children_ms(ring(ctx), spec["span"], spec["child"])
    whole = sum(w for w, _ in pairs)
    return 100.0 * sum(p for _, p in pairs) / whole if whole > 0 else None


def module_rest_ms(ctx, spec):
    """Median device time of the `module` XLA modules minus the time of
    the `ops` operations over the number of those modules: what a program
    spends outside one named kernel, in ms."""
    red = ctx.get("trace") or {}
    ds = trace.module_durations(red, spec["module"])
    inside = trace.op_seconds(red, spec["ops"])
    if not ds or inside <= 0:
        return None
    return 1e3 * (stats.median(ds) - inside / len(ds))


def module_share_of_busy(ctx, spec):
    """Device time of the `module` XLA modules over the device's busy
    time in the traced window, in percent."""
    red = ctx.get("trace") or {}
    ds = trace.module_durations(red, spec["module"])
    if not ds or not red.get("busy_s"):
        return None
    return 100.0 * sum(ds) / red["busy_s"]


def op_share(ctx, spec):
    """Device time of the `num` operations over that of the `den`
    operations, in percent."""
    red = ctx.get("trace") or {}
    den = trace.op_seconds(red, spec["den"])
    num = trace.op_seconds(red, spec["num"])
    return 100.0 * num / den if den > 0 and num > 0 else None
