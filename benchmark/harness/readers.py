"""The general readers of per-layer metrics. A metric is its data file
layer_metrics/<name>.json: which reader, and the counter, clock, module or
operation pattern it reads. A reader that finds nothing to read returns
None and the harness leaves the metric out of the line; it never returns 0
for a share of a roofline or of a peak. A metric that needs code of its own
brings layer_metrics/<name>.py with read(ctx, spec)."""
from __future__ import annotations

from . import manifest, stats, trace, workmodel


def value(ctx, spec):
    v = ctx.get(spec["from"], {}).get(spec["key"])
    return None if v is None else float(v) * float(spec.get("scale", 1))


def ratio(ctx, spec):
    src = ctx.get(spec.get("from", "counters"), {})
    num, den = src.get(spec["num"]), src.get(spec["den"])
    if num is None or not den:
        return None
    return float(num) / float(den) * float(spec.get("scale", 1))


def trace_idle(ctx, spec):
    red = ctx.get("trace") or {}
    if not red.get("window_s") or not red.get("busy_s"):
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])


def trace_module_ms(ctx, spec):
    ds = trace.module_durations(ctx.get("trace") or {}, spec["pattern"])
    if not ds:
        return None
    pick = {"median": stats.median, "max": max, "min": min,
            "sum": sum}[spec.get("stat", "median")]
    return 1e3 * pick(ds)


def kernel_roofline(ctx, spec):
    """The least time the chip could take for the kernel's work in the traced
    window (the larger of operations over peak FLOP/s and bytes over peak
    bytes/s) over the device time of the kernel's events there."""
    red = ctx.get("trace") or {}
    work = (ctx.get("work") or {}).get(spec["work"])
    secs = trace.op_seconds(red, spec["pattern"])
    if not work or secs <= 0:
        return None
    least, _ = workmodel.roofline_seconds(work, ctx["peaks"])
    return 100.0 * least / secs


def mfu(ctx, spec):
    """Model operations of the whole window over window x chips x peak."""
    c = ctx.get("counters", {})
    flops, secs = c.get(spec["flops"]), c.get(spec["seconds"])
    if not flops or not secs:
        return None
    return 100.0 * flops / (secs * ctx["chips"] * ctx["peaks"]["flops_per_s"])


READERS = {"value": value, "ratio": ratio, "trace_idle": trace_idle,
           "trace_module_ms": trace_module_ms,
           "kernel_roofline": kernel_roofline, "mfu": mfu}


def read_all(metrics, ctx) -> dict:
    """{name: {"value", "unit"}} for every metric whose reader found
    something to read."""
    out = {}
    for m in metrics:
        spec, mod = manifest.layer_metric(m["name"])
        v = mod.read(ctx, spec) if mod is not None \
            else READERS[spec["reader"]](ctx, spec)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out
