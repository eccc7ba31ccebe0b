"""The whole decode step's share of the chip's peak (see mfu.decode.json)."""
from benchmark.harness import trace


def read(ctx, spec):
    work = (ctx.get("work") or {}).get(spec["work"])
    secs = sum(trace.module_durations(ctx.get("trace") or {}, spec["module"]))
    if not work or not work["flops"] or secs <= 0:
        return None
    return 100.0 * work["flops"] / (
        secs * ctx["chips"] * ctx["peaks"]["flops_per_s"])
