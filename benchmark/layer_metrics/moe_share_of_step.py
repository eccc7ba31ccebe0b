"""The routed experts' share of the programs that run them (see
moe_share_of_step.json)."""
from benchmark.harness import trace


def read(ctx, spec):
    red = ctx.get("trace") or {}
    inside = trace.op_seconds(red, spec["ops"])
    whole = sum(trace.module_durations(red, spec["module"]))
    return 100.0 * inside / whole if inside > 0 and whole > 0 else None
