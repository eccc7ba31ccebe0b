"""The delta-rule kernels' share of the device's busy time (see
kda_share_of_busy.json)."""
from benchmark.harness import trace


def read(ctx, spec):
    red = ctx.get("trace") or {}
    inside = trace.op_seconds(red, spec["ops"])
    busy = red.get("busy_s")
    return 100.0 * inside / busy if inside > 0 and busy else None
