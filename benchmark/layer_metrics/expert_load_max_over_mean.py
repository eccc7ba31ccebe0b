"""Busiest held expert over the mean (see expert_load_max_over_mean.json).
A program without the gauge gives None."""


def read(ctx, spec):
    try:
        from paddle_tpu.observability.registry import default_registry
    except ImportError:
        return None
    gauge = default_registry().get(spec["gauge"])
    vals = [v for _, v in gauge.samples()] if gauge is not None else []
    return sum(vals) / len(vals) if vals else None
