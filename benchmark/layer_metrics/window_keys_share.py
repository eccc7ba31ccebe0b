"""Keys read in window layers over the contexts' keys (see
window_keys_share.json). A program without the counter gives None."""


def read(ctx, spec):
    try:
        from paddle_tpu.observability.registry import default_registry
    except ImportError:
        return None
    c = default_registry().get(spec["counter"])
    if c is None:
        return None
    num, den = c.value(kind=spec["num"]), c.value(kind=spec["den"])
    return 100.0 * num / den if num > 0 and den > 0 else None
