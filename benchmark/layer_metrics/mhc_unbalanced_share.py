"""Share of the residual mix's applications left unbalanced (see
mhc_unbalanced_share.json). A program without the counters, or one that ran
no application, gives None."""


def read(ctx, spec):
    try:
        from paddle_tpu.observability.registry import default_registry
    except ImportError:
        return None
    reg = default_registry()
    bad, runs = reg.get(spec["unbalanced"]), reg.get(spec["applications"])
    if bad is None or runs is None or not runs.total():
        return None
    return 100.0 * bad.total() / runs.total()
