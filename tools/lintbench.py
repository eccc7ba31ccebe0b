"""Lint benchmark for the static analyzer (ISSUE r8).

Two halves, both trace-only and CPU-safe (a few seconds total):

  * presets  — lint every model-zoo preset (gpt llama bert pallas) with all
               rules; the acceptance bar is ZERO findings. Any ERROR-severity
               finding that is not in the checked-in baseline
               (tools/LINTBENCH_BASELINE.json) fails the run.
  * detect   — run each rule against a synthetic program written to trip
               exactly that rule; a rule that stays silent on its own
               positive fails the run (the analyzer regressed).

Writes one JSON artifact (default LINTBENCH_r08.json at the repo root) and
exits nonzero when either half fails, so the verify pipeline can gate on it.

Usage: python tools/lintbench.py [--out LINTBENCH_r08.json] [--update-baseline]
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# a CPU tool: pin the platform (and the 8-device host mesh) before jax loads
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import numpy as np

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_BASELINE = os.path.join(_REPO, "tools", "LINTBENCH_BASELINE.json")


# --------------------------------------------------------------------------
# detection corpus: one deliberately-broken program per rule
# --------------------------------------------------------------------------

def _bad_corpus():
    """[(rule_id, thunk -> Report)] — each thunk lints a program written to
    trip exactly that rule."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu import analysis

    def collective():
        return analysis.analyze(
            lambda x: jax.lax.psum(x, "nonexistent_axis"),
            np.ones((4,), np.float32))

    def dtype():
        return analysis.analyze(
            lambda x: jnp.sum(x), np.ones((4,), np.float64))

    def recompile():
        return analysis.analyze(
            lambda s, x: x * s, 3.0, np.ones((4,), np.float32))

    def donation():
        return analysis.analyze(
            lambda a, b: jnp.sum(b),
            np.ones((8,), np.float32), np.ones((8,), np.float32),
            donate_argnums=(0,))

    def deadcode():
        def bad(x, w):
            _ = x @ w  # heavy computation that reaches no output
            return jnp.sum(x)
        return analysis.analyze(
            bad, np.ones((4, 4), np.float32), np.ones((4, 4), np.float32))

    def syncpoint():
        def bad(x):
            jax.debug.print("x={x}", x=x)
            return x + 1
        return analysis.analyze(bad, np.ones((4,), np.float32))

    def pallas():
        from jax.experimental import pallas as pl

        def kern(x_ref, o_ref):
            o_ref[...] = x_ref[...]

        def bad(x):
            return pl.pallas_call(
                kern,
                out_shape=jax.ShapeDtypeStruct((128, 100), jnp.float32),
                grid=(1,),
                in_specs=[pl.BlockSpec((128, 100), lambda i: (0, 0))],
                out_specs=pl.BlockSpec((128, 100), lambda i: (0, 0)),
            )(x)
        return analysis.analyze(bad, np.ones((128, 200), np.float32))

    def prefetch():
        def bad(x):
            jax.debug.print("step={x}", x=x)
            return x * 2
        return analysis.analyze(bad, np.ones((4,), np.float32),
                                context={"prefetch_active": True})

    def ppermute_partial():
        # a perm that is NOT a bijection over the axis: missing devices
        # receive zeros — the silent-wrong-result shape the rule warns on
        return analysis.analyze(
            lambda x: jax.lax.ppermute(x, "dp", [(0, 1)]),
            np.ones((4,), np.float32), axis_env={"dp": 8})

    return [
        ("collective-axis", "collective-axis", collective),
        ("collective-axis", "ppermute-partial-perm", ppermute_partial),
        ("dtype-promotion", "dtype-promotion", dtype),
        ("recompile-hazard", "recompile-hazard", recompile),
        ("donation", "donation", donation),
        ("dead-output", "dead-output", deadcode),
        ("host-sync", "host-sync", syncpoint),
        ("pallas-tiling", "pallas-tiling", pallas),
        ("prefetch-effects", "prefetch-effects", prefetch),
    ]


def _good_corpus():
    """[(rule_id, label, thunk -> Report)] — false-positive guards: programs
    that must lint CLEAN for the given rule."""
    from paddle_tpu import analysis

    def ppermute_ring():
        # a decomposed ring all-reduce is 2*(world-1) full-cycle ppermutes
        # over a bound axis (distributed/overlap.py): real communication,
        # zero findings expected — neither no-op nor zero-fill warnings
        from paddle_tpu.distributed import overlap

        return analysis.analyze(
            lambda x: overlap.ring_all_reduce(x, "dp", world=8),
            np.ones((64,), np.float32), axis_env={"dp": 8})

    return [
        ("collective-axis", "ppermute-ring-chain", ppermute_ring),
    ]


def run_detect():
    rows = []
    ok = True
    for rule_id, label, thunk in _bad_corpus():
        try:
            report = thunk()
            hits = [f for f in report.findings if f.rule == rule_id]
            detected = bool(hits)
            msg = hits[0].message if hits else "(no finding with this rule)"
        except Exception as e:  # a crashing positive is also a regression
            detected, msg = False, f"{type(e).__name__}: {e}"
        ok &= detected
        rows.append({"rule": rule_id, "label": label, "detected": detected,
                     "detail": msg})
        print(f"  detect {label:22s} {'OK' if detected else 'MISSED'}")
    return ok, rows


def run_negatives():
    rows = []
    ok = True
    for rule_id, label, thunk in _good_corpus():
        try:
            report = thunk()
            hits = [f for f in report.findings if f.rule == rule_id]
            clean = not hits
            msg = hits[0].message if hits else ""
        except Exception as e:  # a crashing negative is also a failure
            clean, msg = False, f"{type(e).__name__}: {e}"
        ok &= clean
        rows.append({"rule": rule_id, "label": label, "clean": clean,
                     "detail": msg})
        print(f"  negative {label:20s} {'OK' if clean else 'FALSE POSITIVE'}")
    return ok, rows


# --------------------------------------------------------------------------
# presets + baseline
# --------------------------------------------------------------------------

def _finding_key(target, f):
    """Stable identity for baseline comparison: eqn indices shift with any
    model edit, so key on (target, rule, primitive, source-basename)."""
    src = os.path.basename((f.source or "").split(":")[0])
    return f"{target}|{f.rule}|{f.primitive or ''}|{src}"


def run_presets():
    from paddle_tpu.analysis import Severity
    from paddle_tpu.analysis.presets import lint_presets

    rows = lint_presets()
    out = []
    error_keys = []
    total = 0
    for label, report in rows:
        out.append(report.to_dict())
        total += len(report.findings)
        for f in report.findings:
            if f.severity >= Severity.ERROR:
                error_keys.append(_finding_key(label, f))
        status = "clean" if not report.findings else \
            f"{len(report.findings)} finding(s)"
        print(f"  lint {label:28s} {status}")
    return out, error_keys, total


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=os.path.join(_REPO, "LINTBENCH_r08.json"))
    ap.add_argument("--update-baseline", action="store_true",
                    help="rewrite tools/LINTBENCH_BASELINE.json from this run")
    args = ap.parse_args(argv)

    print("== detect: every rule fires on its synthetic positive ==")
    detect_ok, detect_rows = run_detect()

    print("== negatives: known-good shapes must lint clean ==")
    negative_ok, negative_rows = run_negatives()

    print("== presets: model zoo must lint clean ==")
    preset_rows, error_keys, total = run_presets()

    if args.update_baseline:
        with open(_BASELINE, "w") as f:
            json.dump({"error_findings": sorted(error_keys)}, f, indent=2)
            f.write("\n")
        print(f"baseline rewritten: {len(error_keys)} ERROR finding(s)")
    try:
        with open(_BASELINE) as f:
            baseline = set(json.load(f).get("error_findings", []))
    except FileNotFoundError:
        baseline = set()

    new_errors = sorted(set(error_keys) - baseline)
    ok = detect_ok and negative_ok and not new_errors

    result = {
        "bench": "lintbench", "issue": "r08",
        "detect": detect_rows,
        "negatives": negative_rows,
        "presets": preset_rows,
        "preset_findings_total": total,
        "new_error_findings": new_errors,
        "baseline_error_findings": sorted(baseline),
        "ok": ok,
    }
    with open(args.out, "w") as f:
        json.dump(result, f, indent=2)
        f.write("\n")

    print(f"\npresets: {total} finding(s); "
          f"new ERROR findings vs baseline: {len(new_errors)}")
    if new_errors:
        for k in new_errors:
            print(f"  NEW ERROR: {k}")
    if not detect_ok:
        print("  DETECTION REGRESSION: a rule missed its synthetic positive")
    if not negative_ok:
        print("  FALSE POSITIVE: a rule fired on a known-good program")
    print(f"wrote {args.out}  ok={ok}")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
