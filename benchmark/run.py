"""One run of one cell of BENCHMARK.json.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process, which owns the chip(s). Fails, printing no result, when jax
finds no TPU or fewer chips than the cell asks for. Makes its inputs and
weights from --seed, warms up the cell's own shapes (set-up), measures for
--seconds, checks what the timed path produced against the plain reference,
and prints the result as the last line of stdout: with --trace 0 the cell's
end-to-end metrics, with --trace 1 its per-layer metrics.

A cell is data: its configuration (configs/), traffic mix (traffic/), limits
(limits/) and per-layer metrics (layer_metrics/) are files found by the names
in BENCHMARK.json; the one driver per traffic kind is in harness/, and a kind
that DRIVERS does not list is driven by harness/drive_<kind>.py.
"""
from __future__ import annotations

import time

T_START = time.monotonic()      # process start, as near as Python can say

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

DRIVERS = {"train_steps": "drive_train", "open_loop": "drive_serve",
           "closed_loop": "drive_serve"}
# scratch for the profiler's trace, inside the checkout, removed after reading
WORK_DIR = os.path.join(ROOT, ".bench_work")


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def driver_for(kind: str):
    """The driver module of a traffic kind: DRIVERS' entry, else
    harness/drive_<kind>.py, which a later PR brings with its kind."""
    import importlib

    name = "benchmark.harness." + DRIVERS.get(kind, f"drive_{kind}")
    try:
        return importlib.import_module(name)
    except ModuleNotFoundError as e:
        if not (e.name and name.startswith(e.name)):
            raise               # the driver is there; something it imports is not
        raise SystemExit(f"unknown traffic kind {kind!r}: known are "
                         f"{sorted(DRIVERS)}, and there is no "
                         f"benchmark/harness/drive_{kind}.py") from None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    # for the tests' rehearsal only: another manifest, and the CPU allowed
    # (its result names the CPU; the driver never passes these)
    ap.add_argument("--manifest", default=None)
    ap.add_argument("--rehearse-on-cpu", action="store_true")
    # for the builder's readings on the chip: also compute the control (the
    # reference one precision down) and the planted faults, under "notes";
    # and write what the trace holds to a file, for a look by hand
    ap.add_argument("--with-control", action="store_true")
    ap.add_argument("--describe-trace", default=None)
    ap.add_argument("--rate", type=float, default=None,
                    help="the builder's sweep for the knee: an open loop's "
                         "rate other than the mix's")
    args = ap.parse_args(argv)

    from benchmark.harness import correct, device, manifest, readers

    bench = manifest.benchmark() if args.manifest is None else \
        json.load(open(args.manifest))
    cell = manifest.cell(args.workload, bench)
    cell["debug"] = {"control": args.with_control,
                     "describe_trace": args.describe_trace}
    if args.rate is not None:
        cell["traffic"]["rate_per_s"] = args.rate
    driver = driver_for(cell["traffic"]["kind"])     # fails by name, before jax
    devs = device.require_chips(cell["chips"], allow_cpu=args.rehearse_on_cpu)
    if devs[0].platform == "tpu":
        cell["peaks"] = device.peaks_for(devs[0].device_kind)
    else:   # rehearsal: shares of these mean nothing and say so
        cell["peaks"] = {"flops_per_s": 1e12, "bytes_per_s": 1e11,
                         "source": "rehearsal on the CPU, not a peak"}

    from paddle_tpu.jit import enable_persistent_cache

    cache_dir = enable_persistent_cache()   # $JAX_COMPILATION_CACHE_DIR, else
    os.makedirs(WORK_DIR, exist_ok=True)    # the fixed .jax_cache/ in the checkout
    log(f"[{cell['name']}] {devs[0].device_kind} x{len(devs)}, seed {args.seed}, "
        f"{args.seconds} s, trace {args.trace}, compile cache {cache_dir}")

    res = driver.run(cell, args.seed, args.seconds, bool(args.trace), devs,
                     T_START, WORK_DIR)

    if args.trace:
        metrics = readers.read_all(cell["per_layer"], res["ctx"])
    else:
        units = {m["name"]: m["unit"] for m in cell["end_to_end"]}
        metrics = {k: {"value": float(v), "unit": units[k]}
                   for k, v in res["end_to_end"].items()
                   if k in units and v is not None}
    dev = dict(res["device"])
    line = {"correct": bool(res["correct"]), "attempted": int(res["attempted"]),
            "failed": int(res["failed"]), "metrics": metrics, "device": dev}
    red = res["ctx"].get("trace") or {}
    if args.trace and red:
        dev["busy_s"], dev["window_s"] = red["busy_s"], red["window_s"]
        line["breakdown"] = {"device_ops": red["device_ops"],
                             "idle_gaps": red["idle_gaps"]}
    line["notes"] = res.get("notes", {})
    line["checks"] = res["checks"]          # each number beside its limit, last
    log(json.dumps({"notes": line["notes"], "counters": res["ctx"]["counters"],
                    "work": res["ctx"]["work"]}, default=str))
    correct.report(res["checks"], line["correct"])
    print(json.dumps(line, default=float), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
