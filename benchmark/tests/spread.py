"""By hand: read the runs that full_sets.sh left and print, for each metric,
each set's median and spread (the distance between the first and the third
quartile as statistics.quantiles(values, n=4) gives them, over the median),
the bound that five times the wider spread gives, and every number that
`correct` compared.

    python benchmark/tests/spread.py chiprun_out <workload>
"""
import glob
import json
import statistics
import sys


def last_line(path):
    lines = open(path).read().strip().splitlines()
    return json.loads(lines[-1]) if lines else None


def main(d, w):
    sets = {}
    for path in sorted(glob.glob(f"{d}/{w}.*.out")):
        tag = path.split("/")[-1][len(w) + 1:].split(".")[0]
        line = last_line(path)
        if line is None:
            print("no result in", path)
            continue
        sets.setdefault(tag, []).append((path, line))
    for tag, runs in sets.items():
        print(f"== set {tag}: {len(runs)} runs, correct "
              f"{[r['correct'] for _, r in runs]}")
        names = sorted({k for _, r in runs for k in r["metrics"]})
        for k in names:
            vals = [r["metrics"][k]["value"] for _, r in runs if k in r["metrics"]]
            med = statistics.median(vals)
            if len(vals) >= 2:
                q = statistics.quantiles(vals, n=4)
                spread = (q[2] - q[0]) / med if med else float("nan")
            else:
                spread = float("nan")
            print(f"  {k:22s} median {med:12.4f}  spread {100 * spread:6.2f}%  "
                  f"5x {100 * 5 * spread:6.2f}%  values "
                  + " ".join(f"{v:.4g}" for v in vals))
        checks = sorted({k for _, r in runs for k in r["checks"]})
        for k in checks:
            vals = [r["checks"][k][0] for _, r in runs]
            print(f"  check {k:16s} max {max(vals):.4g}  values "
                  + " ".join(f"{v:.3g}" for v in vals))
        for key in ("control_fp8", "fault_half_batch", "control_fp8_gaps", "gaps"):
            vals = [r["notes"][key] for _, r in runs if key in r.get("notes", {})]
            if vals:
                print(f"  notes {key}: {vals}")
        mem = [r["device"]["memory_peak_bytes"] for _, r in runs]
        print(f"  memory_peak_bytes {max(mem)}")
        busy = [(r["device"].get("busy_s"), r["device"].get("window_s")) for _, r in runs]
        if any(b for b, _ in busy):
            print(f"  busy_s, window_s {busy}")


if __name__ == "__main__":
    main(sys.argv[1], sys.argv[2])
