"""Memory observability report (ISSUE r10).

One-shot snapshot of everything observability/memory.py can see on this
host: per-device allocator stats (HBM on TPU/GPU, host-RSS stand-ins on
CPU), host process memory, and — after compiling one small TrainStep the
way jit/trainer.py's AOT path does — the XLA cost/memory analysis of that
executable (flops, bytes accessed, argument/output/temp/generated-code
bytes). The point is validating the whole pipe end-to-end on any backend:
the same gauges a real run exports per scrape are what this prints.

Usage: python tools/memwatch.py [--json] [--out MEMWATCH.json] [--no-compile]
Exit 0 when the report is complete (device + host sections always; the
executable section unless --no-compile), nonzero otherwise.
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def _fmt_bytes(n):
    for unit in ("B", "KiB", "MiB", "GiB", "TiB"):
        if abs(n) < 1024 or unit == "TiB":
            return f"{n:.1f} {unit}" if unit != "B" else f"{int(n)} B"
        n /= 1024.0


def compile_probe():
    """Build + AOT-compile a tiny TrainStep the way the fast-dispatch path
    does (jit/trainer.py calls note_executable right after .compile()), then
    return what memory.py recorded for it."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu import optimizer
    from paddle_tpu.core import flags
    from paddle_tpu.jit.trainer import TrainStep
    from paddle_tpu.models import GPTConfig, GPTForCausalLM
    from paddle_tpu.observability import memory as obs_memory

    flags.set_flags({"jit_fast_dispatch": True})
    cfg = GPTConfig(vocab_size=256, hidden_size=64, num_layers=2,
                    num_heads=2, hidden_dropout_prob=0.0,
                    attention_dropout_prob=0.0)
    paddle.seed(0)
    model = GPTForCausalLM(cfg)
    opt = optimizer.AdamW(1e-4, parameters=model.parameters())
    step = TrainStep(model, lambda ids: model(ids, labels=ids), opt)
    ids = paddle.to_tensor(np.random.RandomState(0).randint(
        0, cfg.vocab_size, (4, 32)).astype(np.int32))
    float(step(ids).item())  # AOT compile happens inside this dispatch
    if step._aot is None:
        raise RuntimeError("AOT executable was not built")
    return obs_memory.note_executable("train_step", step._aot)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", action="store_true",
                    help="print the raw JSON report to stdout")
    ap.add_argument("--out", default="",
                    help="also write the JSON report to this path")
    ap.add_argument("--no-compile", action="store_true",
                    help="skip the TrainStep compile probe (device/host only)")
    args = ap.parse_args()

    # a CPU tool: pin the platform (and the 8-device host mesh) before jax loads
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

    from paddle_tpu.core import flags
    from paddle_tpu.observability import memory as obs_memory

    flags.set_flags({"metrics": "on"})

    exe_info = {}
    if not args.no_compile:
        log("--- compiling TrainStep probe")
        try:
            exe_info = compile_probe()
        except Exception as e:  # noqa: BLE001 — report still useful without
            import traceback

            traceback.print_exc()
            exe_info = {"error": f"{type(e).__name__}: {e}"}

    report = obs_memory.memory_report()
    report["ok"] = bool(report.get("devices") and report.get("host")
                        and (args.no_compile
                             or (exe_info and "error" not in exe_info)))

    if args.out:
        with open(args.out, "w") as f:
            json.dump(report, f, indent=2)
    if args.json:
        print(json.dumps(report, indent=2))
    else:
        for d in report["devices"]:
            parts = [f"device {d['device']} ({d['platform']}/{d['kind']})"]
            for k in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit"):
                if k in d:
                    parts.append(f"{k}={_fmt_bytes(d[k])}")
            if len(parts) == 1:
                parts.append("no allocator stats (CPU backend)")
            print("  ".join(parts))
        host = report["host"]
        print(f"host  rss={_fmt_bytes(host['rss'])}  "
              f"peak_rss={_fmt_bytes(host['peak_rss'])}")
        for what, info in sorted(report.get("executables", {}).items()):
            bits = []
            for k in ("temp", "argument", "output", "generated_code",
                      "total"):
                if k in info:
                    bits.append(f"{k}={_fmt_bytes(info[k])}")
            if "flops" in info:
                bits.append(f"flops={info['flops']:.3g}")
            if "bytes_accessed" in info:
                bits.append(f"accessed={_fmt_bytes(info['bytes_accessed'])}")
            print(f"exe {what}  " + "  ".join(bits))
    return 0 if report["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
