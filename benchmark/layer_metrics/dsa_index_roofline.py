"""The sparse layers' indexer kernel's share of its roofline (see
dsa_index_roofline.json). Its work needs the contexts of the prefilled
tokens, which the program's serving.prefill_chunk spans of the traced
session carry (`start`, `tokens`). A program whose spans lack them, or a
family without the work, gives None."""
from benchmark.harness import manifest, span_readers, trace, workmodel


def read(ctx, spec):
    chunks = [(s["args"]["start"], s["args"]["tokens"])
              for s in span_readers.ring(ctx)
              if s["name"] == spec["span"] and "start" in s.get("args", {})]
    secs = trace.op_seconds(ctx.get("trace") or {}, spec["pattern"])
    if not chunks or secs <= 0:
        return None
    work_mod = manifest.models(ctx["config"]["models"])[2]
    work = work_mod.traced_work(
        ctx["config"], {**ctx["facts"], "prefill_chunks": chunks}
    ).get(spec["work"])
    if not work:
        return None
    return 100.0 * workmodel.roofline_seconds(work, ctx["peaks"])[0] / secs
