"""Driver for traffic of kind `train_steps`: one compiled step object, driven
from the seed through its first steps in set-up (the reference follows
those), then handed to the window unchanged."""
from __future__ import annotations

import gc
import shutil
import time

import numpy as np

from . import correct, device, manifest, stats, trace, traffic


def feed(ids: np.ndarray):
    """Host batch -> what the step is called with. Set-up and the window
    both feed through here."""
    import paddle_tpu as paddle

    return paddle.to_tensor(ids)


def _part_norms(arrays, names, parts, scale=1.0):
    """{compared part: l2 norm x scale} of the program's leaves, in one
    jitted call; `parts` is the model family's split of a leaf into the
    parts that are compared."""
    import jax
    import jax.numpy as jnp

    def f(xs):
        out = {}
        for n, x in zip(names, xs):
            out.update(parts(n, x))
        return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32)))) * scale
                for k, v in out.items()}

    return {k: float(v) for k, v in jax.device_get(jax.jit(f)(arrays)).items()}


def run(cell, seed, seconds, trace_on, devs, t_start, work_dir):
    import jax

    cfg, mix = cell["config"], cell["traffic"]
    tr = cfg["train"]
    ref_mod, prog_mod, work_mod = manifest.models(cfg["models"])
    batch, seq, vocab = int(tr["batch"]), int(tr["sequence"]), int(cfg["vocab_size"])
    model, step, names = prog_mod.build_train_step(cfg, seed)
    mine = [p for _, p in model.named_parameters()]
    if len(mine) != len(step.params) or any(
            a is not b for a, b in zip(mine, step.params)):
        raise RuntimeError("TrainStep's parameters are not the model's, in order")
    batches = traffic.train_batches(seed, vocab, batch, seq)
    n_ref = int(mix["reference_steps"])
    first = [next(batches) for _ in range(n_ref)]

    # ---- set-up: the first steps, which the reference follows
    def parts(name, x):
        return ref_mod.comparison_parts(name, x, cfg)

    losses, grad_norm = [], None
    for i, ids in enumerate(first):
        losses.append(step(feed(ids))._value)
        if i == 0:
            # the first gradient as the optimizer got it: after one step
            # Adam's first moment is (1 - beta1) x gradient
            grad_norm = _part_norms(
                [s["moment1"] for s in step.opt_state], names, parts,
                1.0 / (1.0 - float(tr["optimizer"]["beta1"])))
    start = ref_mod.init_weights(cfg, seed, tr["param_dtype"], per_layer=True)
    change = _part_norms([p._value - start[n]
                          for p, n in zip(step.params, names)], names, parts)
    del start
    prog = {"losses": [float(x) for x in jax.device_get(losses)],
            "grad_norm": grad_norm, "change_norm": change}
    for _ in range(max(0, int(mix["warm_steps"]) - n_ref)):
        loss = step(feed(next(batches)))
    jax.block_until_ready(loss._value)
    setup_s = time.monotonic() - t_start

    # ---- the window (a traced run's clock leaves out the profiler's own
    # start and stop, and the trace is read once the window has closed)
    every = int(mix["loss_fetch_every"])
    steps, chunk, red, traced_steps, profiler_s = 0, [], {}, 0, 0.0
    tdir = work_dir + "/trace"
    if trace_on:
        shutil.rmtree(tdir, ignore_errors=True)
        jax.profiler.start_trace(tdir)
    t0 = time.monotonic()
    if trace_on:
        with jax.profiler.TraceAnnotation(trace.WINDOW):
            while time.monotonic() - t0 < float(mix["trace_seconds"]):
                loss = step(feed(next(batches)))
                steps += 1
                if steps % 4 == 0:      # keep the queue short while tracing
                    float(loss._value)
            float(loss._value)
        t_stop = time.monotonic()
        jax.profiler.stop_trace()
        profiler_s = time.monotonic() - t_stop
        traced_steps = steps
    t_chunk, in_chunk = time.monotonic(), 0
    while time.monotonic() - t0 - profiler_s < seconds:
        loss = step(feed(next(batches)))
        steps += 1
        in_chunk += 1
        if in_chunk == every:
            float(loss._value)
            now = time.monotonic()
            chunk.append((now - t_chunk) / in_chunk)
            t_chunk, in_chunk = now, 0
    last_loss = float(loss._value)
    window_s = time.monotonic() - t0 - profiler_s
    dev = device.describe(devs)
    if trace_on:
        red = trace.read_and_remove(tdir, cell["debug"]["describe_trace"])

    # ---- free the program's state, then the reference follows the steps
    tokens = steps * batch * seq
    del model, step, loss, losses
    gc.collect()
    jax.clear_caches()
    ref = ref_mod.train_reference(cfg, seed, first, tr["optimizer"])
    cmp_ = correct.train_numbers(prog, ref)
    numbers = dict(cmp_["numbers"])
    ok, checks = correct.decide(numbers, correct.limits_for(cell["name"]))
    extra = {}
    if cell["debug"]["control"]:
        for tag, kw in (("control_fp8", {"precision": "fp8"}),
                        ("fault_half_batch", {"half_batch": True})):
            alt = ref_mod.train_reference(cfg, seed, first, tr["optimizer"], **kw)
            extra[tag] = correct.train_numbers(alt, ref)["numbers"]

    peaks = cell["peaks"]
    counters = {"model_flops": tokens * work_mod.train_flops_per_token(cfg, seq),
                "window_s": window_s, "steps": steps, "tokens": tokens}
    clocks = {"step_ms_p50": 1e3 * stats.median(chunk) if chunk else None}
    facts, work = {}, {}
    if traced_steps:
        facts = {"batch": batch, "sequence": seq, "steps": traced_steps}
        work = work_mod.traced_work(cfg, facts)
    return {
        "correct": ok and np.isfinite(last_loss), "checks": checks,
        "attempted": steps, "failed": 0 if np.isfinite(last_loss) else 1,
        "end_to_end": {
            "train_tokens_per_s": stats.rate(tokens, window_s) / len(devs),
            "setup_s": setup_s},
        "ctx": {"counters": counters, "clocks": clocks, "trace": red,
                "work": work, "facts": facts, "requests": [], "config": cfg,
                "peaks": peaks, "chips": len(devs)},
        "device": dev,
        "notes": {"where": cmp_["where"], "program": prog["losses"],
                  "reference": ref["losses"], "last_loss": last_loss,
                  "steps": steps, "traced_steps": traced_steps, **extra},
    }
