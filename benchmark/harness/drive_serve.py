"""Driver for traffic of kinds `open_loop` and `closed_loop`: one thread
hands requests to ServingEngine.submit as they fall due and ticks the engine
with ServingEngine.step, the call that ServingServer's loop makes; no request
goes through the HTTP handler. Timestamps are the program's own
(scheduler.Request, time.monotonic), set where a token reaches the host."""
from __future__ import annotations

import functools
import gc
import shutil
import threading
import time

from . import correct, device, manifest, stats, trace, traffic

LOWERING_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
DRAIN_S = 60.0


class LoweringCount:
    """Counts jax lowerings (a new program for a new shape, whether or not
    the persistent cache then serves the executable)."""

    def __init__(self):
        import jax.monitoring

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, _secs, **_kw):
        if event == LOWERING_EVENT:
            self.n += 1


def _submit(engine, spec, eos):
    return engine.submit(spec["prompt"], max_new_tokens=spec["max_new_tokens"],
                         eos_token_id=eos)


def warm_up(engine, mix, sv, vocab, seed):
    """Every length class of the mix alone, and every pair that can share a
    batched-prefill program, ticked here before the loop thread exists so
    that what lands in one tick is decided and not raced. Returns the
    number of warm-up requests."""
    rng = traffic.rng_for(seed, 9)
    eos = vocab - 1
    chunk = int(sv["prefill_chunk"])

    def toks(n):
        return [int(t) for t in rng.integers(0, vocab - 1, n)]

    def go(prompts):
        for p in prompts:
            engine.submit(p, max_new_tokens=3, eos_token_id=eos)
        engine.run_until_idle()
        return len(prompts)

    docs, kinds = {}, []           # kinds: (suffix to prefill, prompt maker)
    for shared, fresh in traffic.warmup_classes(mix):
        docs.setdefault(shared, toks(shared))
        kinds.append((shared + fresh,
                      lambda s=shared, f=fresh: toks(s) + toks(f)))
        if shared and int(mix["turns"]) > 1:
            kinds.append((fresh, lambda s=shared, f=fresh: docs[s] + toks(f)))
    # the documents that follow-up turns will find in the prefix cache
    n = sum(go([d + toks(16)]) for s, d in docs.items() if s)
    n += sum(go([make()]) for _, make in kinds)
    small = [make for suffix, make in kinds if suffix <= chunk]
    for i, a in enumerate(small):
        for b in small[i:]:
            n += go([a(), b()])
    return n


def _trace_thread(at, secs, tdir, engine, out):
    """Traces `secs` seconds from `at`; leaves in `out` the period's two
    instants and the engine's counts at each."""
    import jax

    def mark(tag):
        out[tag] = {"t": time.monotonic(), "ticks": engine.steps,
                    "prefill_tokens": engine.prefill_tokens}

    def body():
        time.sleep(max(0.0, at - time.monotonic()))
        jax.profiler.start_trace(tdir)
        with jax.profiler.TraceAnnotation(trace.WINDOW):
            mark("begin")
            time.sleep(secs)
            mark("end")
        jax.profiler.stop_trace()

    th = threading.Thread(target=body, name="bench-trace", daemon=True)
    th.start()
    return th


def _plan(mix, seed, seconds, vocab):
    """The window's requests, made before the window opens."""
    if mix["kind"] == "open_loop":
        return traffic.open_loop_schedule(mix, seed, seconds, vocab)
    return {0: traffic.closed_loop_clients(mix, seed, vocab, 0)}


class OpenLoop:
    """Requests due on the plan's schedule, whether or not earlier ones
    have finished."""

    def __init__(self, plan, t0, seconds, **_):
        self.todo = [(t0 + due, spec) for due, spec in plan if due < seconds]
        self.i = 0

    def ready(self, now):
        while self.i < len(self.todo) and self.todo[self.i][0] <= now:
            self.i += 1
            yield self.todo[self.i - 1] + (None,)

    def next_due(self, now):
        return self.todo[self.i][0] if self.i < len(self.todo) else now + 1.0

    def sent(self, client, rec):
        pass


class ClosedLoop:
    """Each client sends its next request when its last one has finished;
    that instant is when the next is due."""

    def __init__(self, plan, t0, seconds, mix, seed, vocab):
        def todo(i):
            for epoch in range(1 << 30):
                if epoch not in plan:   # a faster system than the plan foresaw
                    plan[epoch] = traffic.closed_loop_clients(mix, seed, vocab, epoch)
                for sess in plan[epoch][i]:
                    yield from sess

        self.clients = [{"todo": todo(i), "rec": None, "due": t0}
                        for i in range(int(mix["clients"]))]

    def ready(self, now):
        for c in self.clients:
            cur = c["rec"]
            if cur is not None:
                req = cur["req"]
                if req is not None and req.state != "finished":
                    continue
                c["due"] = req.finish_time if req is not None else cur["sent"]
            yield c["due"], next(c["todo"]), c

    def next_due(self, now):
        return now + 0.002

    def sent(self, client, rec):
        client["rec"] = rec


def drive(engine, source, close, eos):
    """The window: hand over what is due, tick the engine, until the close.
    One thread submits and ticks, so a request never waits on the engine's
    lock (see PERF.md: behind ServingServer's loop thread submit() starved
    for seconds to minutes); one that falls due inside a tick goes in when
    the tick ends, and that wait counts, because time is taken from when it
    was due."""
    recs = []
    while True:
        now = time.monotonic()
        if now >= close:
            return recs
        for due, spec, client in source.ready(now):
            rec = {"due": due, "spec": spec, "req": None, "error": None}
            try:
                rec["req"] = _submit(engine, spec, eos)
            except Exception as e:  # noqa: BLE001 - counted as failed
                rec["error"] = f"{type(e).__name__}: {e}"
            rec["sent"] = time.monotonic()
            source.sent(client, rec)
            recs.append(rec)
        if engine.sched.has_work():
            engine.step()
        else:
            time.sleep(max(0.0, min(0.002, source.next_due(now) - now)))


def _request_facts(recs, n_close):
    """(prompt_tokens, prefix_matched, tokens_at_close) of every request
    the engine took: what a work module counts the window from."""
    return [(len(rec["req"].prompt), int(rec["req"].prefix_matched), n)
            for rec, n in zip(recs, n_close) if rec["req"] is not None]


def _decode_contexts(recs, ta, tb):
    """Live context of every token decoded in [ta, tb], taking a request's
    tokens as evenly spaced between its first token and its finish."""
    ctx = []
    for rec in recs:
        req = rec["req"]
        if req is None or req.first_token_time is None or req.finish_time is None:
            continue
        n = len(req.output_tokens)
        if n < 2:
            continue
        gap = (req.finish_time - req.first_token_time) / (n - 1)
        for j in range(1, n):
            t = req.first_token_time + j * gap
            if ta <= t <= tb:
                ctx.append(len(req.prompt) + j)
    return ctx


def pick_rows(recs, seed, k):
    """The requests the reference goes over: the longest, one that hit the
    prefix cache and one that did not where there are such, the rest drawn
    from the seed."""
    done = [r for r in recs if r["req"] is not None
            and r["req"].state == "finished"
            and r["req"].finish_reason in ("stop", "length")
            and r["req"].output_tokens]
    if not done:
        return []
    size = lambda r: len(r["req"].prompt) + len(r["req"].output_tokens)  # noqa: E731
    picked = [max(range(len(done)), key=lambda i: size(done[i]))]
    for want_hit in (True, False):
        for i, r in enumerate(done):
            if i not in picked and bool(r["req"].prefix_matched) == want_hit:
                picked.append(i)
                break
    rng = traffic.rng_for(seed, 7)
    for i in rng.permutation(len(done)):
        if len(picked) >= k:
            break
        if int(i) not in picked:
            picked.append(int(i))
    return [done[i] for i in picked[:k]]


def _snapshot(engine, lowerings):
    return {"jit": len(engine._jit), "low": lowerings.n, "ticks": engine.steps,
            "prefill": engine.prefill_tokens, "batched": engine.batched_prefills,
            "cow": engine.cow_admissions}


def run(cell, seed, seconds, trace_on, devs, t_start, work_dir):
    import jax

    cfg, mix = cell["config"], cell["traffic"]
    sv = cfg["serve"]
    vocab = int(cfg["vocab_size"])
    ref_mod, prog_mod, work_mod = manifest.models(cfg["models"])
    lowerings = LoweringCount()
    model, engine = prog_mod.build_engine(cfg, seed)
    n_warm = warm_up(engine, mix, sv, vocab, seed)
    plan = _plan(mix, seed, seconds, vocab)
    setup_s = time.monotonic() - t_start

    # ---- the window
    before = _snapshot(engine, lowerings)
    t0 = time.monotonic()
    tr_times, tr_thread = {}, None
    if trace_on:
        tdir = work_dir + "/trace"
        shutil.rmtree(tdir, ignore_errors=True)
        secs = min(float(mix["trace_seconds"]), 0.5 * seconds)
        tr_thread = _trace_thread(t0 + 0.4 * seconds, secs, tdir, engine,
                                  tr_times)
    source = (OpenLoop if mix["kind"] == "open_loop" else ClosedLoop)(
        plan, t0, seconds, mix=mix, seed=seed, vocab=vocab)
    recs = drive(engine, source, t0 + seconds, vocab - 1)
    t_close = time.monotonic()
    n_close = [len(r["req"].output_tokens) if r["req"] is not None else 0
               for r in recs]
    queued_at_close = len(engine.sched.waiting)
    open_at_close = sum(1 for r in recs if r["req"] is not None
                        and r["req"].state != "finished")
    after = _snapshot(engine, lowerings)
    window_s = t_close - t0

    # ---- wait for what is in flight: an answer that comes late is late
    deadline = time.monotonic() + DRAIN_S
    while engine.sched.has_work() and time.monotonic() < deadline:
        engine.step()
    if tr_thread is not None:
        tr_thread.join()
    dev = device.describe(devs)

    red = {}
    if trace_on:
        red = trace.read_and_remove(tdir, cell["debug"]["describe_trace"])

    # ---- metrics over ALL requests due in the window
    answered = [r for r in recs if r["req"] is not None
                and r["req"].state == "finished"
                and r["req"].finish_reason in ("stop", "length")]
    unanswered = len(recs) - len(answered)
    ttft = [1e3 * (r["req"].first_token_time - r["due"]) for r in answered]
    tpot = [1e3 * (r["req"].finish_time - r["req"].first_token_time)
            / (len(r["req"].output_tokens) - 1)
            for r in answered if len(r["req"].output_tokens) > 1]
    late = [r["sent"] - r["due"] for r in recs]
    tokens = sum(n_close)
    requests = _request_facts(recs, n_close)
    prompt_tokens = sum(plen for plen, _, _ in requests)
    matched = sum(m for _, m, _ in requests)
    counters = {
        "compiles_in_window": (after["jit"] - before["jit"])
        + (after["low"] - before["low"]),
        "prefix_matched_tokens": matched, "prompt_tokens": prompt_tokens,
        "decode_tokens": tokens, "ticks": after["ticks"] - before["ticks"],
        "prefill_tokens": after["prefill"] - before["prefill"],
        "batched_prefills": after["batched"] - before["batched"],
        "model_flops": work_mod.served_flops(cfg, requests),
        "window_s": window_s,
    }
    facts, work = {}, {}
    if "end" in tr_times:
        a, b = tr_times["begin"], tr_times["end"]
        facts = {"decode_contexts": _decode_contexts(recs, a["t"], b["t"]),
                 "ticks": b["ticks"] - a["ticks"],
                 "prefill_tokens": b["prefill_tokens"] - a["prefill_tokens"]}
        work = work_mod.traced_work(cfg, facts)

    # ---- free the program's state, then the reference reads the sample
    rows = [(list(r["req"].prompt), list(r["req"].output_tokens))
            for r in pick_rows(recs, seed, int(mix["check_requests"]))]
    notes = {
        "requests": len(recs), "warm_up_requests": n_warm,
        "open_at_close": open_at_close, "queued_at_close": queued_at_close,
        "rate_per_s": mix.get("rate_per_s"),
        "ttft_ms": {"p50": stats.median(ttft), "p90": stats.percentile(ttft, 90),
                    "max": max(ttft) if ttft else None},
        "generator_late_ms_max": 1e3 * max(late) if late else None,
        "generator_late_ms_p50": 1e3 * stats.median(late) if late else None,
        "tpot_p50_ms": stats.median(tpot),
        "checked_rows": len(rows),
        "checked_tokens": sum(len(t) for _, t in rows),
        "errors": [r["error"] for r in recs if r["error"]][:5],
        "batched_prefills": counters["batched_prefills"],
        "cow_admissions": after["cow"] - before["cow"],
    }
    n_recs = len(recs)
    del model, engine, source, recs, answered
    gc.collect()
    jax.clear_caches()
    # one shape for every run of the cell: the mix's longest prompt and answer
    longest = int(mix["output_tokens"]["max"])
    read = functools.partial(
        ref_mod.served_gaps, cfg, seed, rows, dtype=sv["weight_dtype"],
        n_pos=longest, width=max(mix["shared_tokens"]["values"])
        + max(mix["fresh_tokens"]["values"]) + longest)
    gaps = read() if rows else []
    numbers = {"logit_gap": max(gaps) if gaps else float("inf"),
               "unanswered": unanswered}
    ok, checks = correct.decide(numbers, correct.limits_for(cell["name"]))
    notes["gaps"] = gaps
    if cell["debug"]["control"] and rows:
        notes["control_fp8_gaps"] = read(control="fp8")

    clocks = {"ttft_p50_ms": stats.median(ttft), "tpot_p50_ms": stats.median(tpot),
              "ttft_p90_ms": stats.tail_with_misses(ttft, unanswered, 90),
              "generator_late_ms_max": notes["generator_late_ms_max"]}
    return {
        "correct": ok, "checks": checks,
        "attempted": n_recs, "failed": unanswered,
        "end_to_end": {
            "serve_tokens_per_s": stats.rate(tokens, window_s),
            "tpot_p90_ms": stats.tail_with_misses(tpot, unanswered, 90),
            "setup_s": setup_s},
        "ctx": {"counters": counters, "clocks": clocks, "trace": red,
                "work": work, "facts": facts, "requests": requests,
                "config": cfg, "peaks": cell["peaks"], "chips": len(devs)},
        "device": dev, "notes": notes,
    }
