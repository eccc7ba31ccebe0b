"""One span system on the profiler's clock (ISSUE 26): every program span
goes into the ring with an id and a parent and, while a jax profiler session
is live, into the profiler's own host plane as a TraceAnnotation; with
everything off a span is the shared no-op; the device programs and the two
/metrics series carry the names the benchmark and an operator read.
Counts and names only, never a timing."""
import glob
import threading

import jax
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.core import flags
from paddle_tpu.jit import TrainStep
from paddle_tpu.models import GPTConfig, GPTForCausalLM
from paddle_tpu.observability import registry, reset_all, sinks, spans
from paddle_tpu.serving import ServingEngine


@pytest.fixture(autouse=True)
def _clean():
    reset_all()
    yield
    flags.set_flags({"metrics": "off", "metrics_dir": ""})
    reset_all()


def _engine(**kw):
    m = GPTForCausalLM(GPTConfig.tiny())
    m.eval()
    kw.setdefault("max_slots", 2)
    kw.setdefault("block_size", 16)
    kw.setdefault("prefill_chunk", 16)
    return ServingEngine(m, **kw)


def _train_step():
    paddle.seed(0)
    model = GPTForCausalLM(GPTConfig.tiny())
    opt = paddle.optimizer.AdamW(1e-3, parameters=model.parameters())
    step = TrainStep(model, lambda b: model(b, labels=b), opt)
    ids = paddle.to_tensor(
        np.random.default_rng(0).integers(0, 1024, (2, 16)).astype("int32"))
    return step, ids


def _host_events(trace_dir):
    """{event name: [its stats as a dict]} over the planes that are not
    devices, read back with jax.profiler.ProfileData."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(str(trace_dir) + "/plugins/profile/*/*.xplane.pb")
    found = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for e in line.events:
                found.setdefault(e.name, []).append(dict(e.stats))
    return found


def _by_name(name):
    return [s for s in spans.since(0) if s["name"] == name]


# ---------------------------------------------- under a jax profiler session
def test_engine_spans_reach_the_ring_and_the_profilers_host_plane(tmp_path):
    eng = _engine()
    eng.generate([[1, 2, 3, 4, 5]], max_new_tokens=2, eos_token_id=1023)
    assert spans.since(0) == []                 # nothing records yet
    req = eng.submit([5, 4, 3, 2, 1], max_new_tokens=3, eos_token_id=1023)
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.run_until_idle()
    finally:
        jax.profiler.stop_trace()
    assert req.state == "finished"
    ticks = _by_name("serving.tick")
    assert ticks and all(t["parent"] is None for t in ticks)
    tick_ids = {t["id"] for t in ticks}
    assert len(tick_ids) == len(ticks)
    for name in ("serving.schedule", "serving.decode", "serving.fetch"):
        got = _by_name(name)
        assert got, name
        assert all(s["parent"] in tick_ids for s in got), name
        assert all(isinstance(s["id"], int) for s in got)
    assert ticks[-1]["args"]["step"] == eng.steps
    assert {"decoded", "running", "waiting"} <= set(ticks[-1]["args"])
    assert _by_name("serving.fetch")[0]["args"]["what"] in (
        "tokens", "first_token_logits")
    host = _host_events(tmp_path)
    for name in ("serving.tick", "serving.schedule", "serving.decode",
                 "serving.fetch"):
        assert len(host.get(name, [])) == len(_by_name(name)), name
    # args known at the end of the work are on the profiler's event too
    assert any(st.get("step") == eng.steps for st in host["serving.tick"])
    # the session over, nothing records any more
    n = len(spans.since(0))
    eng.generate([[9, 8, 7]], max_new_tokens=2)
    assert len(spans.since(0)) == n


def test_train_step_spans_reach_the_ring_and_the_host_plane(tmp_path):
    step, ids = _train_step()
    step(ids)                                    # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(2):
            loss = step(ids)
        float(loss._value)
    finally:
        jax.profiler.stop_trace()
    got = _by_name("jit.train_step")
    assert [s["args"]["step"] for s in got] == [1, 2]
    assert all(s["parent"] is None and s["id"] for s in got)
    assert len(_by_name("jit.host_scalars")) == 2
    host = _host_events(tmp_path)
    assert len(host["jit.train_step"]) == 2
    assert len(host["jit.host_scalars"]) == 2
    # the profiler's own step marker, around the same dispatch
    assert sorted(st["step_num"] for st in host["train"]) == [1, 2]


# ----------------------------------------------------------- the off path
def test_with_everything_off_a_tick_constructs_and_appends_nothing(
        monkeypatch):
    made = []

    class Counting:
        def __init__(self, *a, **kw):
            made.append(a)

        @staticmethod
        def is_enabled():
            return False

    class Untouchable:
        def __getattr__(self, name):
            raise AssertionError(f"native.{name} on the off path")

    eng = _engine()
    eng.generate([[1, 2, 3]], max_new_tokens=2)      # programs exist
    monkeypatch.setattr(spans, "_TraceAnnotation", Counting)
    monkeypatch.setattr(spans, "_native", Untouchable())
    imports = []
    real_import = __import__
    monkeypatch.setattr(
        "builtins.__import__",
        lambda name, *a, **kw: imports.append(name) or real_import(
            name, *a, **kw))
    assert spans.enabled() is False
    assert spans.span("x") is spans.NOOP
    monkeypatch.undo()
    assert imports == []

    monkeypatch.setattr(spans, "_TraceAnnotation", Counting)
    monkeypatch.setattr(spans, "_native", Untouchable())
    req = eng.submit([4, 5, 6, 7], max_new_tokens=3)
    eng.run_until_idle()
    assert req.state == "finished" and req.trace is None
    assert made == [] and spans.since(0) == []


def test_the_native_tracer_is_fed_only_while_a_profiler_says_it_records(
        monkeypatch):
    calls = []

    class Native:
        def trace_push(self, name):
            calls.append(("push", name))

        def trace_pop(self):
            calls.append(("pop",))

    monkeypatch.setattr(spans, "_native", Native())
    flags.set_flags({"metrics": "on"})
    with spans.span("alone"):
        pass
    assert calls == []
    spans.session(True, native=True)
    try:
        with spans.span("mirrored"):
            pass
    finally:
        spans.session(False, native=True)
    assert calls == [("push", "mirrored"), ("pop",)]
    assert not spans._native_depth and not spans._session_depth


# ------------------------------------------------------ ids, parents, args
def test_parents_follow_the_thread_and_set_reaches_the_ring():
    flags.set_flags({"metrics": "on"})
    seen = {}

    def other():
        with spans.span("other.top") as s:
            pass
        seen["other"] = s.record

    with spans.span("outer", args={"a": 1}) as outer:
        with spans.span("inner") as inner:
            t = threading.Thread(target=other)
            t.start()
            t.join(30)
            assert not t.is_alive()
        outer.set(done=2)
    retro = spans.record_span("after.the.fact", 1, 2)
    assert inner.record["parent"] == outer.record["id"]
    assert outer.record["parent"] is None
    assert seen["other"]["parent"] is None      # another thread's stack
    assert outer.record["args"] == {"a": 1, "done": 2}
    assert retro["parent"] is None and retro["id"] > inner.record["id"]
    ids = [s["id"] for s in spans.since(0)]
    assert len(ids) == len(set(ids)) == 4
    assert spans.since(retro["id"] - 1) == [retro]


def test_request_scoped_spans_carry_the_request_id():
    flags.set_flags({"metrics": "on"})
    eng = _engine()
    req = eng.submit(list(range(1, 21)), max_new_tokens=2)
    eng.run_until_idle()
    for name in ("serving.submit_wait", "serving.prefill_chunk",
                 "serving.queue", "serving.admit", "serving.finish"):
        got = _by_name(name)
        assert got and all(
            s["args"]["request_id"] == req.request_id for s in got), name
    chunks = _by_name("serving.prefill_chunk")
    assert [c["args"]["tokens"] for c in chunks] == [16, 4]
    assert all(c in list(req.trace.spans) for c in chunks)
    # the batch's spans are shared with the request's own trace by reference
    dec = _by_name("serving.decode")
    assert dec and any(d is s for d in dec for s in req.trace.spans)
    # only what is made from request timestamps is outside any tick
    tick_ids = {t["id"] for t in _by_name("serving.tick")}
    for s in spans.since(0):
        if s["name"] in ("serving.queue", "serving.admit",
                         "serving.submit_wait", "serving.tick"):
            assert s["parent"] is None, s["name"]
        elif s["name"].startswith("serving."):
            assert s["parent"] is not None, s["name"]
    assert all(s["parent"] in tick_ids
               for s in _by_name("serving.prefill_chunk"))


def test_host_uploads_and_program_builds_have_spans():
    flags.set_flags({"metrics": "on"})
    eng = _engine()
    eng.generate([[1, 2, 3, 4]], max_new_tokens=3, eos_token_id=1023)
    built = _by_name("serving.program_build")
    kinds = [b["args"]["kind"] for b in built]
    assert {"workspace", "prefill", "scatter", "admit", "decode",
            "clear_slot"} <= set(kinds)
    assert len(kinds) == len(eng._jit)          # one span a program
    def uploads():
        return [u["args"]["what"] for u in _by_name("serving.host_upload")]

    assert uploads() == ["decode_state"]        # the device copies, once
    n = len(built)
    eng.generate([[4, 3, 2, 1]], max_new_tokens=3, eos_token_id=1023)
    assert len(_by_name("serving.program_build")) == n   # nothing new
    # a later greedy admission, eos id or not, is the one admit program
    assert uploads() == ["decode_state"]
    # a sampled one draws on the host and scatters its slot into the live
    # device state
    eng.generate([[2, 4, 1, 3]], max_new_tokens=3, temperature=0.8)
    assert uploads() == ["decode_state", "slot_state"]


# ------------------------------------------------------- always-on counters
def _built(kind):
    return registry.REGISTRY.get("serving_programs_built_total").value(
        kind=kind)


def test_programs_built_rises_once_per_new_shape_and_not_on_a_repeat():
    eng = _engine(prefill_chunk=16)
    kinds = ("workspace", "prefill", "scatter", "decode", "admit")
    before = {k: _built(k) for k in kinds}
    eng.generate([[1, 2, 3, 4]], max_new_tokens=3)
    first = {k: _built(k) - before[k] for k in kinds}
    assert first == {"workspace": 1, "prefill": 1, "scatter": 1,
                     "decode": 1, "admit": 1}
    eng.generate([[5, 6, 7, 8]], max_new_tokens=3)       # the same shapes
    assert {k: _built(k) - before[k] for k in kinds} == first
    eng.generate([list(range(1, 31))], max_new_tokens=3)  # a longer prompt
    assert _built("workspace") - before["workspace"] == 2
    assert _built("prefill") - before["prefill"] == 2
    assert _built("scatter") - before["scatter"] == 2
    assert _built("decode") - before["decode"] == 1
    total = sum(_built(k) for k in ("workspace", "prefill", "scatter",
                                    "decode", "admit",
                                    "clear_slot", "gather", "admit_cow",
                                    "batched_prefill", "spec",
                                    "decode_multi"))
    assert total - sum(before.values()) >= len(eng._jit) - 1


def test_submit_lock_wait_counts_one_observation_a_submit():
    h = registry.REGISTRY.get("serving_submit_lock_wait_seconds")
    before = h.stats()["count"]
    eng = _engine()
    reqs = [eng.submit([1, 2, 3], max_new_tokens=2) for _ in range(3)]
    assert h.stats()["count"] == before + 3
    flags.set_flags({"serving_max_queue": 1})
    try:
        with pytest.raises(Exception):
            eng.submit([1, 2, 3], max_new_tokens=2)     # shed, still waited
    finally:
        flags.set_flags({"serving_max_queue": 0})
    assert h.stats()["count"] == before + 4
    eng.run_until_idle()
    assert all(r.state == "finished" for r in reqs)
    # both series are on /metrics without FLAGS_metrics
    text = sinks.prometheus_text()
    assert "serving_submit_lock_wait_seconds_count" in text
    assert 'serving_programs_built_total{kind="decode"}' in text


# ------------------------------------------------------------ device names
def test_engine_programs_are_named_for_the_trace():
    eng = _engine()
    doc = list(range(1, 33))
    eng.generate([doc], max_new_tokens=2)
    eng.generate([doc + [40, 41]], max_new_tokens=2)             # gather
    eng.generate([doc + [50, 51], doc + [60]], max_new_tokens=2)  # batched
    eng.generate([doc], max_new_tokens=2)                # copy-on-write
    eng._spec_jit(3, False)          # built, not run: drafts need a
    eng._decode_multi_jit(2)         # trained model, fusion a flag
    names = {k[0]: fn.__name__ for k, fn in eng._jit.items()}
    # the decode program alone keeps `step`: decode_step_ms reads jit_step
    assert names == {
        "decode": "step", "workspace": "serve_workspace",
        "prefill": "serve_prefill",
        "scatter": "serve_scatter", "admit": "serve_admit",
        "clear_slot": "serve_clear_slot", "gather": "serve_gather",
        "batched_prefill": "serve_batched_prefill",
        "admit_cow": "serve_admit_cow", "spec": "serve_spec_verify",
        "decode_multi": "serve_decode_fused"}


def test_named_scopes_reach_the_lowered_programs():
    eng = _engine()
    eng.generate([[1, 2, 3]], max_new_tokens=2)
    _, _, pv, bv = eng._functional()
    toks, tables, lens, temps, seed = eng._dev
    text = eng._decode_jit(False).lower(
        pv, bv, toks, eng.pool.layers, tables, lens, temps,
        seed).as_text(debug_info=True)
    assert "module @jit_step " in text
    for scope in ("embed", "h0/attn", "h1/mlp", "h0/attn/kv_append",
                  "final_norm", "lm_head", "sample"):
        assert f"jit(step)/{scope}/" in text, scope
    step, ids = _train_step()
    text = step.lower(ids).as_text(debug_info=True)
    assert "module @jit_train_step " in text
    for scope in ("loss", "optimizer", "h1/attn", "lm_head"):
        assert f"/{scope}/" in text, scope
    step.invalidate_executables()               # the re-traced wrapper
    assert "module @jit_train_step " in step.lower(ids).as_text()
