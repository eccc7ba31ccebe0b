"""Serving observability (ISSUE r16): per-request lifecycle traces (incl.
cancel/timeout/disconnect), SLO histograms vs a hand-timed oracle, the
Prometheus round-trip, anomaly -> serving flight dump with the offending
request's trace aboard, engine-counter thin views, locked /stats + enriched
/healthz + /metrics on the serving HTTP front end, and the metrics-off
no-op contract.
"""
import json
import math
import os
import threading
import time
import urllib.request

import pytest

import paddle_tpu as paddle
from paddle_tpu.core import flags
from paddle_tpu.models import GPTConfig, GPTForCausalLM
from paddle_tpu.observability import registry, reset_all, sinks, spans
from paddle_tpu.observability import flight_recorder
from paddle_tpu.observability.anomaly import (
    CacheHitCollapse,
    GoodputCollapse,
    KVConservationBreach,
    TTFTRegression,
    serving_default_detectors,
)
from paddle_tpu.serving import (
    Request,
    ServingEngine,
    ServingServer,
    export_request_trace,
)
from paddle_tpu.serving.observability import (
    EngineStats,
    ServingObservability,
    new_engine_id,
)


@pytest.fixture(autouse=True)
def _clean():
    reset_all()
    yield
    flags.set_flags({"metrics": "off", "metrics_dir": "",
                     "serving_anomaly": "auto"})
    reset_all()


@pytest.fixture
def metrics_on(tmp_path):
    d = str(tmp_path / "metrics")
    flags.set_flags({"metrics": "on", "metrics_dir": d})
    return d


def _engine(**kw):
    m = GPTForCausalLM(GPTConfig.tiny())
    m.eval()
    kw.setdefault("max_slots", 2)
    kw.setdefault("block_size", 16)
    kw.setdefault("prefill_chunk", 16)
    return ServingEngine(m, **kw)


# ------------------------------------------------------ lifecycle traces
class TestRequestTraces:
    def test_full_lifecycle_span_set(self, metrics_on, tmp_path):
        eng = _engine()
        req = eng.submit(list(range(1, 9)), max_new_tokens=4)
        eng.run_until_idle()
        assert req.trace is not None
        names = req.trace.names()
        # every lifecycle phase shows up, in order
        assert names[0] == "serving.queue"
        assert "serving.prefill_chunk" in names
        assert "serving.admit" in names
        assert "serving.decode" in names
        assert names[-1] == "serving.finish"
        # the admission (prefill start to the first token's value on the
        # host) is recorded at the fetch, a tick after the first decode
        # dispatch; in time it begins before it and ends after it begins
        by_name = {}
        for s in req.trace.spans:
            by_name.setdefault(s["name"], s)
        admit, decode = by_name["serving.admit"], by_name["serving.decode"]
        assert admit["begin_ns"] < decode["begin_ns"] < admit["end_ns"]
        finish = list(req.trace.spans)[-1]
        assert finish["args"]["reason"] == "length"
        assert finish["args"]["request_id"] == req.request_id
        # the same spans landed in the global ring (profiler export path)
        ring = [s["name"] for s in spans.tail(500)]
        assert "serving.queue" in ring and "serving.tick" in ring
        # chrome-trace export of the sampled request round-trips
        p = str(tmp_path / "req_trace.json")
        export_request_trace(req, p)
        with open(p) as f:
            tr = json.load(f)
        evs = tr["traceEvents"]
        assert len(evs) == len(names)
        assert all(e["ph"] == "X" and e["dur"] >= 0 for e in evs)
        assert evs[0]["name"] == "serving.queue"

    def test_export_tagging_never_leaks_into_shared_tick_spans(
            self, metrics_on):
        """The engine appends ONE shared per-tick span dict by reference
        to every traced participant (perf): export-time tagging must
        copy, or exporting request A's trace with attribution args would
        corrupt request B's."""
        from paddle_tpu.serving.observability import chrome_trace_events

        eng = _engine()
        r1 = eng.submit(list(range(1, 9)), max_new_tokens=4)
        r2 = eng.submit(list(range(101, 109)), max_new_tokens=4)
        eng.run_until_idle()
        decode1 = [s for s in r1.trace.spans
                   if s["name"] == "serving.decode"]
        ids2 = {id(s) for s in r2.trace.spans}
        # precondition: at least one tick span IS the same dict object
        assert any(id(s) in ids2 for s in decode1)
        ev1 = chrome_trace_events(list(r1.trace.spans), pid=7,
                                  extra_args={"attempt": 0,
                                              "cause": "primary"})
        # request 1's export tagged nothing onto the raw shared spans
        assert all("attempt" not in (s.get("args") or {})
                   for s in r2.trace.spans)
        ev2 = chrome_trace_events(list(r2.trace.spans), pid=8,
                                  extra_args={"attempt": 1,
                                              "cause": "hedge"})
        assert {e["args"]["attempt"] for e in ev1} == {0}
        assert {e["args"]["attempt"] for e in ev2} == {1}
        assert all(e["pid"] == 7 for e in ev1)
        assert all(e["pid"] == 8 for e in ev2)
        # mutating an exported event can never reach the live spans
        ev1[0]["args"]["poison"] = True
        assert all("poison" not in (s.get("args") or {})
                   for s in r1.trace.spans)

    def test_cow_admission_traces_without_prefill(self, metrics_on):
        eng = _engine()
        prompt = list(range(1, 33))  # two full blocks -> cacheable
        eng.generate([prompt], max_new_tokens=2)
        req = eng.submit(prompt, max_new_tokens=2)
        eng.run_until_idle()
        names = req.trace.names()
        # full-prompt hit: admitted via COW, zero prefill dispatches
        assert "serving.prefill_chunk" not in names
        admit = [s for s in req.trace.spans
                 if s["name"] == "serving.admit"][0]
        assert admit["args"]["cached"] is True

    @pytest.mark.parametrize("reason", ["cancelled", "timeout", "disconnect"])
    def test_cancel_paths_close_the_trace(self, metrics_on, reason):
        eng = _engine()
        req = eng.submit(list(range(1, 9)), max_new_tokens=64)
        eng.step()                      # admitted, maybe a token out
        assert eng.cancel(req, reason=reason)
        names = req.trace.names()
        assert names[-1] == "serving.finish"
        assert list(req.trace.spans)[-1]["args"]["reason"] == reason
        # every non-stop/length finish is shed, labeled by reason
        shed = registry.REGISTRY.get("serving_shed_requests_total")
        assert shed.value(tier="default", reason=reason) == 1
        good = registry.REGISTRY.get("serving_goodput_tokens_total")
        assert good.value(tier="default") == 0.0

    def test_speculative_ticks_traced(self, metrics_on):
        import numpy as np

        # all-zero weights: greedy emits 0 forever — a perfectly
        # draftable stream, so speculation is guaranteed to engage
        m = GPTForCausalLM(GPTConfig.tiny())
        for p in m.parameters():
            p.set_value(paddle.to_tensor(np.zeros(p.shape, np.float32)))
        m.eval()
        eng = ServingEngine(m, max_slots=2, block_size=8, prefill_chunk=8,
                            spec_k=4)
        req = eng.submit([5, 0, 0, 0, 0], max_new_tokens=24)
        eng.run_until_idle()
        names = req.trace.names()
        assert "serving.spec_verify" in names
        assert eng.spec_ticks > 0

    def test_fetch_counters_behind_arg_and_first_token_time(self,
                                                            metrics_on):
        """serving_fetches_total{kind} and the `behind` arg of the
        serving.fetch span say whether the engine had dispatched later
        work before it waited; serving_overshoot_tokens_total counts the
        token a late-found eos decodes too many; and first_token_time is
        the end of the fetch that brought the first token's value, not
        the dispatch of the program that sampled it."""
        eng = _engine()
        prompt = list(range(1, 9))
        ids = paddle.to_tensor([prompt], dtype="int32")
        toks = [int(t) for t in eng.model.generate(
            ids, max_new_tokens=4).numpy()[0, len(prompt):]]
        eos = next(t for t in toks[2:] if t not in toks[:toks.index(t)])
        cut = toks.index(eos) + 1
        fetches = registry.default_registry().get("serving_fetches_total")
        over = registry.default_registry().get(
            "serving_overshoot_tokens_total")
        req = eng.submit(prompt, max_new_tokens=8, eos_token_id=eos)
        eng.run_until_idle()
        assert (req.output_tokens, req.finish_reason) == (toks[:cut], "stop")
        # the eos came to the host under the step after its own: one
        # token decoded past it, dropped unfetched
        assert over.value() == 1
        ring = spans.since(0)
        ticks = {s["id"] for s in ring if s["name"] == "serving.tick"}
        fetch = [s for s in ring if s["name"] == "serving.fetch"]
        assert fetch and all(s["parent"] in ticks for s in fetch)
        assert [s["args"]["behind"] for s in fetch] == [1] * len(fetch)
        assert fetches.value(kind="under_dispatch") == len(fetch) == cut - 1
        assert fetches.value(kind="exposed") == 0
        # the first fetch brought the first token (and the second):
        # stamped when it ended, a tick after the admission's dispatch
        first = fetch[0]
        assert first["args"]["tokens"] == 2
        stamp = int(req.first_token_time * 1e9)
        assert first["end_ns"] <= stamp
        assert all(stamp <= s["begin_ns"] for s in fetch[1:])
        decode = [s for s in ring if s["name"] == "serving.decode"]
        assert len(decode) == cut and decode[1]["end_ns"] < stamp
        assert req.ttft_seconds() == pytest.approx(
            (stamp - int(req.arrival_time * 1e9)) / 1e9, abs=1e-6)

    def test_metrics_off_attaches_no_trace(self):
        eng = _engine()
        req = eng.submit([1, 2, 3, 4], max_new_tokens=2)
        eng.run_until_idle()
        assert req.trace is None
        with pytest.raises(ValueError):
            export_request_trace(req, "/dev/null")


# -------------------------------------------------- SLO metric histograms
class TestSLOMetrics:
    def test_histograms_match_hand_timed_oracle(self, metrics_on):
        """Drive the hooks with a fabricated request whose timestamps are
        set by hand; every SLO histogram must reproduce the arithmetic."""
        eng = _engine()
        obs = eng.obs
        obs._on = True
        req = Request([1, 2, 3], max_new_tokens=8, tier="gold")
        t0 = req.arrival_time
        req.prefill_start = t0 + 0.25          # queue = 0.25
        req.first_token_time = t0 + 0.40       # ttft = 0.40
        obs.on_first_token(req)
        req.output_tokens = list(range(5))     # 5 tokens
        req.finish_time = t0 + 1.40            # e2e = 1.40
        req.state, req.finish_reason = "finished", "stop"
        obs.on_finish(req, "stop")
        R = registry.REGISTRY

        def _stats(name):
            return R.get(name).stats(tier="gold")

        assert _stats("serving_queue_seconds")["sum"] == pytest.approx(0.25)
        assert _stats("serving_ttft_seconds")["sum"] == pytest.approx(0.40)
        assert _stats("serving_e2e_seconds")["sum"] == pytest.approx(1.40)
        # TPOT = (finish - first_token) / (n - 1) = 1.0 / 4
        assert _stats("serving_tpot_seconds")["sum"] == pytest.approx(0.25)
        assert _stats("serving_tpot_seconds")["count"] == 1
        # decode rate = (n - 1) / (finish - first) = 4.0
        assert _stats("serving_decode_tokens_per_s")["sum"] == \
            pytest.approx(4.0)
        assert R.get("serving_goodput_tokens_total").value(tier="gold") == 5

    def test_quantile_linear_interpolation(self):
        h = registry.histogram("q_test_seconds", buckets=(1.0, 2.0, 4.0),
                               always=True)
        assert math.isnan(h.quantile(0.5))   # empty: well-defined nan
        for v in (0.5, 1.5, 1.5, 3.0):
            h.observe(v)
        # ranks: bucket<=1 holds 1, <=2 holds 3, <=4 holds 4
        assert h.quantile(0.0) == pytest.approx(0.0)
        assert h.quantile(0.5) == pytest.approx(1.5)  # 2/4 -> mid bucket 2
        assert h.quantile(1.0) == pytest.approx(4.0)
        h.observe(100.0)                               # +Inf bucket
        assert h.quantile(1.0) == pytest.approx(4.0)   # clamped to last

    def test_quantile_degenerate_rows(self):
        """Empty row -> nan for EVERY q; single observation -> the sole
        value exactly (not a bucket midpoint interpolation)."""
        h = registry.histogram("q_edge_seconds", buckets=(1.0, 2.0, 4.0),
                               always=True)
        for q in (0.0, 0.5, 0.95, 1.0):
            assert math.isnan(h.quantile(q))
        h.observe(1.7)
        for q in (0.0, 0.5, 0.99, 1.0):
            assert h.quantile(q) == pytest.approx(1.7)
        # unknown label rows stay nan, never a crash
        hl = registry.histogram("q_edge_lbl_seconds", buckets=(1.0,),
                                labelnames=("tier",), always=True)
        assert math.isnan(hl.quantile(0.5, tier="nope"))
        hl.observe(0.25, tier="gold")
        assert hl.quantile(0.5, tier="gold") == pytest.approx(0.25)

    def test_rollup_quantiles_merge_label_rows(self):
        h = registry.histogram("q_roll_seconds", buckets=(1.0, 2.0, 4.0),
                               labelnames=("replica",), always=True)
        assert h.rollup_quantiles() == {}     # nothing observed anywhere
        h.observe(0.5, replica="a")
        h.observe(3.0, replica="b")
        h.observe(3.0, replica="b")
        h.observe(3.0, replica="b")
        roll = h.rollup_quantiles(qs=(0.5, 0.95))
        # merged ranks: <=1 holds 1, <=4 holds 4 -> p95 in the top bucket
        assert set(roll) == {"p50", "p95"}
        assert 2.0 <= roll["p95"] <= 4.0
        assert roll["p50"] <= roll["p95"]

    def test_tier_label_rides_through(self, metrics_on):
        eng = _engine()
        eng.submit([1, 2, 3, 4], max_new_tokens=2, tier="bulk")
        eng.run_until_idle()
        h = registry.REGISTRY.get("serving_ttft_seconds")
        assert h.stats(tier="bulk")["count"] == 1
        assert h.stats(tier="default")["count"] == 0


# ------------------------------------------------ engine-counter views
class TestEngineStatsViews:
    def test_per_engine_isolation_and_int_reads(self):
        a, b = EngineStats(new_engine_id()), EngineStats(new_engine_id())
        a.inc("prefill_tokens", 7)
        a.inc("prefill_programs")
        assert a["prefill_tokens"] == 7 and isinstance(
            a["prefill_tokens"], int)
        assert b["prefill_tokens"] == 0
        with pytest.raises(KeyError):
            a.inc("nonsense")
        with pytest.raises(KeyError):
            a["nonsense"]

    def test_engine_attrs_are_registry_backed(self, metrics_on):
        eng = _engine()
        eng.generate([[1, 2, 3, 4, 5]], max_new_tokens=2)
        assert eng.prefill_programs >= 1
        assert eng.prefill_tokens == 5
        ev = registry.REGISTRY.get("serving_engine_events_total")
        assert ev.value(engine=eng._stats._eid,
                        event="prefill_tokens") == 5.0
        # stats() JSON keeps its r11 shape
        s = eng.stats()
        for k in ("steps", "kv", "prefix_cache", "prefill_programs",
                  "batched_prefills", "prefill_tokens", "cow_admissions",
                  "dedup_admissions", "speculative", "waiting", "running"):
            assert k in s
        assert s["kv"]["conservation_ok"] is True


# ---------------------------------------------------- prometheus round-trip
class TestPrometheusRoundTrip:
    def test_scrape_parses_back(self, metrics_on):
        eng = _engine()
        eng.generate([[1, 2, 3, 4, 5, 6]], max_new_tokens=3)
        parsed = sinks.parse_prometheus_text(
            sinks.prometheus_text(registry.default_registry()))
        ttft_count = parsed[("serving_ttft_seconds_count",
                             (("tier", "default"),))]
        assert ttft_count == 1.0
        assert ("serving_e2e_seconds_sum", (("tier", "default"),)) in parsed
        assert ("serving_kv_blocks_used", ()) in parsed
        occ = [k for k in parsed if k[0] == "serving_slot_occupancy"]
        assert occ, "per-tick gauge missing from scrape"
        events = [k for k in parsed
                  if k[0] == "serving_engine_events_total"]
        assert any(dict(lbls).get("event") == "prefill_tokens"
                   for _, lbls in events)


# --------------------------------------------- anomaly -> flight dump
def _tick(step, **kw):
    rec = {"kind": "serving_tick", "step": step, "ts": 0.0,
           "running": 1, "waiting": 0, "kv_conservation_breach": 0.0}
    rec.update(kw)
    return rec


class TestServingAnomalies:
    def test_goodput_collapse_dumps_with_offending_trace(self, metrics_on):
        flags.set_flags({"serving_anomaly": "on"})
        eng = _engine()
        req = eng.submit([1, 2, 3, 4], max_new_tokens=3)
        eng.run_until_idle()
        obs = eng.obs
        for i in range(12):
            obs.observe_record(_tick(i, goodput_tokens_per_s=100.0))
        for i in range(12, 18):
            obs.observe_record(_tick(i, goodput_tokens_per_s=4.0))
        assert obs.dumps, "collapse did not dump"
        with open(obs.dumps[0]) as f:
            payload = json.load(f)
        assert payload["anomaly"]["kind"] == "goodput_collapse"
        recs = payload["serving_requests"]
        mine = [r for r in recs if r["request_id"] == req.request_id]
        assert mine and mine[0]["trace"], "offending request trace missing"
        assert mine[0]["trace"][-1]["name"] == "serving.finish"
        assert payload["serving_ticks"], "tick snapshots missing"
        # shared naming/dir scheme with the training dumps
        base = os.path.basename(obs.dumps[0])
        assert base.startswith("flight_") and base.endswith(
            "_serving_goodput_collapse.json")
        assert "/flight/" in obs.dumps[0]
        # healthz flips to anomalous -> 503 semantics
        snap = obs.health_snapshot()
        assert snap["status"] == "anomalous" and snap["ok"] is False

    def test_conservation_breach_fires_immediately(self, metrics_on):
        flags.set_flags({"serving_anomaly": "on"})
        eng = _engine()
        evs = eng.obs.observe_record(_tick(0, kv_conservation_breach=1.0))
        assert [e["kind"] for e in evs] == ["kv_conservation_breach"]

    def test_detector_semantics_standalone(self):
        # TTFT regression: 3x the median, sustained for patience ticks
        d = TTFTRegression()
        evs = [d.observe({"step": i, "ttft_s": 0.01}) for i in range(10)]
        assert not any(evs)
        evs = [d.observe({"step": 10 + i, "ttft_s": 0.2}) for i in range(4)]
        assert any(e is not None for e in evs)
        # goodput: an idle engine is never a collapse
        g = GoodputCollapse()
        for i in range(10):
            g.observe({"step": i, "goodput_tokens_per_s": 50.0,
                       "running": 1, "waiting": 0})
        for i in range(10, 20):
            assert g.observe({"step": i, "goodput_tokens_per_s": 1.0,
                              "running": 0, "waiting": 0}) is None
        # cache-hit collapse fires below half the rolling median
        c = CacheHitCollapse()
        for i in range(10):
            c.observe({"step": i, "prefix_hit_rate": 0.8})
        fired = [c.observe({"step": 10 + i, "prefix_hit_rate": 0.1})
                 for i in range(4)]
        assert any(fired)
        # breach detector needs no warm-up
        b = KVConservationBreach()
        assert b.observe({"step": 0, "kv_conservation_breach": 1.0})
        kinds = {d.kind for d in serving_default_detectors()}
        assert kinds == {"ttft_regression", "goodput_collapse",
                         "cache_hit_collapse", "kv_conservation_breach"}

    def test_anomaly_off_is_inert(self, metrics_on):
        flags.set_flags({"serving_anomaly": "off"})
        eng = _engine()
        for i in range(20):
            evs = eng.obs.observe_record(_tick(i, kv_conservation_breach=1.0))
            assert evs == []
        assert eng.obs.dumps == []


# --------------------------------------------------------- HTTP surface
class TestServingServerEndpoints:
    def test_metrics_healthz_stats(self, metrics_on):
        eng = _engine()
        srv = ServingServer(eng, port=0)
        try:
            url = srv.url()
            gen = urllib.request.Request(
                url + "/generate",
                data=json.dumps({"prompt": [1, 2, 3, 4],
                                 "max_new_tokens": 3,
                                 "tier": "gold"}).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(gen, timeout=60) as r:
                out = json.loads(r.read())
            assert out["telemetry"]["tier"] == "gold"
            with urllib.request.urlopen(url + "/metrics", timeout=10) as r:
                assert r.status == 200
                text = r.read().decode()
            parsed = sinks.parse_prometheus_text(text)
            assert parsed[("serving_ttft_seconds_count",
                           (("tier", "gold"),))] == 1.0
            with urllib.request.urlopen(url + "/healthz", timeout=10) as r:
                snap = json.loads(r.read())
            assert snap["ok"] is True and snap["status"] == "ok"
            assert snap["steps"] >= 1 and "last_tick_age_s" in snap
            with urllib.request.urlopen(url + "/stats", timeout=10) as r:
                stats = json.loads(r.read())
            assert stats["kv"]["conservation_ok"] is True
        finally:
            srv.stop()

    def test_stats_consistent_under_concurrent_streaming(self, metrics_on):
        """Scrape /stats in a tight loop while requests stream: every
        snapshot must be internally consistent (taken under the engine
        lock), e.g. running never exceeds slots and the KV conservation
        law holds in every single scrape."""
        eng = _engine()
        srv = ServingServer(eng, port=0)
        bad = []

        def scrape():
            for _ in range(40):
                with urllib.request.urlopen(srv.url() + "/stats",
                                            timeout=10) as r:
                    s = json.loads(r.read())
                if (not s["kv"]["conservation_ok"]
                        or s["running"] > eng.max_slots):
                    bad.append(s)

        t = threading.Thread(target=scrape)
        try:
            t.start()
            for _ in range(6):
                reqs = [eng.submit([1, 2, 3, 4, 5], max_new_tokens=4)
                        for _ in range(3)]
                for r in reqs:
                    r.wait(60)
            t.join(30)
            assert not bad, bad[:2]
        finally:
            srv.stop()


# ------------------------------------------------------ metrics-off no-op
class TestMetricsOffNoop:
    def test_off_mode_records_nothing_extra(self):
        eng = _engine()
        req = eng.submit([1, 2, 3, 4], max_new_tokens=3)
        eng.run_until_idle()
        obs = eng.obs
        assert req.trace is None
        assert list(obs._ticks) == []
        assert obs._anomaly is None and obs.dumps == []
        assert spans.tail(10) == []
        # gauges never set; always-on SLO histograms still count (the
        # /stats contract predates FLAGS_metrics)
        R = registry.REGISTRY
        assert R.get("serving_slot_occupancy").value() == 0.0
        assert R.get("serving_ttft_seconds").stats(
            tier="default")["count"] == 1
        # health snapshot still works without metrics
        assert obs.health_snapshot()["ok"] is True

    def test_outputs_identical_with_metrics_on_and_off(self, tmp_path):
        """Recording spans, traces and gauges changes no token."""
        prompts = [list(range(1, 9)), [7, 3, 5], list(range(20, 40))]
        paddle.seed(5)
        off = _engine().generate(prompts, max_new_tokens=6)
        flags.set_flags({"metrics": "on",
                         "metrics_dir": str(tmp_path / "metrics")})
        paddle.seed(5)                         # the same weights again
        on = _engine().generate(prompts, max_new_tokens=6)
        assert spans.tail(10)                  # it really recorded
        assert on == off

    def test_tick_begin_is_cheap_noop(self):
        eng = _engine()
        obs = eng.obs
        obs.tick_begin()
        # with nothing recording every span is the one shared no-op
        assert obs.span("serving.tick") is spans.NOOP
        assert obs.span("serving.decode", [], batch=0) is spans.NOOP
        obs.on_tick({"admitted": 0, "decoded_tokens": 0, "running": 0,
                     "waiting": 0, "prefilling": 0, "free_slots": 2,
                     "reserved_blocks": 0})
        assert obs.last_tick_ts is not None  # liveness still tracked
        assert list(obs._ticks) == []
