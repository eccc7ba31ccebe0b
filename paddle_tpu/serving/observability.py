"""Serving-side observability: request traces, SLO metrics, flight arm.

The training stack got a full observability layer in r9/r10 (telemetry,
metrics registry, spans, anomaly engine, flight recorder); the serving
engine grew to production shape with only ad-hoc `/stats` dicts. This
module closes that gap — it is the telemetry substrate the multi-replica
fleet/router work consumes (SLO-aware admission and shedding need
per-request TTFT/TPOT/goodput, not aggregate averages):

  * ``RequestTrace`` — per-request lifecycle spans (queue-wait, admission,
    each prefill chunk, decode ticks, speculative verify, rollback,
    finish/cancel) recorded through the process-wide ``observability.spans``
    ring, so a ``profiler.Profiler`` session collects them into its
    chrome-trace export and a jax profiler session gets them as
    annotations on its host plane; ``export_request_trace`` writes one
    request's own spans as a standalone chrome trace. The engine opens its
    spans with ``with obs.span(name, reqs, **args):`` around the work;
    only the two whose ends are request timestamps (``serving.queue``,
    ``serving.admit``) are appended after the fact, and so are ring-only.
  * SLO metrics on the shared registry, labeled by admission ``tier``
    (one tier today — "default" — the label is the seam the router's
    priority classes plug into): TTFT, TPOT (mean inter-token latency),
    queue time and e2e latency histograms; goodput token and shed request
    counters. All ``always=True`` like the rest of the serving_* family —
    serving runs don't require FLAGS_metrics.
  * Engine gauges sampled every TICK_SAMPLE engine ticks
    (FLAGS_metrics-gated — with metrics off the tick path is a flag
    check): slot occupancy,
    batch size, rolling prefix-cache hit rate, speculative acceptance.
    Block-pool live/evictable/free gauges are published by the allocator
    itself (blocks.py, always on).
  * A serving flight-recorder arm: bounded rings of finished request
    records (telemetry + trace) and engine tick snapshots, auto-dumped
    through the SAME ``flight_recorder.dump`` path as training (one
    naming/dir scheme under FLAGS_metrics_dir/flight) when a serving
    anomaly detector fires — TTFT regression, goodput collapse, cache-hit
    collapse, allocator conservation breach (observability/anomaly.py,
    same rolling-window engine as the training detectors).

Everything here is host-side and engine-lock-friendly: hooks are invoked
by the engine while it already holds ``engine._lock``, and the only
cross-thread readers (the HTTP handlers) go through snapshot methods.
"""
from __future__ import annotations

import itertools
import json
import os
import time
from collections import deque
from typing import Any, Dict, List, Optional

from ..core.flags import define_flag, get_flag
from ..observability import anomaly as _anomaly
from ..observability import flight_recorder as _flight
from ..observability import spans as _spans
from ..observability.registry import (
    counter as _counter,
    gauge as _gauge,
    histogram as _histogram,
    metrics_enabled,
)

define_flag("serving_metrics_port", 0,
            "Also serve the process-wide GET /metrics (Prometheus text) + "
            "/healthz on this dedicated port from the serving process "
            "(observability/serve.py machinery); 0 disables. The "
            "ServingServer's own port always answers GET /metrics and "
            "/healthz regardless.")
define_flag("serving_anomaly", "auto",
            "Serving anomaly detectors (TTFT regression, goodput collapse, "
            "cache-hit collapse, KV conservation breach) over per-tick "
            "records: 'auto' follows FLAGS_anomaly, 'on'/'off' override it. "
            "Needs FLAGS_metrics=on either way.")

_TRUE = ("1", "on", "true", "yes")

#: healthz: engine has work but no tick for this long => status "stale"
STALE_AFTER_S = 60.0
#: healthz: anomalies within this window => status "anomalous"
ANOMALY_RECENT_S = 300.0

# ---------------------------------------------------------------- metrics
# SLO histograms/counters are labeled by admission tier ("default" until
# the router's priority classes land) and always=True like every other
# serving_* metric: the legacy /stats contract predates FLAGS_metrics.
_TTFT_H = _histogram("serving_ttft_seconds",
                     "Arrival -> first token, per request.",
                     labelnames=("tier",), always=True)
_QUEUE_H = _histogram("serving_queue_seconds",
                      "Arrival -> prefill start, per request.",
                      labelnames=("tier",), always=True)
_TPOT_H = _histogram("serving_tpot_seconds",
                     "Mean inter-token latency (time per output token "
                     "after the first), per request.",
                     labelnames=("tier",), always=True)
_E2E_H = _histogram("serving_e2e_seconds",
                    "Arrival -> finish, per request.",
                    labelnames=("tier",), always=True)
_TOKRATE_H = _histogram("serving_decode_tokens_per_s",
                        "Per-request steady-state decode rate.",
                        labelnames=("tier",), always=True)
_GEN_TOKENS = _counter("serving_generated_tokens_total",
                       "Tokens generated across all requests.", always=True)
_PREFILL_TOKENS = _counter("serving_prefill_tokens_total",
                           "Prompt tokens actually computed by prefill "
                           "(cache hits skip theirs).", always=True)
_GOODPUT_TOKENS = _counter("serving_goodput_tokens_total",
                           "Tokens delivered by requests that finished "
                           "normally (stop/length) — shed, cancelled and "
                           "timed-out work excluded.",
                           labelnames=("tier",), always=True)
_SHED = _counter("serving_shed_requests_total",
                 "Requests evicted before normal completion, by reason "
                 "(timeout, disconnect, cancelled, shed).",
                 labelnames=("tier", "reason"), always=True)

SUBMIT_LOCK_WAIT_H = _histogram(
    "serving_submit_lock_wait_seconds",
    "submit(): entry -> engine lock held. The lock is held all through a "
    "tick, so under load this is what a request waits before it is even "
    "queued.", always=True)
PROGRAMS_BUILT = _counter(
    "serving_programs_built_total",
    "Engine programs built for a shape not seen before, by kind (prefill, "
    "scatter, gather, batched_prefill, admit, decode, ...): each is a "
    "compile that stalls every running request.",
    labelnames=("kind",), always=True)

FETCHES = _counter(
    "serving_fetches_total",
    "Waits for token values on the host, by whether the engine had "
    "dispatched later work before it waited (`under_dispatch`: the device "
    "runs on under the wait and under the host's bookkeeping after it) or "
    "had not (`exposed`: once the values are in, the device idles until "
    "the host dispatches again).", labelnames=("kind",), always=True)
OVERSHOOT_TOKENS = _counter(
    "serving_overshoot_tokens_total",
    "Tokens decoded past a request's stop or length and dropped: the step "
    "dispatched before the host had the token that finished it (an eos is "
    "found a tick late), and a fused chunk's tail.", always=True)

# per-tick engine gauges: FLAGS_metrics-gated (stats() is the always-on
# view of the same numbers)
_SLOT_OCC = _gauge("serving_slot_occupancy",
                   "Running sequences / decode slots, sampled per tick.")
_BATCH = _gauge("serving_batch_size",
                "Sequences in the decode batch, sampled per tick.")
_HIT_RATE = _gauge("serving_prefix_hit_rate",
                   "Rolling prefix-cache hit rate (cached prompt tokens / "
                   "prompt tokens over recent admissions).")
_SPEC_ACC = _gauge("serving_spec_acceptance",
                   "Cumulative speculative acceptance (accepted / "
                   "proposed draft tokens), sampled per tick.")
_GOODPUT_G = _gauge("serving_goodput_tokens_per_s",
                    "Decoded tokens per second over the recent tick "
                    "window, sampled per tick.")

#: finish reasons that count as delivered work (everything else is shed).
#: "prefill_complete" is the disaggregated prefill-only finish: the KV it
#: computed is the product, not the (zero) output tokens.
_GOOD_REASONS = ("stop", "length", "prefill_complete")

_ENGINE_SEQ = itertools.count()


def new_engine_id() -> str:
    """Unique per-process engine label for serving_engine_events_total."""
    return f"engine{next(_ENGINE_SEQ)}"

_ENGINE_EVENTS = _counter(
    "serving_engine_events_total",
    "Per-engine serving counters (prefill dispatches/tokens, cache "
    "admissions, speculation ticks), labeled by engine instance — the "
    "registry backing for ServingEngine's historical int attributes "
    "(thin views, same pattern as autotune._STATS).",
    labelnames=("engine", "event"), always=True)


class EngineStats:
    """Dict-shaped thin view over serving_engine_events_total{engine=...}.

    ServingEngine's historical counter attributes (prefill_programs,
    cow_admissions, ...) read through this, so one registry snapshot /
    Prometheus scrape carries every engine's counters while `/stats` and
    the bench deltas keep their int semantics. Per-engine label keeps
    engines isolated (tests build several engines per process)."""

    _KEYS = ("prefill_programs", "batched_prefills", "prefill_tokens",
             "cow_admissions", "dedup_admissions", "spec_ticks",
             "spec_proposed", "spec_accepted", "spec_rollbacks")

    __slots__ = ("_eid",)

    def __init__(self, engine_id: str):
        self._eid = str(engine_id)

    def inc(self, key: str, amount: int = 1) -> None:
        if key not in self._KEYS:
            raise KeyError(key)
        _ENGINE_EVENTS.inc(amount, engine=self._eid, event=key)

    def __getitem__(self, key: str) -> int:
        if key not in self._KEYS:
            raise KeyError(key)
        return int(_ENGINE_EVENTS.value(engine=self._eid, event=key))

    def __iter__(self):
        return iter(self._KEYS)

    def as_dict(self) -> Dict[str, int]:
        return {k: self[k] for k in self._KEYS}


def serving_anomaly_on() -> bool:
    """Serving detectors run when FLAGS_metrics=on and FLAGS_serving_anomaly
    says so ('auto' defers to FLAGS_anomaly)."""
    if not metrics_enabled():
        return False
    mode = str(get_flag("serving_anomaly")).lower()
    if mode in _TRUE:
        return True
    if mode == "auto":
        return str(get_flag("anomaly")).lower() in _TRUE
    return False


class RequestTrace:
    """Per-request span list, mirrored into the global spans ring.

    Attached to a Request at submit when span recording is enabled
    (FLAGS_metrics=on, an open ``Profiler`` session or a live jax profiler
    trace). Spans the engine opens around its work (``ServingObservability
    .span``) are recorded once and land in each participant's list by
    reference; ``add`` is for the spans made after the fact from request
    timestamps (ring + local list). Bounded so one long-running request
    cannot grow without bound."""

    MAX_SPANS = 1024

    __slots__ = ("request_id", "tier", "spans", "ctx", "slot")

    def __init__(self, request_id: str, tier: str = "default",
                 ctx: Optional[Dict[str, Any]] = None):
        self.request_id = str(request_id)
        self.tier = str(tier)
        # fleet trace context (fleet_request_id / attempt / cause) stamped
        # by the router at dispatch: baked into every request-scoped
        # span's args so a cross-replica merge needs no re-tagging.
        # Batch-scoped spans (shared dict, see _EngineSpan) are tagged at
        # export time on copies instead.
        self.ctx = dict(ctx) if ctx else None
        # decode slot, captured at admission (the scheduler clears
        # req.slot at finish; the merged fleet trace wants tid=slot)
        self.slot: Optional[int] = None
        self.spans: deque = deque(maxlen=self.MAX_SPANS)

    def scoped(self, args: Dict[str, Any]) -> Dict[str, Any]:
        """`args` of a request-scoped span: the request's id and fleet
        trace context first."""
        return {"request_id": self.request_id, **(self.ctx or {}), **args}

    def add(self, name: str, begin_ns: int, end_ns: int, **args) -> None:
        """Record a request-scoped span whose ends are request timestamps
        (local list + global ring; ring-only, see spans.record_span)."""
        self.spans.append(_spans.record_span(
            name, begin_ns, end_ns, cat="serving", args=self.scoped(args)))

    def names(self) -> List[str]:
        return [s["name"] for s in self.spans]


def chrome_trace_events(span_dicts, *, pid: Optional[int] = None,
                        tid: Optional[int] = None,
                        extra_args: Optional[Dict[str, Any]] = None
                        ) -> List[Dict[str, Any]]:
    """Convert ring-format span dicts to chrome-trace complete events
    (the same event shape profiler/xplane.py merges).

    Every event gets its OWN args dict (deep-copied from the span): the
    engine appends one shared per-tick span dict by reference to every
    traced participant (_EngineSpan), so tagging export-time fields on the
    original would corrupt every other request's trace. `pid`/`tid`
    override the lane (the fleet merge maps pid=replica, tid=slot);
    `extra_args` fills attribution keys (attempt/cause) without
    clobbering anything the span already carries."""
    default_pid = os.getpid() if pid is None else pid
    out = []
    for s in span_dicts:
        begin = int(s.get("begin_ns", 0))
        args = dict(s.get("args") or {})
        if extra_args:
            for k, v in extra_args.items():
                args.setdefault(k, v)
        out.append({"name": s.get("name", "?"), "ph": "X",
                    "cat": s.get("cat", "serving"),
                    "ts": begin / 1e3,
                    "dur": max(int(s.get("end_ns", begin)) - begin, 0) / 1e3,
                    "pid": default_pid,
                    "tid": s.get("tid", 0) if tid is None else tid,
                    "args": args})
    return out


def export_request_trace(req, path: str) -> str:
    """Write one request's lifecycle spans as a standalone chrome trace
    (chrome://tracing / Perfetto). ``req`` is a Request with an attached
    trace, or a RequestTrace directly. Raises ValueError when the request
    was never traced (metrics were off at submit)."""
    trace = req if isinstance(req, RequestTrace) else getattr(req, "trace",
                                                              None)
    if trace is None:
        raise ValueError("request has no trace (was FLAGS_metrics on when "
                         "it was submitted?)")
    payload = {"traceEvents": chrome_trace_events(list(trace.spans)),
               "displayTimeUnit": "ms"}
    with open(path, "w", encoding="utf-8") as f:
        json.dump(payload, f)
    return path


class _EngineSpan(_spans.Span):
    """A span the engine opens around work done for `reqs`: recorded once,
    and its ring dict handed by reference to every traced participant —
    this runs every engine tick for every running request, so per-request
    dict construction is exactly the overhead the <=3% budget forbids."""

    __slots__ = ("_reqs",)

    def __init__(self, name: str, reqs, args):
        super().__init__(name, "serving", args)
        self._reqs = reqs

    def __exit__(self, *exc):
        super().__exit__(*exc)
        for req in self._reqs:
            if req.trace is not None:
                req.trace.spans.append(self.record)
        return False


class ServingObservability:
    """Per-engine observability hub: the engine opens ``span``s around its
    work and calls the ``on_*`` hooks under its own lock; HTTP handlers
    read through ``health_snapshot``.

    Cheap when nothing records: ``span`` hands out the shared no-op after
    three reads (spans.enabled), ``tick_begin``/``on_tick`` reduce to a
    flag check + one attribute write, traces are never attached, and the
    SLO histogram observes (always-on by contract) were already paid by
    the pre-r16 engine."""

    #: flight-recorder arm: how many finished request records (telemetry
    #: + trace) and engine tick snapshots ride along in an anomaly dump
    FLIGHT_REQUESTS = 64
    #: samples in the rolling goodput window
    GOODPUT_WINDOW = 16
    #: recent admissions in the rolling prefix-hit-rate window
    ADMIT_WINDOW = 64
    #: gauge/record sampling stride: the tick hot path only accumulates
    #: decoded-token counts; gauges, the tick snapshot, and the anomaly
    #: detectors run every TICK_SAMPLE-th engine step (per-tick
    #: dict/registry work is host time on the decode path)
    TICK_SAMPLE = 4

    def __init__(self, engine, *, dump: bool = True,
                 dump_cooldown_steps: int = 50):
        self.engine = engine
        self.dump = bool(dump)
        self.dump_cooldown_steps = int(dump_cooldown_steps)
        n = self.FLIGHT_REQUESTS
        self._records: deque = deque(maxlen=n)   # finished request records
        self._ticks: deque = deque(maxlen=n)     # engine tick snapshots
        self._tok_window: deque = deque(maxlen=self.GOODPUT_WINDOW)
        self._admit_window: deque = deque(maxlen=self.ADMIT_WINDOW)
        self._admit_matched = 0   # running sums over _admit_window
        self._admit_total = 0
        self._decoded_acc = 0     # decoded tokens since the last sample
        self._tick_n = 0          # sampling stride counter (first tick
        #                           always samples: short runs still
        #                           produce a snapshot + anomaly record)
        self._ttft_acc: List[float] = []
        self._on = False          # metrics enabled, refreshed per tick
        self._anomaly: Optional[_anomaly.AnomalyEngine] = None
        self._dump_armed_at = -1
        self.last_tick_ts: Optional[float] = None
        self.dumps: List[str] = []

    def span(self, name: str, reqs=(), **args):
        """``with obs.span("serving.decode", reqs, batch=n):`` around the
        work. `reqs` are the requests it is done for: the finished span
        lands in each one's trace, by reference."""
        if not _spans.enabled():
            return _spans.NOOP
        return _EngineSpan(name, tuple(reqs), args)

    def request_span(self, name: str, req, **args):
        """A span of work done for ONE request: its id (and fleet trace
        context) lead the args."""
        if not _spans.enabled():
            return _spans.NOOP
        tr = req.trace
        return _EngineSpan(name, (req,), tr.scoped(args) if tr is not None
                           else {"request_id": req.request_id, **args})

    # -- request lifecycle hooks (engine lock held) ------------------------
    def on_submit(self, req) -> None:
        if _spans.enabled():
            req.trace = RequestTrace(req.request_id, req.tier,
                                     ctx=getattr(req, "trace_ctx", None))

    def on_shed(self, req, reason: str) -> None:
        """Request rejected at admission (never entered the queue): shed
        accounting only — no trace, no SLO samples, it did no work."""
        _SHED.inc(tier=req.tier, reason=str(reason))

    def on_admitted(self, req) -> None:
        """Queued -> prefill: close the queue-wait span, feed the rolling
        prefix-hit window (running sums — the tick path must not re-sum
        the window)."""
        m, p = req.prefix_matched, len(req.prompt)
        w = self._admit_window
        if len(w) == w.maxlen:
            om, op = w[0]
            self._admit_matched -= om
            self._admit_total -= op
        w.append((m, p))
        self._admit_matched += m
        self._admit_total += p
        tr = req.trace
        if tr is not None:
            tr.slot = req.slot
            if req.prefill_start is not None:
                tr.add("serving.queue", int(req.arrival_time * 1e9),
                       int(req.prefill_start * 1e9),
                       prompt_tokens=len(req.prompt),
                       prefix_matched=req.prefix_matched)

    def on_first_token(self, req) -> None:
        """The first token's value reached the host (the engine has just
        stamped `first_token_time`): SLO queue/TTFT observes + the
        admission span."""
        q = req.queue_seconds()
        if q is not None:
            _QUEUE_H.observe(q, tier=req.tier)
        t = req.ttft_seconds()
        if t is not None:
            _TTFT_H.observe(t, tier=req.tier)
            if self._on:
                self._ttft_acc.append(float(t))
        tr = req.trace
        if tr is not None and req.prefill_start is not None \
                and req.first_token_time is not None:
            tr.add("serving.admit", int(req.prefill_start * 1e9),
                   int(req.first_token_time * 1e9),
                   cached=req._cow_src is not None)

    def on_rollback(self, req, rejected: int) -> None:
        if req.trace is not None:
            with self.request_span("serving.rollback", req,
                                   rejected=int(rejected)):
                pass

    def on_finish(self, req, reason: str) -> None:
        """Any terminal transition (stop/length/cancel/timeout/disconnect):
        SLO e2e + TPOT + goodput/shed accounting, the finish span, and the
        flight-arm request record."""
        tier = req.tier
        n = len(req.output_tokens)
        _GEN_TOKENS.inc(n)
        rate = req.decode_tokens_per_s()
        if rate is not None:
            _TOKRATE_H.observe(rate, tier=tier)
        if req.finish_time is not None:
            _E2E_H.observe(req.finish_time - req.arrival_time, tier=tier)
        if req.first_token_time is not None and req.finish_time is not None \
                and n > 1:
            _TPOT_H.observe((req.finish_time - req.first_token_time)
                            / (n - 1), tier=tier)
        if reason in _GOOD_REASONS:
            _GOODPUT_TOKENS.inc(n, tier=tier)
        else:
            _SHED.inc(tier=tier, reason=str(reason))
        tr = req.trace
        if tr is not None:
            with self.request_span("serving.finish", req,
                                   reason=str(reason), output_tokens=n):
                pass
        if self._on or tr is not None:
            self._records.append(self._request_record(req))

    # -- per-tick sampling -------------------------------------------------
    def tick_begin(self) -> None:
        """Start-of-tick: refresh the cached metrics flag (the engine then
        opens the ``serving.tick`` span itself)."""
        self._on = metrics_enabled()

    def on_tick(self, out: Dict[str, Any]) -> None:
        """End-of-tick, after the tick span has closed: every
        TICK_SAMPLE-th step engine gauges, the tick snapshot record, and
        anomaly detection (+ flight dump). Between samples the hot path is
        one liveness timestamp and a decoded-token accumulate. Called
        under the engine lock."""
        eng = self.engine
        now = time.monotonic()
        self.last_tick_ts = now
        if not self._on:
            return
        self._decoded_acc += int(out["decoded_tokens"])
        n = self._tick_n
        self._tick_n = n + 1
        if n % self.TICK_SAMPLE:
            return
        running = int(out["running"])
        _SLOT_OCC.set(running / eng.max_slots if eng.max_slots else 0.0)
        _BATCH.set(running)
        self._tok_window.append((now, self._decoded_acc))
        rec: Dict[str, Any] = {
            "kind": "serving_tick", "step": eng.steps, "ts": time.time(),
            "decoded_tokens": self._decoded_acc,
            "running": running, "waiting": int(out["waiting"]),
            "kv_conservation_breach":
                0.0 if eng.allocator.conservation_ok() else 1.0,
        }
        self._decoded_acc = 0
        goodput = self._windowed_goodput()
        if goodput is not None:
            rec["goodput_tokens_per_s"] = goodput
            _GOODPUT_G.set(goodput)
        if self._admit_total:
            rate = self._admit_matched / self._admit_total
            rec["prefix_hit_rate"] = rate
            _HIT_RATE.set(rate)
        proposed = eng.spec_proposed
        if proposed:
            _SPEC_ACC.set(eng.spec_accepted / proposed)
        if self._ttft_acc:
            rec["ttft_s"] = sum(self._ttft_acc) / len(self._ttft_acc)
            self._ttft_acc = []
        self._ticks.append(rec)
        self.observe_record(rec)

    def observe_record(self, rec: Dict[str, Any]) -> List[Dict[str, Any]]:
        """Feed one tick record through the serving anomaly detectors;
        dumps the flight arm on detection. Public seam (tests
        inject synthetic records through the same path on_tick uses)."""
        engine = self._anomaly_engine()
        if engine is None:
            return []
        events = engine.observe(rec)
        if events and self.dump:
            self._maybe_dump(events)
        return events

    def _windowed_goodput(self) -> Optional[float]:
        if len(self._tok_window) < 2:
            return None
        t_first = self._tok_window[0][0]
        t_last = self._tok_window[-1][0]
        dt = t_last - t_first
        if dt <= 0:
            return None
        # tokens of every tick after the window's first timestamp
        toks = sum(n for _, n in list(self._tok_window)[1:])
        return toks / dt

    def _anomaly_engine(self) -> Optional[_anomaly.AnomalyEngine]:
        """Lazy: detectors arm the first tick the flags allow it (dump
        handled here, so the shared engine runs with dump=False)."""
        if self._anomaly is None and serving_anomaly_on():
            self._anomaly = _anomaly.AnomalyEngine(
                _anomaly.serving_default_detectors(), dump=False)
        return self._anomaly

    def _maybe_dump(self, events: List[Dict[str, Any]]) -> None:
        step = self.engine.steps
        if step <= self._dump_armed_at:
            return
        self._dump_armed_at = step + self.dump_cooldown_steps
        sched = self.engine.sched
        inflight = [self._request_record(r)
                    for r in list(sched.prefilling)
                    + list(sched.running.values())]
        extra = {
            "anomaly": events[0],
            "serving_anomalies": events,
            "serving_requests": list(self._records) + inflight,
            "serving_ticks": list(self._ticks),
        }
        try:
            path = _flight.get_flight_recorder().dump(
                f"serving_{events[0]['kind']}", extra=extra)
            self.dumps.append(path)
        except OSError:
            pass

    def _request_record(self, req) -> Dict[str, Any]:
        rec = dict(req.telemetry())
        rec["ts"] = time.time()
        tr = req.trace
        if tr is not None:
            rec["trace"] = list(tr.spans)
        return rec

    # -- snapshots (HTTP handlers; takes the engine lock itself) -----------
    def recent_requests(self, n: int = 16) -> List[Dict[str, Any]]:
        with self.engine._lock:
            return list(self._records)[-int(n):]

    def recent_ticks(self, n: int = 16) -> List[Dict[str, Any]]:
        with self.engine._lock:
            return list(self._ticks)[-int(n):]

    def health_snapshot(self, loop_alive: bool = True,
                        stale_after_s: float = STALE_AFTER_S
                        ) -> Dict[str, Any]:
        """The serving /healthz body: one consistent engine snapshot taken
        under the engine lock (load-balancer semantics — 'ok' False means
        take this replica out of rotation; the body says why)."""
        now = time.monotonic()
        eng = self.engine
        with eng._lock:
            counts = eng.sched.counts()
            steps = eng.steps
            has_work = eng.sched.has_work()
            draining = bool(getattr(eng, "_draining", False))
            last_tick = self.last_tick_ts
            anomaly = self._anomaly
        out: Dict[str, Any] = {
            "status": "ok", "ok": True, "steps": steps,
            "last_tick_age_s": (round(now - last_tick, 3)
                                if last_tick is not None else None),
            **counts,
        }
        if not loop_alive:
            out["status"], out["ok"] = "dead", False
            return out
        recent = []
        if anomaly is not None:
            wall = time.time()
            recent = [a for a in anomaly.recent()
                      if wall - float(a.get("ts", 0)) <= ANOMALY_RECENT_S]
        out["anomalies_recent"] = len(recent)
        if recent:
            out["status"], out["ok"] = "anomalous", False
            out["last_anomaly"] = {k: v for k, v in recent[-1].items()
                                   if k in ("kind", "step", "value")}
        elif has_work and last_tick is not None \
                and now - last_tick > float(stale_after_s):
            out["status"], out["ok"] = "stale", False
        elif draining:
            # deliberate drain: not a fault, but ok=False so a load
            # balancer stops routing here while in-flight work finishes
            out["status"], out["ok"] = "draining", False
        elif steps == 0 and not has_work:
            out["status"] = "idle"
        return out
