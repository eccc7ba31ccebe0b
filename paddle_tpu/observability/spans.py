"""Unified span tracing across subsystems.

One process-wide bounded span ring that every runtime component writes
through `span("name")`: TrainStep dispatch, DevicePrefetcher waits,
grad-bucket construction, CheckpointManager save/commit, collective init,
the serving engine's tick and what it does inside one, and
`profiler.RecordEvent`'s pure-Python fallback.

Every span in the ring has an `id` (its sequence number) and a `parent`
(the id of the span that enclosed it on the same thread, None at the top),
so a layer's self time is its duration minus its children's.

Consumers:

  * a live jax profiler session (`jax.profiler.start_trace`, the
    benchmark's `--trace 1`, the `Profiler` with a device target) — while
    one is live, a span also opens a `jax.profiler.TraceAnnotation` of the
    same name and args, so it is written into the profiler's own host
    plane, on the profiler's clock, beside the device planes of the same
    `.xplane.pb`. Ring and annotation come from one `with`, so they cannot
    disagree;
  * the native HostTracer (native/src/tracer.cc) — while a `Profiler`
    session records through it (`session(True, native=True)`), spans are
    mirrored through trace_push/trace_pop so they land in the chrome-trace
    merge (profiler/xplane.py) exactly like hand-annotated RecordEvents;
  * the profiler's pure-Python fallback — when the native library is
    absent, `Profiler` collects spans from THIS ring between start/stop;
  * the crash flight recorder and whoever looks for a stall — `tail(n)`
    returns the most recent spans regardless of any profiler session;
  * the benchmark's per-layer span metrics, which read `since(0)` after a
    traced window.

Clock of the ring: time.monotonic_ns(), the same steady clock family as the
native tracer's now_ns. The annotation is on the profiler's clock.

Recording is gated: a span records when FLAGS_metrics is on, a `Profiler`
session is open, or a jax profiler session is live. With all three off
`span()` reads one flag, one integer and `TraceAnnotation.is_enabled()`,
and returns the shared no-op: nothing is constructed or appended.
"""
from __future__ import annotations

import threading
import time
from collections import deque
from typing import Dict, List, Optional

from jax.profiler import TraceAnnotation as _TraceAnnotation

from .. import native as _native
from .registry import metrics_enabled

_MAX_SPANS = 65536

_lock = threading.Lock()
_ring: deque = deque(maxlen=_MAX_SPANS)
_seq = 0
_session_depth = 0  # Profiler sessions currently open
_native_depth = 0   # ... of which record through the native HostTracer
_tls = threading.local()  # .stack: ids of the spans open on this thread


def session(on: bool, native: bool = False) -> None:
    """Open/close a `Profiler` recording session (profiler/). `native`
    says the session records through the native HostTracer, which the
    profiler has switched on: spans are then mirrored into it."""
    global _session_depth, _native_depth
    step = 1 if on else -1
    with _lock:
        _session_depth = max(_session_depth + step, 0)
        if native:
            _native_depth = max(_native_depth + step, 0)


def enabled() -> bool:
    return (_session_depth > 0 or metrics_enabled()
            or _TraceAnnotation.is_enabled())


def mark() -> int:
    """Sequence watermark; `since(mark())` later returns spans begun
    after this point (profiler fallback session collection)."""
    with _lock:
        return _seq


def _next_id() -> int:
    global _seq
    with _lock:
        _seq += 1
        return _seq


def _append(span_id: int, name: str, begin_ns: int, end_ns: int, cat: str,
            args: Optional[Dict], parent: Optional[int]) -> Dict:
    span_d = {
        "name": str(name),
        "begin_ns": int(begin_ns),
        "end_ns": int(end_ns),
        "tid": threading.get_ident() & 0xFFFF,
        "cat": cat,
        "id": span_id,
        "parent": parent,
    }
    if args:
        span_d["args"] = args
    with _lock:
        _ring.append((span_id, span_d))
    return span_d


def record_span(name: str, begin_ns: int, end_ns: int, cat: str = "span",
                args: Optional[Dict] = None) -> Dict:
    """Append one completed span whose ends the caller took itself
    (monotonic_ns): request timestamps (`serving.queue`, `serving.admit`,
    the fleet's route spans) and the RecordEvent fallback. Ring-only: a
    span that is already over cannot be a TraceAnnotation, so it is not in
    a profiler's trace; and its `parent` is None, because its interval is
    not inside whatever span happens to be open now."""
    return _append(_next_id(), name, begin_ns, end_ns, cat, args, None)


def since(watermark: int) -> List[Dict]:
    with _lock:
        return [s for q, s in _ring if q > watermark]


def tail(n: int = 200) -> List[Dict]:
    with _lock:
        items = list(_ring)[-int(n):]
    return [s for _, s in items]


def clear() -> None:
    global _seq
    with _lock:
        _ring.clear()
        _seq = 0


class Span:
    """One recording span: ring entry, profiler annotation and native
    mirror from one `with`. Made by `span()`, which hands out NOOP
    instead when nothing records. After the exit `record` is the ring's
    dict."""

    __slots__ = ("name", "cat", "args", "record", "_t0", "_id", "_parent",
                 "_ann", "_native")

    def __init__(self, name: str, cat: str = "span",
                 args: Optional[Dict] = None):
        self.name = name
        self.cat = cat
        self.args = args
        self.record = None
        self._ann = None

    def set(self, **args) -> None:
        """Args known only once the work is done (a tick's decoded tokens,
        a fetch's token count): into the ring's dict and, while the
        annotation is open, into the profiler's event."""
        self.args = {**self.args, **args} if self.args else args
        if self._ann is not None:
            self._ann.set_metadata(**args)

    def __enter__(self):
        stack = getattr(_tls, "stack", None)
        if stack is None:
            stack = _tls.stack = []
        self._parent = stack[-1] if stack else None
        self._id = _next_id()
        stack.append(self._id)
        self._native = _native_depth > 0
        if self._native:
            _native.trace_push(self.name)
        if _TraceAnnotation.is_enabled():
            self._ann = _TraceAnnotation(self.name, **(self.args or {}))
            self._ann.__enter__()
        self._t0 = time.monotonic_ns()
        return self

    def __exit__(self, *exc):
        t1 = time.monotonic_ns()
        if self._ann is not None:
            self._ann.__exit__(*exc)
            self._ann = None
        if self._native:
            _native.trace_pop()
        _tls.stack.pop()
        self.record = _append(self._id, self.name, self._t0, t1, self.cat,
                              self.args, self._parent)
        return False


class _NoopSpan:
    """What `span()` returns while nothing records."""

    __slots__ = ()
    record = None

    def set(self, **args) -> None:
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


NOOP = _NoopSpan()


def span(name: str, cat: str = "span", args: Optional[Dict] = None):
    """Context manager recording one span into the unified ring, and into
    the profiler's trace and the native tracer while those record.

        with span("ckpt.commit", cat="io", args={"step": 7}):
            ...
    """
    return Span(name, cat, args) if enabled() else NOOP
