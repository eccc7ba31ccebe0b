"""paddle.profiler analog.

Reference: python/paddle/profiler/profiler.py (Profiler, ProfilerState:79,
ProfilerTarget:99, make_scheduler, export_chrome_tracing:215), RecordEvent
(utils.py), statistics tables (profiler_statistic.py), benchmark timer
(timer.py), over the C++ unified profiler (paddle/fluid/platform/profiler/
profiler.h:47 with HostTracer/CudaTracer plugins).

TPU-native split (SURVEY.md §5.1): host spans come from the native C++ ring-
buffer tracer (paddle_tpu/native/src/tracer.cc — the HostTracer equivalent);
the device timeline belongs to XLA, surfaced by delegating to jax.profiler
(xplane/tensorboard) when a trace_dir is given. Chrome-trace export merges
host spans; statistics aggregate by event name.
"""
from __future__ import annotations

import enum
import json
import os
import time
from typing import Callable, Iterable, Optional

from .. import native
from ..observability import spans as _obs_spans


class ProfilerState(enum.Enum):
    CLOSED = 0
    READY = 1
    RECORD = 2
    RECORD_AND_RETURN = 3


class ProfilerTarget(enum.Enum):
    CPU = 0
    GPU = 1  # accepted for API parity; maps to the device timeline
    TPU = 2
    CUSTOM_DEVICE = 3


class TracerEventType(enum.Enum):
    Operator = 0
    Dataloader = 1
    ProfileStep = 2
    Forward = 3
    Backward = 4
    Optimization = 5
    Communication = 6
    PythonOp = 7
    UserDefined = 8


def make_scheduler(*, closed: int, ready: int, record: int, repeat: int = 0,
                   skip_first: int = 0) -> Callable[[int], ProfilerState]:
    """Reference: profiler.py make_scheduler — step-indexed state machine."""
    period = closed + ready + record

    def scheduler(step: int) -> ProfilerState:
        if step < skip_first:
            return ProfilerState.CLOSED
        s = step - skip_first
        if repeat > 0 and s >= repeat * period:
            return ProfilerState.CLOSED
        pos = s % period
        if pos < closed:
            return ProfilerState.CLOSED
        if pos < closed + ready:
            return ProfilerState.READY
        if pos == period - 1:
            return ProfilerState.RECORD_AND_RETURN
        return ProfilerState.RECORD

    return scheduler


def _default_state_scheduler(step: int) -> ProfilerState:
    return ProfilerState.RECORD


def export_chrome_tracing(dir_name: str, worker_name: Optional[str] = None
                          ) -> Callable:
    """on_trace_ready handler writing chrome://tracing JSON
    (reference: profiler.py:215)."""

    def handler(prof: "Profiler"):
        os.makedirs(dir_name, exist_ok=True)
        worker = worker_name or f"host_{os.getpid()}"
        path = os.path.join(dir_name, f"{worker}_step{prof.step_num}.json")
        prof.export(path, format="json")
        prof.last_export_path = path

    return handler


def export_protobuf(dir_name: str, worker_name: Optional[str] = None) -> Callable:
    """API-parity handler (reference exports a protobuf dump); emits the same
    chrome JSON payload with a .pb.json suffix."""

    def handler(prof: "Profiler"):
        os.makedirs(dir_name, exist_ok=True)
        worker = worker_name or f"host_{os.getpid()}"
        path = os.path.join(dir_name, f"{worker}_step{prof.step_num}.pb.json")
        prof.export(path, format="json")
        prof.last_export_path = path

    return handler


class RecordEvent:
    """User-annotated host span (reference: paddle.profiler.RecordEvent).

    Falls back to the pure-Python span ring (observability/spans.py) when the
    native library is absent: spans recorded between Profiler.start/stop are
    collected from that ring and merged into the exported chrome trace, so
    annotations survive on hosts without the C++ tracer (r6–r8 silently
    dropped them). Outside a recording context the fallback is a no-op, same
    as the native tracer when disabled.
    """

    def __init__(self, name: str, event_type: TracerEventType = TracerEventType.UserDefined):
        self.name = name
        self.event_type = event_type
        self._begun = False
        self._t0 = 0

    def begin(self):
        self._t0 = 0
        if native.available():
            native.trace_push(self.name)
        elif _obs_spans.enabled():
            self._t0 = time.monotonic_ns()
        self._begun = True

    def end(self):
        if self._begun:
            if native.available():
                native.trace_pop()
            elif self._t0:
                _obs_spans.record_span(self.name, self._t0,
                                       time.monotonic_ns(), cat="user")
        self._begun = False
        self._t0 = 0

    def __enter__(self):
        self.begin()
        return self

    def __exit__(self, *exc):
        self.end()
        return False


def load_profiler_result(path: str):
    with open(path) as f:
        return json.load(f)


class _EventStat:
    __slots__ = ("name", "calls", "total_ns", "max_ns", "min_ns")

    def __init__(self, name):
        self.name = name
        self.calls = 0
        self.total_ns = 0
        self.max_ns = 0
        self.min_ns = 1 << 62

    def add(self, dur):
        self.calls += 1
        self.total_ns += dur
        self.max_ns = max(self.max_ns, dur)
        self.min_ns = min(self.min_ns, dur)

    @property
    def avg_ns(self):
        return self.total_ns // max(self.calls, 1)


class Profiler:
    """Reference: paddle.profiler.Profiler — start/stop/step driven by a
    scheduler; on RECORD_AND_RETURN boundaries the on_trace_ready handler
    fires with the collected spans."""

    def __init__(self, *, targets: Optional[Iterable[ProfilerTarget]] = None,
                 scheduler=None, on_trace_ready: Optional[Callable] = None,
                 timer_only: bool = False, record_shapes: bool = False,
                 profile_memory: bool = False, with_flops: bool = False):
        self.targets = list(targets) if targets else [ProfilerTarget.CPU]
        if scheduler is None:
            self._scheduler = _default_state_scheduler
        elif isinstance(scheduler, (tuple, list)):
            start, end = scheduler
            self._scheduler = make_scheduler(
                closed=max(start, 0), ready=0, record=end - start, repeat=1)
        else:
            self._scheduler = scheduler
        self.on_trace_ready = on_trace_ready or (lambda prof: None)
        self.timer_only = timer_only
        self.step_num = 0
        self.current_state = ProfilerState.CLOSED
        self.last_export_path = None
        self._spans = []
        self._benchmark = _Benchmark()
        self._recording = False
        self._device_trace_dir = None
        self._last_device_dir = None   # kept after stop for export merge
        self._clock_sync = None        # (host steady_ns, epoch_ns) pair
        self._span_mark = 0            # python span-ring watermark (fallback)

    # -- lifecycle ---------------------------------------------------------
    def start(self):
        self._benchmark.begin()
        if self.timer_only:
            return
        self.current_state = self._scheduler(self.step_num)
        if self.current_state in (ProfilerState.RECORD, ProfilerState.RECORD_AND_RETURN):
            self._start_record()

    def stop(self):
        self._benchmark.end()
        if self.timer_only:
            return
        if self._recording:
            self._stop_record()
            self.on_trace_ready(self)

    def step(self, num_samples: Optional[int] = None):
        """Advance the scheduler one training step."""
        self._benchmark.step(num_samples)
        if self.timer_only:
            self.step_num += 1
            return
        prev = self.current_state
        self.step_num += 1
        new = self._scheduler(self.step_num)
        if prev == ProfilerState.RECORD_AND_RETURN and self._recording:
            self._stop_record()
            self.on_trace_ready(self)
        if new in (ProfilerState.RECORD, ProfilerState.RECORD_AND_RETURN) and not self._recording:
            self._start_record()
        elif new == ProfilerState.CLOSED and self._recording and prev != ProfilerState.RECORD_AND_RETURN:
            self._stop_record()
            self.on_trace_ready(self)
        self.current_state = new

    def step_info(self, unit: Optional[str] = None) -> str:
        return self._benchmark.step_info(unit)

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.stop()
        return False

    # -- recording ---------------------------------------------------------
    def _start_record(self):
        # a fresh session must not inherit the previous session's device
        # dump or clock pair — export() would merge stale device lanes
        self._last_device_dir = None
        self._clock_sync = None
        if native.available():
            native.trace_clear()
            native.trace_enable(True)
            # runtime spans mirror themselves into the native tracer
            # while this session says it records there
            _obs_spans.session(True, native=True)
        else:
            # pure-Python fallback: open a span-ring session and note the
            # watermark — stop collects everything recorded after it
            _obs_spans.session(True)
            self._span_mark = _obs_spans.mark()
        if ProfilerTarget.TPU in self.targets or ProfilerTarget.GPU in self.targets:
            # device timeline is XLA's: delegate to jax.profiler (xplane)
            try:
                import jax

                self._device_trace_dir = os.environ.get(
                    "PADDLE_TPU_TRACE_DIR", "/tmp/paddle_tpu_xplane")
                jax.profiler.start_trace(self._device_trace_dir)
                # clock-correspondence sample: host spans are steady_clock
                # ns, xplane timestamps are epoch ns — one paired reading
                # lets export() place both on a single axis
                steady = (native.trace_now_ns() if native.available()
                          else time.monotonic_ns())
                self._clock_sync = (steady, time.time_ns())
            except Exception:
                self._device_trace_dir = None
        self._recording = True

    def _stop_record(self):
        if native.available():
            self._spans = native.trace_spans()
            native.trace_enable(False)
            _obs_spans.session(False, native=True)
        else:
            self._spans = _obs_spans.since(self._span_mark)
            _obs_spans.session(False)
        if self._device_trace_dir is not None:
            try:
                import jax

                jax.profiler.stop_trace()
                self._last_device_dir = self._device_trace_dir
            except Exception:
                pass
            self._device_trace_dir = None
        self._recording = False

    # -- export / stats ----------------------------------------------------
    def export(self, path: str, format: str = "json"):
        """One chrome trace: host spans + the XLA device timeline (parsed
        from the jax.profiler xplane protobufs) on a shared time axis —
        the reference's host+CUPTI merged chrome_tracing_logger, TPU-style
        (SURVEY §5.1)."""
        from .xplane import merged_chrome_trace

        events = merged_chrome_trace(self._spans, self._last_device_dir,
                                     self._clock_sync)
        with open(path, "w") as f:
            json.dump({"traceEvents": events,
                       "displayTimeUnit": "ms"}, f)

    def events(self):
        return list(self._spans)

    def summary(self, sorted_by="total", op_detail=True, thread_sep=False,
                time_unit="ms") -> str:
        """Aggregate spans by name (reference: profiler_statistic.py tables)."""
        stats = {}
        for s in self._spans:
            st = stats.get(s["name"])
            if st is None:
                st = stats[s["name"]] = _EventStat(s["name"])
            st.add(s["end_ns"] - s["begin_ns"])
        div = {"s": 1e9, "ms": 1e6, "us": 1e3, "ns": 1.0}[time_unit]
        rows = sorted(stats.values(), key=lambda st: -st.total_ns)
        lines = [
            f"{'Name':<40} {'Calls':>8} {'Total(' + time_unit + ')':>14} "
            f"{'Avg(' + time_unit + ')':>12} {'Max(' + time_unit + ')':>12}"
        ]
        for st in rows:
            lines.append(
                f"{st.name:<40} {st.calls:>8} {st.total_ns / div:>14.3f} "
                f"{st.avg_ns / div:>12.3f} {st.max_ns / div:>12.3f}"
            )
        return "\n".join(lines)


class _Benchmark:
    """Reader-cost / ips tracker (reference: profiler/timer.py Benchmark)."""

    def __init__(self):
        self.reset()

    def reset(self):
        self._step_start = None
        self._steps = 0
        self._total_time = 0.0
        self._samples = 0

    def begin(self):
        self._step_start = time.perf_counter()

    def end(self):
        pass

    def step(self, num_samples=None):
        now = time.perf_counter()
        if self._step_start is not None:
            self._total_time += now - self._step_start
            self._steps += 1
            if num_samples:
                self._samples += num_samples
        self._step_start = now

    def step_info(self, unit=None):
        if self._steps == 0:
            return "no steps recorded"
        avg = self._total_time / self._steps
        msg = f"avg_step_time: {avg * 1e3:.3f} ms"
        if self._samples:
            ips = self._samples / self._total_time
            msg += f" ips: {ips:.1f} {unit or 'samples'}/s"
        return msg


class benchmark:
    """paddle.profiler.benchmark() — module-level timer facade."""

    _inst = _Benchmark()

    @classmethod
    def begin(cls):
        cls._inst.begin()

    @classmethod
    def step(cls, num_samples=None):
        cls._inst.step(num_samples)

    @classmethod
    def step_info(cls, unit=None):
        return cls._inst.step_info(unit)

    @classmethod
    def reset(cls):
        cls._inst.reset()


__all__ = [
    "Profiler",
    "ProfilerState",
    "ProfilerTarget",
    "TracerEventType",
    "RecordEvent",
    "make_scheduler",
    "export_chrome_tracing",
    "export_protobuf",
    "load_profiler_result",
    "benchmark",
]


class SortedKeys(enum.Enum):
    """Summary-table sort orders (reference profiler/profiler_statistic.py)."""

    CPUTotal = 0
    CPUAvg = 1
    CPUMax = 2
    CPUMin = 3
    GPUTotal = 4
    GPUAvg = 5
    GPUMax = 6
    GPUMin = 7


class SummaryView(enum.Enum):
    """Summary report views (reference profiler/profiler.py SummaryView)."""

    DeviceView = 0
    OverView = 1
    ModelView = 2
    DistributedView = 3
    KernelView = 4
    OperatorView = 5
    MemoryView = 6
    MemoryManipulationView = 7
    UDFView = 8
