"""Live scrape endpoint: /metrics (Prometheus text) + /healthz (JSON).

The textfile sink covers node-exporter setups; a real cluster scrapes HTTP.
One stdlib ThreadingHTTPServer on FLAGS_metrics_port (0 = disabled; an
ephemeral port is picked when constructed with port=0 explicitly, for
tests), serving:

  * GET /metrics  — the registry rendered through sinks.prometheus_text,
                    always fresh (memory gauges refreshed per scrape);
  * GET /healthz  — {ok, status, step, last_step_age_s, anomalies_recent,
                    stragglers} with HTTP 200 when healthy and 503 when the
                    run is stale (no step for `stale_after_s`) or anomalous
                    in the last few minutes — load-balancer semantics, body
                    says why.

The server thread is a daemon reading shared singletons; it holds no lock
while rendering beyond the registry's own per-metric locks, so scraping
cannot stall a training step.
"""
from __future__ import annotations

import json
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any, Dict, Optional

from . import memory, sinks, telemetry
from .registry import default_registry
from ..core.flags import define_flag, get_flag

define_flag(
    "metrics_port", 0,
    "Serve /metrics (Prometheus text) and /healthz (JSON run health) on "
    "this port from inside the training process; 0 disables the endpoint. "
    "Needs FLAGS_metrics=on to have anything to say.")

STALE_AFTER_S = 300.0  # healthz: no step for this long => status "stale"
ANOMALY_RECENT_S = 300.0  # healthz: anomalies within this window count


def metrics_body() -> bytes:
    """The GET /metrics response body: the whole registry as Prometheus
    text, memory gauges refreshed per scrape. Shared by this server and
    the serving front end (serving/server.py) so both scrape surfaces
    render identically."""
    try:
        memory.update_memory_gauges()  # fresh HBM per scrape
    except Exception:  # noqa: BLE001
        pass
    return sinks.prometheus_text(default_registry()).encode()


def health_snapshot(stale_after_s: float = STALE_AFTER_S) -> Dict[str, Any]:
    """The /healthz body, also usable directly (tests)."""
    now = time.time()
    tele = telemetry.get_telemetry()
    last = dict(getattr(tele, "_last", {}) or {})
    out: Dict[str, Any] = {
        "status": "ok",
        "ok": True,
        "step": last.get("step"),
        "last_step_age_s": None,
        "records_emitted": tele.records_emitted,
    }
    ts = last.get("ts")
    if ts:
        out["last_step_age_s"] = round(now - float(ts), 3)
        if out["last_step_age_s"] > float(stale_after_s):
            out["status"], out["ok"] = "stale", False
    elif tele.records_emitted == 0 and not last:
        out["status"] = "idle"  # serving before the first step is not failure
    eng = _engine()
    recent = []
    if eng is not None:
        recent = [a for a in eng.recent()
                  if now - float(a.get("ts", 0)) <= ANOMALY_RECENT_S]
    out["anomalies_recent"] = len(recent)
    if recent:
        out["status"], out["ok"] = "anomalous", False
        out["last_anomaly"] = {k: v for k, v in recent[-1].items()
                               if k in ("kind", "step", "value")}
    from . import flight_recorder as _fr

    snap = _fr.cluster_snapshot()
    if snap:
        out["stragglers"] = snap.get("flagged", {})
    return out


_engine_ref: Optional[Any] = None


def _engine():
    return _engine_ref


def set_health_engine(engine) -> None:
    """Point /healthz at the live AnomalyEngine (ResilientTrainer does)."""
    global _engine_ref
    _engine_ref = engine


class _Handler(BaseHTTPRequestHandler):
    server_version = "paddle_tpu_metrics/1.0"

    def do_GET(self):  # noqa: N802 — BaseHTTPRequestHandler contract
        path = self.path.split("?", 1)[0]
        try:
            if path == "/metrics":
                self._reply(200, metrics_body(),
                            "text/plain; version=0.0.4; charset=utf-8")
            elif path in ("/healthz", "/health"):
                snap = health_snapshot()
                body = json.dumps(snap).encode()
                self._reply(200 if snap["ok"] or snap["status"] == "idle"
                            else 503, body, "application/json")
            else:
                self._reply(404, b'{"error": "not found"}',
                            "application/json")
        except (BrokenPipeError, ConnectionResetError):
            pass

    def _reply(self, code: int, body: bytes, ctype: str) -> None:
        self.send_response(code)
        self.send_header("Content-Type", ctype)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def log_message(self, fmt, *args):  # scrapes must not spam stderr
        pass


class MetricsServer:
    """Owns the HTTP server + its daemon thread."""

    def __init__(self, port: int = 0, host: str = "0.0.0.0"):
        self._httpd = ThreadingHTTPServer((host, int(port)), _Handler)
        self._httpd.daemon_threads = True
        self.port = int(self._httpd.server_address[1])
        self.host = host
        self._thread = threading.Thread(
            target=self._httpd.serve_forever, kwargs={"poll_interval": 0.25},
            name="metrics-http", daemon=True)
        self._thread.start()

    def stop(self) -> None:
        self._httpd.shutdown()
        self._httpd.server_close()
        self._thread.join(timeout=5)

    def __repr__(self):  # pragma: no cover
        return f"MetricsServer(port={self.port})"


_server: Optional[MetricsServer] = None
_server_lock = threading.Lock()


def start_metrics_server(port: Optional[int] = None) -> MetricsServer:
    """Start (or return) the process-wide server. port=None reads
    FLAGS_metrics_port; port=0 binds an ephemeral port (tests)."""
    global _server
    with _server_lock:
        if _server is not None:
            return _server
        if port is None:
            port = int(get_flag("metrics_port"))
        _server = MetricsServer(port)
        return _server


def maybe_start_from_flags() -> Optional[MetricsServer]:
    """FLAGS_metrics_port > 0 => the server; else None. Safe to call every
    run start — idempotent, and bind errors degrade to a warning event, not
    a dead training job."""
    p = int(get_flag("metrics_port"))
    if p <= 0:
        return None
    try:
        return start_metrics_server(p)
    except OSError as e:
        telemetry.get_telemetry().event(
            "metrics_server_error", port=p, error=f"{type(e).__name__}: {e}")
        return None


def reset() -> None:
    """Stop and drop the server + health engine (tests / reset_all)."""
    global _server, _engine_ref
    with _server_lock:
        if _server is not None:
            try:
                _server.stop()
            except Exception:  # noqa: BLE001 — teardown best-effort
                pass
            _server = None
    _engine_ref = None
